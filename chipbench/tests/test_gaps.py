"""The arithmetic of the numbers that decide ``correct``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import harness
from chipbench.tests import tiny


def _readings(grad, delta=None):
    norms = {u: {p: float(np.linalg.norm(g)) for p, g in leaves.items()}
             for u, leaves in grad.items()}
    return harness.Readings(losses=[1.0, 1.0, 1.0], grad=grad,
                            delta=delta or norms)


def test_grad_err_reads_an_error_that_the_gap_of_norms_averages_away():
    rng = np.random.default_rng(0)
    g = {p: rng.standard_normal(1000).astype(np.float32) for p in "abc"}
    ref = _readings({0: g})
    # the same values in another order: every norm is kept, every value
    # is wrong
    got = _readings({0: {p: v[::-1].copy() for p, v in g.items()}})
    out = harness.gaps(got, ref)
    assert out["grad_gap"] < 1e-6
    assert out["grad_err"] > 1.0


def test_a_gradient_left_at_zero_reads_one():
    g = {p: np.full(10, 1.0 + i, np.float32) for i, p in enumerate("abc")}
    got = _readings({0: {p: np.zeros_like(v) for p, v in g.items()}})
    out = harness.gaps(got, _readings({0: g}))
    assert out["grad_gap"] == pytest.approx(1.0)
    assert out["grad_err"] == pytest.approx(1.0)


def test_leaves_that_move_by_round_off_alone_are_left_out():
    g = {"a": np.ones(10, np.float32), "b": np.ones(10, np.float32),
         "tiny": np.full(10, 1e-6, np.float32)}
    got = dict(g, tiny=np.full(10, 5e-6, np.float32))
    out = harness.gaps(_readings({0: got}), _readings({0: g}))
    assert out["grad_err"] == 0.0 and out["grad_gap"] == 0.0


def test_errors_are_measured_against_the_median_leaf_where_a_leaf_is_small():
    g = {"a": np.ones(100, np.float32), "b": np.ones(100, np.float32),
         "small": np.full(100, 0.01, np.float32)}
    got = dict(g, small=np.full(100, 0.02, np.float32))
    out = harness.gaps(_readings({0: got}), _readings({0: g}))
    # |0.2 - 0.1| over the median leaf's norm 10, not over its own 0.1
    assert out["grad_err"] == pytest.approx(0.01, rel=1e-5)
    assert out["grad_gap"] == pytest.approx(0.01, rel=1e-5)


def test_the_change_is_measured_from_the_clients_mean_initial_row():
    cell = tiny.cell("ssm")
    fam = harness.family(cell.config)
    keys = harness.client_keys(5, [0, 1])
    rows = [fam.init(cell.config["model"], jnp.asarray(k),
                     dtype=jnp.bfloat16) for k in keys]
    mean = jax.tree.map(lambda a, b: (a.astype(jnp.float32)
                                      + b.astype(jnp.float32)) / 2, *rows)
    stacked = jax.tree.map(lambda *r: jnp.stack(r), *rows, mean)
    out = harness.delta_leaf_norms(cell, stacked, [0, 1, 2], keys)
    assert all(v == 0.0 for v in out[2].values())
    # each initial row lies half the clients' distance from their mean
    # (leaves made alike for every client, such as norms, lie on it)
    for p, v in out[0].items():
        assert v == pytest.approx(out[1][p], rel=1e-5)
    assert sum(v > 0 for v in out[0].values()) > len(out[0]) // 2
