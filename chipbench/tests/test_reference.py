"""The plain references against the program, and their FLOP counts."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import harness
from chipbench.tests import tiny

FAMILIES = ["ssm", "dense"]
BENCH = json.load(open(os.path.join(harness.ROOT, "BENCHMARK.json")))


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda c: c["name"])
def test_weights_have_the_programs_layout(entry):
    from repro.models import init_params
    cfg = harness._read_json(os.path.join(harness.ROOT, entry["file"]))
    fam = harness.family(cfg)
    want = jax.eval_shape(lambda: init_params(
        harness.arch_config(cfg), jax.random.PRNGKey(0), dtype=jnp.bfloat16))
    got = jax.eval_shape(lambda: fam.init(cfg["model"],
                                          jax.random.PRNGKey(0)))
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)


@pytest.mark.parametrize("fam", FAMILIES)
def test_reference_loss_and_gradient_match_the_program_in_f32(fam):
    from repro.models import train_loss
    cell = tiny.cell(fam)
    cfg = harness.arch_config(cell.config)
    m = cell.config["model"]
    ref = harness.family(cell.config)
    params = ref.init(m, jax.random.PRNGKey(1), dtype=jnp.float32)
    toks = jax.random.randint(jax.random.PRNGKey(2), (2, 32), 0, 300)
    labels = jax.random.randint(jax.random.PRNGKey(3), (2, 32), 0, 300)
    with jax.default_matmul_precision("highest"):
        lp, gp = jax.value_and_grad(lambda p: train_loss(
            cfg, p, {"tokens": toks, "labels": labels}, remat=False))(params)
    lr, gr = jax.value_and_grad(lambda p: ref.loss(m, p, toks, labels))(
        params)
    assert float(lp) == pytest.approx(float(lr), rel=1e-5)
    for a, b in zip(jax.tree.leaves(gp), jax.tree.leaves(gr)):
        np.testing.assert_allclose(a, b, rtol=1e-3,
                                   atol=1e-4 * float(jnp.max(jnp.abs(b))))


@pytest.mark.parametrize("fam", FAMILIES)
def test_the_control_is_the_reference_one_precision_down(fam):
    from chipbench.families import common
    cell = tiny.cell(fam)
    m = cell.config["model"]
    ref = harness.family(cell.config)
    params = ref.init(m, jax.random.PRNGKey(1), dtype=jnp.float32)
    toks = jax.random.randint(jax.random.PRNGKey(2), (1, 32), 0, 300)
    exact = float(ref.loss(m, params, toks, toks))
    low = float(ref.loss(m, params, toks, toks, q=common.fp8))
    assert exact != low and abs(exact - low) / exact < 0.05
    x = jnp.linspace(-3.0, 3.0, 101)
    assert float(jnp.max(jnp.abs(common.fp8(x) - x))) <= 3.0 / 448 * 16
    g = jax.grad(lambda a: common.dot("i,i->", a, x, common.fp8))(x)
    np.testing.assert_array_equal(g, common.fp8(x))


def test_flops_are_six_per_matmul_parameter_plus_the_sequence_terms():
    """Outside the SSD and attention terms, the model FLOPs per token are
    6 x the parameters that take part in a matmul, counted from the
    program's ``ArchConfig.param_count``."""
    for entry in BENCH["configs"]:
        cfg = harness._read_json(os.path.join(harness.ROOT, entry["file"]))
        m, arch = cfg["model"], harness.arch_config(cfg)
        fam = harness.family(cfg)
        L, d = m["num_layers"], m["d_model"]
        if cfg["family"] == "ssm":
            s = arch.ssm
            di, n, k, nh = s.d_inner(d), s.d_state, s.d_conv, s.nheads(d)
            Q = min(s.chunk, 2048)
            extra = 3 * L * (2 * k * (di + 2 * n) + 2 * Q * n + 2 * Q * di
                             + 4 * n * di)
            not_matmul = L * (d + k * (di + 2 * n) + 2 * nh + di)
        else:
            extra = 3 * L * 4 * m["num_heads"] * m["head_dim"] * 2049 / 2
            not_matmul = 2 * L * d
        matmul = arch.param_count() - not_matmul
        assert fam.flops_per_token(m, 2048) == pytest.approx(
            6 * matmul + extra, rel=1e-12)


@pytest.mark.parametrize("fam", FAMILIES)
def test_flops_agree_with_xla_on_a_reduced_forward(fam, monkeypatch):
    """XLA's count of the program's unrolled forward at a reduced size is
    the model's forward FLOPs (a third of ``flops_per_token``) to within
    what it adds: elementwise work, and attention over the whole square
    where the model counts the causal half."""
    import repro.models.model as model_mod
    from repro.models import train_loss
    monkeypatch.setattr(model_mod, "SCAN_UNROLL", True)
    cell = tiny.cell(fam, seq_len=64)
    cell.config["model"].update(d_model=256, vocab_size=512)
    if fam == "dense":
        cell.config["model"].update(d_ff=1024, num_heads=4, head_dim=64)
    else:
        cell.config["model"]["ssm"].update(headdim=64, d_state=64, chunk=32)
    cfg = harness.arch_config(cell.config)
    m = cell.config["model"]
    params = harness.family(cell.config).init(m, jax.random.PRNGKey(0),
                                              dtype=jnp.float32)
    batch = {"tokens": jnp.zeros((1, 64), jnp.int32),
             "labels": jnp.zeros((1, 64), jnp.int32)}
    cost = jax.jit(lambda p: train_loss(cfg, p, batch, remat=False)).lower(
        params).compile().cost_analysis()
    xla = float(cost["flops"]) / 64
    model = harness.family(cell.config).flops_per_token(m, 64) / 3
    assert 0.95 <= xla / model <= 1.25, xla / model
