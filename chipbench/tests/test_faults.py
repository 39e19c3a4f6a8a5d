"""A whole run with the timed path broken underneath reads not correct.

Each test drives ``harness.run`` on a tiny cell of the family on the CPU
(the look for a chip is the command's, and is skipped here), with the
limits of the benchmark cell that the family stands for, once sound
and once for each fault the cell can have.
"""

import time

import jax
import pytest

from chipbench import harness
from chipbench.tests import tiny

#: each tiny cell with the limits of a benchmark cell; the four-chip
#: layout has no cell yet and is held to the one-chip mamba2 limits
CELLS = {"ssm": "mamba2-370m.c2.t2048", "ssm-t256": "mamba2-370m.c2.t256",
         "dense": "qwen3-4b-l3v8.c2.t2048",
         "ssm-4chip": "mamba2-370m.c2.t2048"}


def _cell(kind: str) -> harness.Cell:
    limits = harness.load_cell(CELLS[kind]).limits
    if kind == "ssm-t256":
        return tiny.cell("ssm", limits=limits)
    if kind == "ssm-4chip":
        return tiny.cell("ssm", chips=4, clients_per_chip=1, fuse=None,
                         limits=limits)
    return tiny.cell(kind, limits=limits)


def _run(cell: harness.Cell, seed: int) -> dict:
    return harness.run(cell, seed, 0.2, False, jax.devices(),
                       time.perf_counter(), log=lambda m: None)


def _state_unchanged(monkeypatch):
    import repro.runtime.masked as masked
    monkeypatch.setattr(masked, "masked_where", lambda m, new, old: old)


def _half_batch(monkeypatch):
    import repro.models.model as model
    xent = model.softmax_xent
    monkeypatch.setattr(model, "softmax_xent", lambda logits, labels: xent(
        logits[:, :logits.shape[1] // 2], labels[:, :labels.shape[1] // 2]))


def _no_exchange(monkeypatch):
    from repro.overlay import OverlayController
    monkeypatch.setattr(OverlayController, "mixer", property(
        lambda self: lambda params, mask, **kw: params))


def _double_lr(monkeypatch):
    import repro.optim.optimizers as optimizers
    adamw = optimizers.adamw
    monkeypatch.setattr(optimizers, "adamw",
                        lambda lr, **kw: adamw(2 * lr, **kw))


FAULTS = {"state_unchanged": _state_unchanged, "half_batch": _half_batch,
          "no_exchange": _no_exchange, "double_lr": _double_lr}


@pytest.mark.parametrize("kind", list(CELLS))
def test_a_sound_run_is_correct(kind):
    result = _run(_cell(kind), 2 ** 31 + 7)
    assert result["correct"], result["checks"]


@pytest.mark.parametrize("fault", list(FAULTS))
@pytest.mark.parametrize("kind", list(CELLS))
def test_a_broken_run_is_not_correct(kind, fault, monkeypatch):
    FAULTS[fault](monkeypatch)
    result = _run(_cell(kind), 2 ** 31 + 7)
    assert not result["correct"], result["checks"]
