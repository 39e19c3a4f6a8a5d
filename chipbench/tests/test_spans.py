"""The gossip round's spans and scopes on the tiny CPU cell: each phase
once a round, inside its ``slot.round``, and not a bit of difference to
what the round computes."""

import contextlib

import jax
import numpy as np

from chipbench import harness
from chipbench.tests import tiny
from repro import obs

ROUNDS = 3
#: in the order the round runs them
PHASES = ("overlay.step", "overlay.commit", "slot.batch", "slot.step",
          "slot.mix", "slot.loss_wait", "slot.record")
#: only in rounds that rebuild the schedule or change membership
SOMETIMES = ("overlay.rebuild", "slot.apply_plan")
SCOPED = ("repro.launch.steps", "repro.models.ssm", "repro.models.attention")


def _run(bus=None):
    loop = harness.build_trainer(tiny.cell("ssm"), 11, jax.devices())
    with obs.telemetry(bus) if bus is not None else obs.disabled():
        loop.run(ROUNDS)
    return loop


def _interval(e):
    return e.t - e.attrs["ms"] / 1e3, e.t


def test_every_round_emits_each_phase_once_inside_its_round():
    bus = obs.Telemetry()
    loop = _run(bus)
    by_round = {}
    for e in bus.events:
        by_round.setdefault(e.attrs["round"], []).append(e)
    assert sorted(by_round) == [r.step for r in loop.records]
    for evs in by_round.values():
        names = [e.name for e in evs]
        assert {n: names.count(n) for n in PHASES + ("slot.round",)} == {
            n: 1 for n in PHASES + ("slot.round",)}
        assert set(names) <= set(PHASES + SOMETIMES + ("slot.round",))
        (outer,) = [e for e in evs if e.name == "slot.round"]
        lo, hi = _interval(outer)
        for e in evs:
            a, b = _interval(e)
            # the events' ms are rounded to 0.1 us
            assert lo - 1e-6 <= a <= b <= hi + 1e-6, e
        ends = [_interval(e)[1] for n in PHASES for e in evs if e.name == n]
        assert ends == sorted(ends)


def test_spans_and_scopes_change_no_bit_and_add_no_trace(monkeypatch):
    runs = {"off": _run(), "on": _run(obs.Telemetry())}
    for name in SCOPED:
        monkeypatch.setattr(f"{name}.scope",
                            lambda _name: contextlib.nullcontext())
    runs["unscoped"] = _run()
    losses = {k: [r.loss for r in loop.records] for k, loop in runs.items()}
    assert losses["on"] == losses["off"] == losses["unscoped"]
    assert all(np.isfinite(losses["off"]))
    for k in ("on", "unscoped"):
        for a, b in zip(jax.tree.leaves(runs[k].params),
                        jax.tree.leaves(runs["off"].params)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert all(loop.trace_count.retraces == 0 for loop in runs.values())

