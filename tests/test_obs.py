"""The unified telemetry plane (ISSUE 8): :mod:`repro.obs`.

Pinned here:

* the **bus contract** — counters/gauges/histograms/spans/events on a
  monotonic clock; the :data:`~repro.obs.NULL` singleton is the
  process-global default, every method a no-op, and the
  enable/disable/scoped-context plumbing restores state exactly;
* the **round ledger** — field routing (unknown kwargs → ``extra``),
  per-record bus counter deltas, strict-JSON JSONL export, summary and
  terminal table;
* **counting_jit edge cases** — nested jit counts the inlined trace,
  ``static_argnums``/``donate_argnums`` forward to ``jax.jit`` with
  jax's own cache semantics, grouped ``G > 1`` masked mixers stay
  zero-retrace under mask changes;
* the **ISSUE 8 acceptance run** — a grouped capacity-mode churn loop
  (8-device mesh, G = 2, ``codec="int8-block"``) produces a ledger
  where every round records wire bytes, a zero retrace delta after
  warmup, cache hit/miss, and repair/commit latency — and writes valid
  JSONL;
* **zero impact when disabled** — the same loop under
  :func:`repro.obs.disabled` computes identical losses at zero
  retraces, and the instrumented loops add no trace when enabled.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core.mixing import build_permute_schedule
from repro.dist.compat import make_client_mesh
from repro.dist.sync import global_mixer
from repro.obs import (NULL, NullTelemetry, RoundLedger, Telemetry,
                       annotation, capture, disabled, get_round_ledger,
                       get_telemetry, round_ledger, scope, set_telemetry,
                       telemetry)
from repro.runtime.loop import TraceCount, counting_jit


# --------------------------------------------------------------------------
# The bus
# --------------------------------------------------------------------------

def test_bus_instruments():
    bus = Telemetry()
    bus.count("overlay.swaps")
    bus.count("overlay.swaps", 2)
    bus.gauge("slot.num_alive", 7)
    bus.gauge("slot.num_alive", 5)
    bus.observe("overlay.rebuild_ms", 2.0)
    bus.observe("overlay.rebuild_ms", 4.0)
    bus.event("churn", node=3)
    assert bus.counters == {"overlay.swaps": 3}
    assert bus.gauges == {"slot.num_alive": 5.0}
    h = bus.histograms["overlay.rebuild_ms"]
    assert (h.count, h.total, h.min, h.max, h.mean) == (2, 6.0, 2.0, 4.0, 3.0)
    assert bus.events[0].name == "churn" and bus.events[0].attrs == {"node": 3}
    s = bus.summary()
    assert s["counters"]["overlay.swaps"] == 3
    assert s["histograms"]["overlay.rebuild_ms"]["mean"] == 3.0
    assert s["num_events"] == 1


def test_bus_span_times_into_histogram():
    bus = Telemetry()
    with bus.span("overlay.commit"):
        pass
    h = bus.histograms["overlay.commit.ms"]
    assert h.count == 1 and h.min >= 0.0
    # attrs promote the span to an event too
    with bus.span("overlay.commit", slot=2):
        pass
    assert bus.events and bus.events[0].attrs["slot"] == 2


def test_nested_spans_inherit_the_open_spans_attributes():
    bus = Telemetry()
    with bus.span("slot.round", round=7):
        with bus.span("slot.step"):
            pass
        with bus.span("overlay.step", epoch=2):
            pass
    with bus.span("slot.round"):
        pass
    got = [(e.name, {k: v for k, v in e.attrs.items() if k != "ms"})
           for e in bus.events]
    assert got == [("slot.step", {"round": 7}),
                   ("overlay.step", {"round": 7, "epoch": 2}),
                   ("slot.round", {"round": 7})]
    assert bus.histograms["slot.round.ms"].count == 2


def test_bus_event_cap_drops_not_grows():
    bus = Telemetry(max_events=2)
    for i in range(5):
        bus.event("e", i=i)
    assert len(bus.events) == 2 and bus.dropped_events == 3
    assert bus.summary()["dropped_events"] == 3


def test_null_bus_is_inert_and_default():
    assert get_telemetry() is NULL
    assert not NULL.enabled and Telemetry().enabled
    NULL.count("x")
    NULL.gauge("x", 1)
    NULL.observe("x", 1)
    NULL.event("x", a=1)
    with NULL.span("x"):
        pass
    assert NULL.snapshot() == {} and NULL.summary() == {}
    assert isinstance(NULL, NullTelemetry)


def test_enable_disable_and_scoped_context_restore():
    assert get_telemetry() is NULL
    bus = obs.enable()
    try:
        assert get_telemetry() is bus and bus.enabled
    finally:
        obs.disable()
    assert get_telemetry() is NULL
    with telemetry() as scoped:
        assert get_telemetry() is scoped
        with telemetry(Telemetry()) as inner:
            assert get_telemetry() is inner
        assert get_telemetry() is scoped
    assert get_telemetry() is NULL
    # set_telemetry returns the previous bus; None restores NULL
    prev = set_telemetry(bus)
    assert prev is NULL and get_telemetry() is bus
    set_telemetry(None)
    assert get_telemetry() is NULL


# --------------------------------------------------------------------------
# The round ledger
# --------------------------------------------------------------------------

def test_ledger_field_routing_and_counter_deltas():
    bus = Telemetry()
    led = RoundLedger(bus=bus)
    bus.count("overlay.cache_misses")
    r0 = led.record(round=0, loop="t", loss=1.0, my_extra=42)
    assert r0.loss == 1.0 and r0.extra["my_extra"] == 42
    assert r0.extra["overlay.cache_misses"] == 1
    bus.count("overlay.cache_hits", 3)
    r1 = led.record(round=1, loop="t")
    # deltas, not totals: the miss from round 0 does not reappear
    assert r1.extra == {"overlay.cache_hits": 3}
    assert len(led) == 2


def test_ledger_jsonl_roundtrip_strict_json(tmp_path):
    led = RoundLedger(bus=NULL)
    led.record(round=0, loop="t", loss=float("nan"), joined=(5, 6),
               wire_bytes_per_client=128.0)
    led.record(round=1, loop="t", loss=0.25, left=(5,))
    path = tmp_path / "rounds.jsonl"
    assert led.to_jsonl(path) == 2
    rows = [json.loads(line) for line in path.read_text().splitlines()]
    assert rows[0]["loss"] is None          # NaN → null, strict JSON
    assert rows[0]["joined"] == [5, 6]
    assert rows[0]["wire_bytes_per_client"] == 128.0
    assert rows[1]["loss"] == 0.25 and rows[1]["left"] == [5]


def test_ledger_summary_and_table():
    led = RoundLedger(bus=NULL)
    for r in range(25):
        led.record(round=r, loop="slot", num_alive=6, participating=6,
                   loss=1.0 / (r + 1), wire_bytes_per_client=1000.0,
                   payload_bytes_per_client=4000.0, retraces=1,
                   swapped=(r == 3), rebuilt=(r == 3), cache_hit=(r == 9),
                   joined=(100,) if r == 3 else (), repair_ms=2.0)
    s = led.summary()
    assert s["rounds"] == 25 and s["loop"] == "slot"
    assert s["swaps"] == 1 and s["cache_hits"] == 1 and s["joins"] == 1
    assert s["wire_reduction"] == 4.0
    assert s["final_loss"] == 1.0 / 25
    table = led.summary_table()
    assert "earlier rounds elided" in table     # capped at last 20
    assert "wire_mb/client" in table
    assert table.count("\n") >= 22


def test_ledger_global_context_and_disabled():
    assert get_round_ledger() is None
    with round_ledger() as led:
        assert get_round_ledger() is led
        with telemetry(), disabled():
            assert get_round_ledger() is None
            assert get_telemetry() is NULL
        assert get_round_ledger() is led
    assert get_round_ledger() is None


# --------------------------------------------------------------------------
# Profiling wrappers
# --------------------------------------------------------------------------

def test_scope_annotation_capture_are_harmless():
    with scope("test.scope"), annotation("test.annotation", step=1):
        x = jnp.ones((4,)) + 1
    np.testing.assert_array_equal(np.asarray(x), 2.0)
    with capture(None):                   # falsy log_dir → no-op
        pass

    @jax.jit
    def f(v):
        with scope("test.inner"):
            return v * 2
    np.testing.assert_array_equal(np.asarray(f(x)), 4.0)


def test_capture_writes_profile(tmp_path):
    log_dir = tmp_path / "prof"
    with capture(log_dir):
        jax.block_until_ready(jnp.arange(8) * 2)
    assert log_dir.exists() and any(log_dir.rglob("*"))


def _host_event_names(log_dir):
    from jax.profiler import ProfileData
    (path,) = log_dir.rglob("*.xplane.pb")
    data = ProfileData.from_file(str(path))
    return [e.name for plane in data.planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events]


def test_spans_land_on_the_profile_host_plane_with_the_bus_on_or_off(
        tmp_path):
    bus = Telemetry()
    with capture(tmp_path / "prof"):
        with bus.span("slot.x", round=1):
            jax.block_until_ready(jnp.arange(8) * 2)
        with NULL.span("slot.y"):
            jax.block_until_ready(jnp.arange(8) * 3)
    names = _host_event_names(tmp_path / "prof")
    assert names.count("slot.x") == 1 and names.count("slot.y") == 1
    assert bus.histograms["slot.x.ms"].count == 1
    # the disabled bus kept no state
    assert not vars(NULL)


def test_the_compile_cache_key_covers_the_named_scopes(tmp_path,
                                                       monkeypatch):
    """An executable compiled without a scope must not be found again
    for the scoped program: its ops would reach the profiler unnamed."""
    from repro.launch.compile_cache import ROOT, enable_compile_cache
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    keys = ("jax_compilation_cache_include_metadata_in_key",
            "jax_hlo_source_file_canonicalization_regex")
    before = {k: getattr(jax.config, k) for k in keys}
    try:
        assert enable_compile_cache() == str(tmp_path)
        assert getattr(jax.config, keys[0]) is True
        # source files enter the key relative to the checkout's root, so
        # a second checkout of the same code finds the same entries
        text = jax.jit(lambda x: x * 3.0).lower(1.0).as_text(
            debug_info=True)
        assert '"tests/test_obs.py"' in text
        assert ROOT + os.sep not in text
    finally:
        for k, v in before.items():
            jax.config.update(k, v)


# --------------------------------------------------------------------------
# counting_jit edge cases
# --------------------------------------------------------------------------

def test_counting_jit_nested_jit_counts_inlined_trace():
    inner_fn, inner = counting_jit(lambda x: x + 1)
    outer_fn, outer = counting_jit(lambda x: inner_fn(x) * 2)
    assert np.asarray(outer_fn(jnp.float32(3.0))) == 8.0
    outer_fn(jnp.float32(4.0))
    # one outer trace; the inner body traced once, inlined into it
    assert outer.traces == 1 and inner.traces == 1
    # standalone call with the same aval hits the shared jit cache
    inner_fn(jnp.float32(1.0))
    assert inner.traces == 1 and inner.retraces == 0
    # a new shape is a genuine retrace
    inner_fn(jnp.ones((2,), jnp.float32))
    assert inner.traces == 2 and inner.retraces == 1


def test_counting_jit_static_argnums_trace_per_value():
    fn, count = counting_jit(lambda x, k: x * k, static_argnums=(1,))
    fn(jnp.float32(1.0), 2)
    fn(jnp.float32(2.0), 2)     # same static value: cached
    assert count.traces == 1
    fn(jnp.float32(1.0), 3)     # new static value: its own trace
    assert count.traces == 2 and count.retraces == 1


def test_counting_jit_donated_args_single_trace():
    fn, count = counting_jit(lambda x: x + 1, donate_argnums=(0,))
    x = jnp.arange(4, dtype=jnp.float32)
    for _ in range(3):
        x = fn(x)               # donation reuses the buffer, no retrace
    np.testing.assert_array_equal(np.asarray(x), np.arange(4) + 3)
    assert count.traces == 1 and count.retraces == 0


def test_counting_jit_grouped_masked_mixer_zero_retrace():
    """The G > 1 global fused mixer under changing runtime masks: one
    trace, every mask a cache hit."""
    n, G = 8, 2
    sched = build_permute_schedule(n, 2)
    mixer = global_mixer("fedlay", sched, masked=True,
                         clients_per_device=G, fuse="flat")
    fn, count = counting_jit(mixer)
    buf = {"w": jnp.asarray(np.random.default_rng(0)
                            .normal(size=(n, 48)).astype(np.float32))}
    for alive in ([1] * 8, [1, 1, 0, 1, 1, 1, 1, 0], [0, 1] * 4):
        out = fn(buf, jnp.asarray(alive, jnp.float32))
        assert np.isfinite(np.asarray(out["w"])).all()
    assert count.traces == 1 and count.retraces == 0


# --------------------------------------------------------------------------
# Loop integration
# --------------------------------------------------------------------------

def _make_sim(n=12, L=2, seed=0):
    from repro.core.ndmp import Simulator
    sim = Simulator(num_spaces=L, latency=0.05, heartbeat_period=0.5,
                    probe_period=1.0, seed=seed)
    sim.seed_network(list(range(n)))
    return sim


def _toy_harness(dim=24):
    def make_params(u):
        w = np.random.default_rng(u).normal(size=dim).astype(np.float32)
        return {"w": jnp.asarray(w)}

    def make_batch(node_ids, step):
        rows = [np.random.default_rng(abs(hash((u, step))) % 2**32)
                .normal(size=dim).astype(np.float32) for u in node_ids]
        return {"x": jnp.asarray(np.stack(rows))}

    def base_step(params, opt_state, batch):
        w, x = params["w"], batch["x"]
        loss = jnp.mean((w - x) ** 2, axis=-1)
        return {"w": w - 0.05 * 2.0 * (w - x) / dim}, opt_state, \
            {"loss": loss}
    return make_params, make_batch, base_step


_CHURN = [(2.5, "fail", 1), (4.5, "fail", 3),
          (6.5, "join", 100, 0), (8.5, "join", 101, 0)]


@pytest.mark.multi_device
def test_acceptance_grouped_codec_churn_round_ledger(multi_device, tmp_path):
    """The ISSUE 8 acceptance pin: a churn run over the int8-block
    codec (G = 2, 8-device mesh) produces a round-ledger JSONL where
    every round records wire bytes, its retrace delta (0 after warmup),
    cache hit/miss, and repair/commit latency."""
    from repro.optim.optimizers import sgd
    from repro.overlay import ChurnTrace, OverlayController
    from repro.runtime import SlotTrainLoop, counting_jit, masked_local_step

    make_params, make_batch, base_step = _toy_harness()
    mesh = make_client_mesh(8, "data")
    ctl = OverlayController(_make_sim(n=12), capacity=16,
                            clients_per_device=2, codec="int8-block",
                            double_buffered=True)
    sjit, scount = counting_jit(masked_local_step(base_step))
    bus = Telemetry()
    led = RoundLedger(bus=bus)
    loop = SlotTrainLoop(
        ctl, local_step=sjit, make_params=make_params, optimizer=sgd(0.0),
        make_batch=make_batch, jit_local_step=False, mesh=mesh,
        telemetry=bus, ledger=led, trace_count=scount)
    recs = loop.run(12, trace=ChurnTrace.scripted(_CHURN))

    assert len(led) == len(recs) == 12
    rows = led.rows
    # data plane: every round prices the codec wire, and the payload
    # (uncompressed f32 image) shows the ~4x int8 wire reduction
    assert all(r.wire_bytes_per_client > 0 for r in rows)
    assert all(r.payload_bytes_per_client > 3.5 * r.wire_bytes_per_client
               for r in rows)
    # zero-retrace guarantee, observed live: one warmup trace, then 0
    assert rows[0].retrace_delta == 1
    assert all(r.retrace_delta == 0 for r in rows[1:])
    assert rows[-1].retraces == 0 and scount.traces == 1
    # control plane joined in: churn membership, swaps, cache traffic,
    # repair/commit latency on the rounds that rebuilt
    assert sum(len(r.joined) for r in rows) == 2
    assert sum(len(r.left) for r in rows) == 2
    swapped = [r for r in rows if r.swapped]
    assert swapped and any(r.cache_hit for r in rows)
    assert all(r.repair_ms > 0 for r in rows if r.rebuilt)
    assert all(r.repair_ms == 0 for r in rows if not r.rebuilt)
    assert all(r.commit_ms >= 0 for r in rows)
    assert any(r.commit_ms > 0 for r in swapped)
    # the bus counted the same control-plane events the ledger flagged
    assert bus.counters["slot.steps"] == 12
    assert bus.counters["overlay.churn_joins"] == 2
    assert bus.counters["overlay.churn_leaves"] == 2
    assert bus.counters["overlay.swaps"] == len(swapped)
    assert bus.counters.get("overlay.cache_hits", 0) == ctl.cache.hits > 0
    # and the JSONL export is strict JSON, row per round
    path = tmp_path / "ledger.jsonl"
    assert led.to_jsonl(path) == 12
    parsed = [json.loads(line) for line in path.read_text().splitlines()]
    assert [p["round"] for p in parsed] == [r.round for r in rows]
    assert all(p["wire_bytes_per_client"] > 0 for p in parsed)


@pytest.mark.multi_device
def test_disabled_telemetry_is_zero_impact(multi_device):
    """The same grouped codec churn run fully disabled vs fully on:
    identical losses, zero retraces both ways."""
    from repro.optim.optimizers import sgd
    from repro.overlay import ChurnTrace, OverlayController
    from repro.runtime import SlotTrainLoop, counting_jit, masked_local_step

    make_params, make_batch, base_step = _toy_harness()

    def run_arm(enable):
        mesh = make_client_mesh(8, "data")
        ctl = OverlayController(_make_sim(n=12), capacity=16,
                                clients_per_device=2, codec="int8-block")
        sjit, scount = counting_jit(masked_local_step(base_step))
        loop = SlotTrainLoop(
            ctl, local_step=sjit, make_params=make_params,
            optimizer=sgd(0.0), make_batch=make_batch,
            jit_local_step=False, mesh=mesh, trace_count=scount)
        if enable:
            with telemetry(), round_ledger() as led:
                recs = loop.run(10, trace=ChurnTrace.scripted(_CHURN))
            assert len(led) == 10
        else:
            with disabled():
                recs = loop.run(10, trace=ChurnTrace.scripted(_CHURN))
        assert scount.retraces == 0
        return [r.loss for r in recs]

    np.testing.assert_allclose(run_arm(False), run_arm(True), rtol=0, atol=0)


def test_churn_loop_ledger_shows_restack_retrace_tax():
    """ChurnTrainLoop re-stacks per alive count: its ledger's retrace
    deltas light up at every new alive count — the tax the slot loop's
    ledger shows as zero."""
    from repro.optim.optimizers import sgd
    from repro.overlay import ChurnTrace, ChurnTrainLoop, OverlayController

    make_params, make_batch, base_step = _toy_harness()

    def restack_step(params, opt_state, batch):
        p, o, m = base_step(params, opt_state, batch)
        return p, o, {"loss": jnp.mean(m["loss"])}

    bus = Telemetry()
    led = RoundLedger(bus=bus)
    loop = ChurnTrainLoop(
        OverlayController(_make_sim(n=6)), local_step=restack_step,
        make_params=make_params, optimizer=sgd(0.0), make_batch=make_batch,
        telemetry=bus, ledger=led)
    loop.run(10, trace=ChurnTrace.scripted(_CHURN))
    rows = led.rows
    assert len(rows) == 10 and all(r.loop == "churn" for r in rows)
    distinct_alive = len({r.num_alive for r in rows})
    assert distinct_alive >= 3
    # one fresh trace per distinct alive count, attributed to the round
    # where that count first appeared
    assert sum(r.retrace_delta for r in rows) == distinct_alive
    assert rows[-1].retraces == distinct_alive - 1
    assert all(r.wire_bytes_per_client > 0 for r in rows)
    assert bus.counters["churn.steps"] == 10
    assert bus.counters["churn.remaps"] == sum(
        1 for r in rows if r.joined or r.left)


def test_cohort_loop_reports_to_global_ledger():
    from repro.scale import CohortStreamLoop, VectorSimulator

    sim = VectorSimulator(num_spaces=2, latency=0.05, heartbeat_period=0.5,
                          probe_period=1.0)
    sim.seed_network(range(64))
    loop = CohortStreamLoop(
        sim, capacity=8, cohort_size=8,
        make_params=lambda u: np.random.default_rng(u)
        .random(16).astype(np.float32), seed=3)
    with telemetry() as bus, round_ledger() as led:
        loop.run(6)
    rows = led.rows
    assert len(rows) == 6 and all(r.loop == "cohort" for r in rows)
    assert all(r.wire_bytes_per_client > 0 for r in rows)
    assert all(r.retrace_delta == 0 for r in rows[1:])
    assert all(r.repair_ms > 0 for r in rows)       # remap cost, per round
    assert all(r.extra["restored"] + r.extra["donor_seeded"]
               + r.extra["fresh"] == len(r.joined) for r in rows)
    assert bus.counters["cohort.rounds"] == 6
    assert bus.histograms["cohort.remap_ms"].count == 6


def test_engine_run_scoped_telemetry_kwargs():
    from repro.core.dfl import Engine
    from repro.data.noniid import shard_partition
    from repro.data.synthetic import mnist_like
    from repro.models.small import MLPTask

    data = mnist_like(n_train=160, n_test=80, seed=0)
    part = shard_partition(data.y_train, num_clients=6, shards_per_client=3,
                           seed=0)
    task = MLPTask(data, part, hidden=8, local_steps=1, batch=16)
    bus = Telemetry()
    led = RoundLedger(bus=bus)
    res = Engine().run(task, "fedlay", total_time=6.0, model_bytes=1000,
                       telemetry=bus, ledger=led)
    assert res.final_mean_acc > 0
    # the scope was per-run: globals restored afterwards
    assert get_telemetry() is NULL and get_round_ledger() is None
    assert bus.counters["engine.evals"] == len(led)
    assert bus.counters["engine.msgs_sent"] == pytest.approx(
        res.messages_per_client * 6)
    assert all(r.loop == "engine" for r in led.rows)
    assert led.rows[-1].num_alive == 6
    # per-snapshot byte deltas sum to the run's per-client mean
    total = sum(r.wire_bytes_per_client for r in led.rows)
    assert total == pytest.approx(res.comm_bytes_per_client)
