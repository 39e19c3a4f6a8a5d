"""The reduction from a trace to per-layer numbers, on synthetic traces."""

import types

import ml_dtypes
import numpy as np
import pytest

from chipbench import harness, trace as tr


def test_merge_unions_overlapping_intervals():
    assert tr.merge([(5, 7), (0, 2), (1, 3), (6, 9), (9, 10)]) == [
        (0, 3), (5, 10)]
    assert tr.length([(0, 2), (1, 3), (10, 11)]) == 4


@pytest.mark.parametrize("intervals,cover,left", [
    ([(0, 10)], [(2, 3), (5, 7)], [(0, 2), (3, 5), (7, 10)]),
    ([(0, 10)], [(-5, 20)], []),
    ([(0, 4), (6, 10)], [(3, 7)], [(0, 3), (7, 10)]),
    ([(0, 4)], [], [(0, 4)]),
])
def test_subtract_leaves_the_uncovered_parts(intervals, cover, left):
    assert tr.subtract(intervals, cover) == left


def _device(ops, modules=(), async_ops=()):
    return tr.Device(modules=[tr.Event(*m) for m in modules],
                     ops=[tr.op_event(f"%{o[0]} = f32[8] {o[0].split('.')[0]}"
                                      f"(f32[8] %x)", o[1], o[2]) for o in ops],
                     async_ops=[tr.op_event(o[0], o[1], o[2])
                                for o in async_ops])


def _ctx(devices, window=(0, 100), spans=(), rounds=2, **kw):
    spans = [tr.Event("chipbench.window", *window)] + [
        tr.Event(*s) for s in spans]
    fields = dict(cell=None, rounds=rounds, tokens_per_s=0.0, peaks={},
                  step_module="jit_counted", mix_module="jit_mix",
                  family=None, gather_mix_least_s=0.0,
                  gather_mix_bound="memory", units={})
    fields.update(kw)
    return tr.Context(trace=tr.Trace(devices, spans), **fields)


def test_busy_is_the_union_and_idle_its_complement():
    dev = _device([("a", 0, 30), ("b", 20, 40), ("c", 60, 70),
                   ("d", 90, 130)])
    ctx = _ctx([dev], window=(0, 100))
    # busy: [0, 40] + [60, 70] + [90, 100] = 60 of 100
    assert ctx.busy_s == pytest.approx(60e-9)
    idle = harness.metric_reader("device_idle_share")(ctx)
    assert idle == pytest.approx(40.0)


def test_busy_is_averaged_over_devices():
    ctx = _ctx([_device([("a", 0, 50)]), _device([("a", 0, 100)])])
    assert ctx.busy_s == pytest.approx(75e-9)


def test_program_time_by_name_per_round():
    mods = [("jit_counted(1)", 0, 30), ("jit_mix(2)", 30, 35),
            ("jit_counted(1)", 40, 70), ("jit_mix(2)", 70, 80),
            ("jit_counted(1)", 95, 120)]
    ctx = _ctx([_device([], mods)], window=(0, 100), rounds=2)
    step = harness.metric_reader("step_ms")(ctx)
    mix = harness.metric_reader("mix_ms")(ctx)
    assert step == pytest.approx((30 + 30 + 5) / 2 / 1e6)
    assert mix == pytest.approx((5 + 10) / 2 / 1e6)


def test_a_program_that_never_ran_reads_nothing():
    ctx = _ctx([_device([("x", 0, 10)], [("jit_other", 0, 10)])])
    assert harness.metric_reader("mix_ms")(ctx) is None
    assert harness.metric_reader("gather_mix_roofline")(ctx) is None
    assert _exposed_collective_ms(ctx) is None


def _exposed_collective_ms(ctx):
    """Exposed collective time of the mixer per round, on the fullest
    device: what a cell whose clients span chips reads."""
    per = ctx.per_device(lambda d, lo, hi: tr.exposed_collective_ns(
        d, ctx.mix_module, lo, hi))
    return tr.nonempty(max(per) / ctx.rounds / 1e6)


def test_exposed_collective_time_leaves_out_overlapped_compute():
    mods = [("jit_mix(3)", 10, 60)]
    ops = [("all-gather.1", 10, 30),       # 10..20 overlapped by fusion
           ("fusion.2", 10, 20),
           ("all-gather-start.3", 40, 50),  # fully exposed
           ("copy.4", 50, 60),
           ("all-reduce.5", 70, 80)]        # outside the mixer program
    ctx = _ctx([_device(ops, mods)], rounds=1)
    got = _exposed_collective_ms(ctx)
    assert got == pytest.approx((10 + 10) / 1e6)


def test_exposed_collective_time_is_read_on_the_fullest_device():
    mods = [("jit_mix(3)", 0, 100)]
    a = _device([("all-gather.1", 0, 10)], mods)
    b = _device([("all-gather.1", 0, 40)], mods)
    ctx = _ctx([a, b], rounds=2)
    got = _exposed_collective_ms(ctx)
    assert got == pytest.approx(40 / 2 / 1e6)


def test_gather_mix_least_time_and_roofline_share():
    # two clients; one bf16 and one f32 leaf of 1000 elements a row
    params = {"a": np.zeros((2, 10, 100), ml_dtypes.bfloat16),
              "b": np.zeros((2, 1000), np.float32)}
    peaks = {"hbm_bytes_per_s": 1e9, "bf16_flops_per_s": 1e12}
    least, bound = tr.gather_mix_least_s(params, peaks)
    nbytes = (2 * 2 * 1000 * 2 + 16) + (2 * 2 * 1000 * 4 + 16)
    assert bound == "memory"
    assert least == pytest.approx(nbytes / 1e9)
    ops = [("kernels.gather_mix.1", 0, 1000), ("fusion.5", 1000, 2000),
           ("kernels.gather_mix.2", 2000, 3000)]
    ctx = _ctx([_device(ops)], window=(0, 5000), rounds=1,
               gather_mix_least_s=least)
    share = harness.metric_reader("gather_mix_roofline")(ctx)
    assert share == pytest.approx(100 * least / 2000e-9)


def test_mfu_is_model_flops_over_the_chips_peak():
    fam = types.SimpleNamespace(flops_per_token=lambda m, s: 1e9)
    cell = types.SimpleNamespace(chips=4, config={"model": {}},
                                 traffic={"seq_len": 16})
    ctx = _ctx([_device([])], cell=cell, family=fam, tokens_per_s=1e4,
               peaks={"bf16_flops_per_s": 1e14})
    assert harness.metric_reader("mfu")(ctx) == pytest.approx(
        100 * 1e9 * 1e4 / 4e14)


def test_idle_gaps_are_labelled_by_the_innermost_span():
    dev = _device([("a", 0, 10), ("b", 30, 40), ("c", 45, 100)])
    spans = [("chipbench.round", 0, 50), ("chipbench.make_batch", 12, 28)]
    ctx = _ctx([dev], spans=spans)
    gaps = ctx.breakdown()["idle_gaps"]
    assert gaps[0] == ["chipbench.make_batch", pytest.approx(20e-9)]
    assert gaps[1] == ["chipbench.round", pytest.approx(5e-9)]
    ops = ctx.breakdown()["device_ops"]
    assert ops[0] == ["c (c)", pytest.approx(55e-9)]


def test_op_events_are_named_by_instruction_and_opcode():
    e = tr.op_event("%kernels.gather_mix.2 = bf16[2,8]{1,0:T(2,128)(2,1)} "
                    "custom-call(f32[2,2]{1,0:T(2,128)S(1)} %copy-done.1, "
                    "bf16[2,8] %fusion.3), custom_call_target=\"x\"", 0, 1)
    assert (e.name, e.opcode) == ("kernels.gather_mix.2", "custom-call")
    w = tr.op_event("%while.4 = (u32[]{:T(128)}, bf16[4]{0:T(1024)}) "
                    "while((u32[]{:T(128)}, bf16[4]) %tuple.3)", 0, 1)
    assert (w.name, w.opcode) == ("while.4", "while")
    f = tr.op_event("%fusion.7 = bf16[4] fusion(bf16[4] %all-gather.2)", 0, 1)
    assert not tr.is_collective(f)
    assert tr.is_collective(tr.op_event(
        "%all-gather-start.1 = (f32[4], f32[16]) all-gather-start(f32[4] "
        "%p)", 0, 1))


def test_a_while_is_not_counted_beside_its_body():
    dev = _device([("while.1", 0, 100), ("fusion.2", 10, 60),
                   ("fusion.3", 60, 90)])
    ctx = _ctx([dev])
    names = [n for n, _ in ctx.breakdown()["device_ops"]]
    assert names == ["fusion.2 (fusion)", "fusion.3 (fusion)"]
    assert ctx.busy_s == pytest.approx(100e-9)


def test_async_collective_spans_count_as_collective_time():
    mods = [("jit_mix(1)", 0, 100)]
    ops = [("all-gather-start.1", 0, 1), ("fusion.2", 0, 30),
           ("all-gather-done.1", 60, 61)]
    asyn = [("%all-gather-start.1 = (f32[4], f32[16]) all-gather-start("
             "f32[4] %p)", 0, 61)]
    ctx = _ctx([_device(ops, mods, asyn)], rounds=1)
    got = _exposed_collective_ms(ctx)
    assert got == pytest.approx(31 / 1e6)


def test_p90_is_taken_over_all_rounds():
    rounds = list(range(1, 101))
    assert harness.percentile(rounds, 90) == pytest.approx(90.1)
    assert harness.percentile([5.0], 90) == 5.0
