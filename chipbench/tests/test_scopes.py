"""The program's spans and scopes, read from synthetic ``.xplane.pb``
files written as the TPU profiler writes them: host spans on a
``/host:`` plane, device operations whose event metadata carries the
``tf_op`` stat."""

import os
import types

import pytest

from chipbench import harness, scopes, trace as tr

WINDOW = ("chipbench.window", 0, 1000)
BENCH_SPANS = [WINDOW, ("chipbench.round", 0, 500),
               ("chipbench.round", 500, 1000)]
PROGRAM_SPANS = [
    ("slot.round", 10, 490), ("overlay.step", 10, 30),
    ("overlay.rebuild", 12, 20), ("overlay.commit", 30, 35),
    ("slot.batch", 40, 60), ("slot.step", 60, 80), ("slot.mix", 80, 90),
    ("slot.loss_wait", 90, 480), ("slot.record", 480, 490),
    ("slot.round", 510, 990), ("overlay.step", 510, 540),
    ("overlay.commit", 540, 545), ("slot.batch", 550, 570),
    ("slot.step", 570, 590), ("slot.mix", 590, 600),
    ("slot.loss_wait", 600, 980), ("slot.record", 980, 990),
]
MODULES = [("jit_counted(1)", 50, 300), ("jit_mix_flat(2)", 300, 450),
           ("jit_counted(1)", 560, 800), ("jit_mix_flat(2)", 800, 950)]
FWD = "jit(counted)/step.fwd_bwd/transpose(jvp(model.ssd))/dot_general:"
OPT = "jit(counted)/step.optimizer/mul:"
# (name, opcode, start, end, tf_op)
OPS = [
    ("fusion.1", "fusion", 50, 180, FWD),
    ("fusion.9", "fusion", 180, 200,
     "jit(counted)/step.fwd_bwd/checkpoint/model.attention/dot_general:"),
    ("fusion.2", "fusion", 200, 260, OPT),
    ("select.3", "select", 260, 300, "jit(counted)/step.optimizer/select_n:"),
    ("dynamic-update-slice.4", "dynamic-update-slice", 300, 340,
     "jit(mix_flat)/flat.ravel/concatenate:"),
    ("kernels.gather_mix.5", "custom-call", 340, 400, "jit(mix_flat)/pallas:"),
    ("fusion.6", "fusion", 400, 430,
     "jit(mix_flat)/flat.unravel/convert_element_type:"),
    ("copy.7", "copy", 430, 450, ""),
    ("fusion.1", "fusion", 560, 700, FWD),
    ("while.8", "while", 695, 800, "jit(counted)/step.optimizer/while:"),
    ("fusion.2", "fusion", 700, 770, OPT),
    ("select.3", "select", 770, 800, "jit(counted)/step.optimizer/select_n:"),
    ("dynamic-update-slice.4", "dynamic-update-slice", 800, 850,
     "jit(mix_flat)/flat.ravel/concatenate:"),
    ("kernels.gather_mix.5", "custom-call", 850, 900, "jit(mix_flat)/pallas:"),
    ("fusion.6", "fusion", 900, 940,
     "jit(mix_flat)/flat.unravel/convert_element_type:"),
    ("copy.7", "copy", 940, 950, ""),
]
#: the readers of what the program labels
LABELLED = ("control_ms", "host_ms", "optimizer_ms", "fwd_bwd_ms", "ssd_ms",
            "attention_ms")
NEW = LABELLED + ("mix_ravel_ms",)
OLD = ("step_ms", "mfu", "mix_ms", "gather_mix_roofline", "device_idle_share")


def _long(name, opcode):
    return f"%{name} = f32[8] {opcode}(f32[8] %x)"


def _plane(pid, name, lines, events, stat_names=()):
    """One ``XPlane`` in text format: ``lines`` are (name, [(metadata
    name, start ns, end ns)]); ``events`` {metadata name: tf_op}."""
    ids = {n: i + 1 for i, n in enumerate(events)}
    out = [f'planes {{ id: {pid} name: "{name}"']
    for k, (line, evs) in enumerate(lines):
        out.append(f'lines {{ id: {k + 1} name: "{line}" timestamp_ns: 0')
        out += [f"events {{ metadata_id: {ids[n]} offset_ps: {a * 1000} "
                f"duration_ps: {(b - a) * 1000} }}" for n, a, b in evs]
        out.append("}")
    for n, i in ids.items():
        stats = "".join(
            f' stats {{ metadata_id: 1 str_value: "{events[n]}" }}'
            if events[n] else "")
        out.append(f'event_metadata {{ key: {i} value {{ id: {i} '
                   f'name: "{n}"{stats} }} }}')
    for i, s in enumerate(stat_names):
        out.append(f'stat_metadata {{ key: {i + 1} value {{ id: {i + 1} '
                   f'name: "{s}" }} }}')
    return " ".join(out) + " }"


def _write(directory, *, program=True):
    """A trace of two rounds; ``program=False`` leaves out what the
    program labels (its host spans and its ops' scopes), as a trace of a
    program without them holds."""
    from jax.profiler import ProfileData
    spans = BENCH_SPANS + (PROGRAM_SPANS if program else [])
    host = _plane(2, "/host:CPU", [("python", spans)],
                  {n: "" for n, _, _ in spans})
    ops = [(_long(n, op), a, b) for n, op, a, b, _ in OPS]
    meta = {_long(n, op): (s if program else "") for n, op, _, _, s in OPS}
    meta.update({n: "" for n, _, _ in MODULES})
    dev = _plane(1, "/device:TPU:0",
                 [("XLA Modules", MODULES), ("XLA Ops", ops)], meta,
                 stat_names=("tf_op",))
    raw = ProfileData.text_proto_to_serialized_xspace(host + " " + dev)
    os.makedirs(directory, exist_ok=True)
    with open(os.path.join(directory, "t.xplane.pb"), "wb") as f:
        f.write(raw)
    return directory


def _ctx(directory):
    cell = types.SimpleNamespace(chips=1, config={"model": {}},
                                 traffic={"seq_len": 16})
    fam = types.SimpleNamespace(flops_per_token=lambda m, s: 1e9)
    ctx = tr.Context(trace=tr.load(directory), cell=cell, rounds=2,
                     tokens_per_s=1e4, peaks={"bf16_flops_per_s": 1e14},
                     step_module="jit_counted", mix_module="jit_mix_flat",
                     family=fam, gather_mix_least_s=50e-9,
                     gather_mix_bound="memory", units={})
    scopes.of(ctx, directory)
    return ctx


@pytest.fixture
def traced(tmp_path):
    return _ctx(_write(str(tmp_path / "program")))


@pytest.mark.parametrize("name,want_ns", [
    # overlay.step + overlay.commit: (20 + 5) + (30 + 5), over 2 rounds
    ("control_ms", (25 + 35) / 2),
    # slot.round less slot.loss_wait: (480 - 390) + (480 - 380)
    ("host_ms", (90 + 100) / 2),
    # step.optimizer: fusion.2 + select.3 in each round; the while that
    # holds them is not counted beside them
    ("optimizer_ms", (60 + 40 + 70 + 30) / 2),
    # the step's runs less its step.optimizer ops
    ("fwd_bwd_ms", ((250 + 240) - (60 + 40 + 70 + 30)) / 2),
    # model.ssd: fusion.1, inside a transformation's parentheses
    ("ssd_ms", (130 + 140) / 2),
    # model.attention: fusion.9
    ("attention_ms", 20 / 2),
    # the mixer's runs less its gather_mix kernel
    ("mix_ravel_ms", ((150 + 150) - (60 + 50)) / 2),
])
def test_each_new_reader_gives_the_hand_computed_value(traced, name,
                                                       want_ns):
    got = harness.metric_reader(name)(traced)
    assert got == pytest.approx(want_ns / 1e6)


def test_control_time_lies_inside_host_time_and_ravel_inside_the_mixer(
        traced):
    read = {n: harness.metric_reader(n)(traced) for n in NEW + OLD}
    assert read["control_ms"] <= read["host_ms"]
    assert read["optimizer_ms"] < read["step_ms"]
    gather_ms = tr.kernel_ns(traced.trace.devices[0], "kernels.gather_mix",
                             *traced.window) / traced.rounds / 1e6
    assert read["mix_ravel_ms"] + gather_ms == pytest.approx(read["mix_ms"])
    assert read["ssd_ms"] + read["attention_ms"] <= read["fwd_bwd_ms"]
    assert read["fwd_bwd_ms"] + read["optimizer_ms"] == pytest.approx(
        read["step_ms"])


def test_the_existing_readers_read_the_same_with_and_without_the_labels(
        tmp_path):
    plain = _ctx(_write(str(tmp_path / "plain"), program=False))
    before = {n: harness.metric_reader(n)(plain)
              for n in OLD + ("mix_ravel_ms",)}
    labelled = _ctx(_write(str(tmp_path / "labelled")))
    after = {n: harness.metric_reader(n)(labelled)
             for n in OLD + ("mix_ravel_ms",)}
    assert before == after
    assert all(v is not None for v in after.values())
    assert plain.breakdown() == labelled.breakdown()


def test_a_program_without_spans_or_scopes_reads_nothing(tmp_path):
    plain = _ctx(_write(str(tmp_path / "plain"), program=False))
    assert {n: harness.metric_reader(n)(plain) for n in LABELLED} == {
        n: None for n in LABELLED}


def test_a_trace_that_is_not_the_contexts_is_refused(tmp_path):
    directory = _write(str(tmp_path / "t"))
    ctx = _ctx(directory)
    del ctx.program_trace
    ctx.trace.devices[0].ops.pop()
    with pytest.raises(ValueError, match="not the one the context holds"):
        scopes.of(ctx, directory)


def test_load_keeps_the_program_spans_and_each_op_scope(tmp_path):
    prog = scopes.load(_write(str(tmp_path / "t")))
    assert [s.name for s in prog.spans] == [n for n, _, _ in PROGRAM_SPANS]
    assert [(o.name, o.opcode, o.scope) for o in prog.ops[0]] == [
        (n, op, s) for n, op, _, _, s in OPS]


def test_a_name_two_entries_scope_differently_has_no_scope():
    from jax.profiler import ProfileData
    a, b = _long("fusion.1", "fusion"), _long("fusion.2", "fusion")
    text = (f'planes {{ id: 1 name: "/device:TPU:0" '
            f'event_metadata {{ key: 1 value {{ id: 1 name: "{a}" stats '
            f'{{ metadata_id: 1 str_value: "x/step.optimizer/mul:" }} }} }} '
            f'event_metadata {{ key: 2 value {{ id: 2 name: "{a}" stats '
            f'{{ metadata_id: 1 str_value: "x/flat.ravel/pad:" }} }} }} '
            # a scope given by reference to a stat metadata's name
            f'event_metadata {{ key: 3 value {{ id: 3 name: "{b}" stats '
            f'{{ metadata_id: 1 ref_value: 2 }} }} }} '
            f'stat_metadata {{ key: 1 value {{ id: 1 name: "tf_op" }} }} '
            f'stat_metadata {{ key: 2 value {{ id: 2 name: "y/flat.unravel/'
            f'slice:" }} }} }} '
            f'planes {{ id: 2 name: "/host:CPU" event_metadata {{ key: 1 '
            f'value {{ id: 1 name: "slot.round" }} }} }}')
    got = scopes.metadata_scopes(ProfileData.text_proto_to_serialized_xspace(
        text))
    assert got == {"/device:TPU:0": {a: "", b: "y/flat.unravel/slice:"}}


@pytest.mark.parametrize("scope,names,inside", [
    ("jit(counted)/step.optimizer/mul:", ("step.optimizer",), True),
    ("jit(counted)/step.fwd_bwd/transpose(jvp(model.ssd))/dot:",
     ("model.ssd",), True),
    ("jit(mix_flat)/flat.ravel/concatenate:", ("flat.unravel",), False),
    ("jit(mix_flat)/flat.unravel/slice:", ("flat.ravel", "flat.unravel"),
     True),
    ("jit(counted)/step.optimizer_x/mul:", ("step.optimizer",), False),
    ("jit(counted)/my.step.optimizer/mul:", ("step.optimizer",), False),
    ("", ("step.optimizer",), False),
])
def test_a_scope_matches_whole_path_components(scope, names, inside):
    assert scopes.in_scope(scope, names) is inside
