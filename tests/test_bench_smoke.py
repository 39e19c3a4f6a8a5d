"""Benchmark-harness smoke: the quick-mode front door must exit 0 so
benchmark-breaking API changes fail tier-1 instead of silently rotting
(fig3 exercises the topology-metrics path, churn_swap the overlay
control plane, slot_runtime the fixed-capacity runtime,
sync_collectives the grouped clients-per-device HLO accounting, and
mix_fusion the flat-buffer fused mixing acceptance claims — all
seconds-fast in quick mode).  Plus the --json side artifacts: the
BENCH_history.jsonl append-log and the --baseline regression gate."""

import json
import os
import subprocess
import sys

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def _run(*args):
    env = dict(os.environ)
    src = os.path.join(REPO, "src")
    env["PYTHONPATH"] = src + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    # don't leak the conftest-forced 8-device flag: benchmarks must run
    # under the same device config here as in CI / standalone, or the
    # accumulated BENCH_<name>.json perf rows are not comparable
    env.pop("XLA_FLAGS", None)
    # the harness turns on JAX's persistent compile cache; keep test
    # runs from writing one
    env["JAX_ENABLE_COMPILATION_CACHE"] = "false"
    return subprocess.run(
        [sys.executable, "-m", "benchmarks.run", *args],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600)


def test_benchmarks_quick_fig3():
    res = _run("--only", "fig3")
    assert res.returncode == 0, res.stderr[-2000:]
    assert "fig3" in res.stdout


def test_benchmarks_quick_churn_and_slot_runtime_json():
    """churn_swap + slot_runtime in quick mode through the --json path:
    exit 0, machine-readable BENCH_<name>.json rows at the repo root,
    and the slot runtime's zero-retrace claim visible in them."""
    res = _run("--only", "churn_swap,slot_runtime", "--json")
    assert res.returncode == 0, res.stderr[-2000:]
    by_name = {}
    for name in ("churn_swap", "slot_runtime"):
        path = os.path.join(REPO, f"BENCH_{name}.json")
        assert os.path.exists(path), name
        with open(path) as f:
            data = json.load(f)
        assert data["benchmark"] == name and data["quick"]
        assert not data["failed"] and data["rows"]
        by_name[name] = data
    by_loop = {r["loop"]: r for r in by_name["slot_runtime"]["rows"]
               if r["table"] == "slot_runtime"}
    assert by_loop["slot"]["retraces"] == 0
    assert by_loop["slot"]["distinct_alive"] >= 3
    assert by_loop["restack"]["retraces"] >= by_loop["restack"][
        "distinct_alive"] - 1


def test_benchmarks_quick_mix_fusion_json():
    """The ISSUE 5 acceptance pins through the --json path: fused ≡
    dense oracle ≤ 1e-6 for G ∈ {1,2,4} masked+unmasked; O(1) full-model
    temporaries per round at every L vs O(2L) for the tree walk; the
    shard_map round moves 2L flat-row ppermutes instead of T·2L
    per-leaf ones at identical wire bytes, and is no slower."""
    res = _run("--only", "mix_fusion", "--json")
    assert res.returncode == 0, res.stderr[-2000:]
    with open(os.path.join(REPO, "BENCH_mix_fusion.json")) as f:
        data = json.load(f)
    assert not data["failed"] and data["quick"]
    rows = data["rows"]
    parity = [r for r in rows if r["table"] == "mix_fusion_parity"]
    assert {(r["G"], r["masked"]) for r in parity} == \
        {(g, m) for g in (1, 2, 4) for m in (0, 1)}
    assert all(r["max_abs_err"] <= 1e-6 for r in parity), parity
    temps = {(r["path"], r["spaces"]): r["full_model_temps"]
             for r in rows if r["table"] == "mix_fusion_temps"}
    # fused: constant (O(1)) in the overlay degree; tree walk: O(2L)
    assert len({temps["flat", L] for L in (1, 2, 3)}) == 1
    assert temps["flat", 3] <= 4
    assert all(temps["tree", L] >= 2 * L for L in (1, 2, 3))
    rnd = {r["path"]: r for r in rows if r["table"] == "mix_fusion_round"}
    assert rnd["flat"]["ppermutes"] == 2 * rnd["flat"]["spaces"]
    assert rnd["tree"]["ppermutes"] == \
        rnd["tree"]["leaves"] * 2 * rnd["tree"]["spaces"]
    assert rnd["flat"]["wire_mb_per_dev"] == rnd["tree"]["wire_mb_per_dev"]
    # "no slower per round in quick mode" — the fused round eliminates
    # T·2L−2L collective dispatches, which dominates even on CPU
    assert rnd["flat"]["cpu_round_ms"] <= rnd["tree"]["cpu_round_ms"]
    # ISSUE 7: the wire-codec axis — HLO-measured reductions vs the
    # uncompressed flat round (int8 pays ~2 bf16 scale bytes per
    # 128-value block on the wire, hence >= 3.5x measured vs 4x payload)
    codec = {r["codec"]: r for r in rows
             if r["table"] == "mix_fusion_codec"}
    assert set(codec) >= {"uncompressed", "bf16", "int8-block",
                          "int4-block", "topk"}
    assert codec["uncompressed"]["wire_reduction"] == 1.0
    assert codec["bf16"]["wire_reduction"] >= 1.9
    assert codec["int8-block"]["wire_reduction"] >= 3.5
    assert codec["int8-block"]["payload_reduction"] >= 4.0
    assert codec["int4-block"]["wire_reduction"] >= 4.0
    assert codec["topk"]["wire_reduction"] >= 4.0
    for r in codec.values():
        # measured collective bytes agree with the codec closed form
        assert abs(r["wire_mb"] - r["predicted_wire_mb"]) <= \
            0.05 * r["predicted_wire_mb"] + 1e-4, r


def test_benchmarks_history_log_and_baseline_gate():
    """--json appends one record per run to BENCH_history.jsonl, and
    --baseline exits 0 against the just-committed artifact (a run is
    its own baseline within tolerance on the deterministic fields)."""
    hist = os.path.join(REPO, "BENCH_history.jsonl")
    before = sum(1 for _ in open(hist)) if os.path.exists(hist) else 0
    res = _run("--only", "fig3", "--json")
    assert res.returncode == 0, res.stderr[-2000:]
    with open(hist) as f:
        lines = f.read().splitlines()
    assert len(lines) == before + 1
    rec = json.loads(lines[-1])
    assert rec["benchmark"] == "fig3" and not rec["failed"] and rec["rows"]
    # baseline mode: fig3 is deterministic apart from its wall-time
    # rows, which compare within tolerance against the file just written
    res2 = _run("--only", "fig3", "--baseline")
    assert res2.returncode == 0, (res2.stdout[-500:], res2.stderr[-2000:])
    assert "baseline" in res2.stdout or "REGRESSION" not in res2.stderr


def test_baseline_compare_flags_regressions():
    """Unit-level: compare_rows matches rows by identity and gates both
    perf directions at the 25% tolerance."""
    sys.path.insert(0, REPO)
    try:
        from benchmarks.run import compare_rows, perf_direction
    finally:
        sys.path.remove(REPO)
    assert perf_direction("seconds") == -1
    assert perf_direction("per_round_ms") == -1
    assert perf_direction("steps_per_s") == +1
    assert perf_direction("cpu_speedup") == +1
    assert perf_direction("final_loss") is None
    # ISSUE 7: bytes-on-the-wire fields gate lower-is-better, reduction
    # factors higher-is-better; identity-ish names stay ungated
    assert perf_direction("wire_mb") == -1
    assert perf_direction("payload_bytes") == -1
    assert perf_direction("wire_reduction") == +1
    assert perf_direction("wire_mb_per_dev") is None
    assert perf_direction("codec") is None
    base = [{"table": "t", "loop": "slot", "steps_per_s": 100.0,
             "seconds": 2.0, "final_loss": 0.5}]
    bad = [{"table": "t", "loop": "slot", "steps_per_s": 60.0,
            "seconds": 3.0, "final_loss": 9.9}]
    msgs = compare_rows(base, bad)
    assert len(msgs) == 2 and all("tolerance" in m for m in msgs)
    ok = [{"table": "t", "loop": "slot", "steps_per_s": 90.0,
           "seconds": 2.2, "final_loss": 0.5}]
    assert compare_rows(base, ok) == []
    # unmatched identities never regress
    assert compare_rows(base, [{"table": "t", "loop": "other",
                                "seconds": 99.0}]) == []


def test_benchmarks_quick_sync_collectives_grouped_json():
    """The grouped clients-per-device axis through the --json path:
    rows for G = 1 and G > 1, with the G > 1 fedlay schedule provably
    cheaper on the wire than the flat-layout paper bound."""
    res = _run("--only", "sync_collectives", "--json")
    assert res.returncode == 0, res.stderr[-2000:]
    path = os.path.join(REPO, "BENCH_sync_collectives.json")
    assert os.path.exists(path)
    with open(path) as f:
        data = json.load(f)
    assert not data["failed"] and data["rows"]
    fedlay = {r["clients_per_device"]: r for r in data["rows"]
              if r.get("strategy") == "fedlay"
              and r["table"] == "sync_collectives"}
    assert 1 in fedlay and any(g > 1 for g in fedlay)
    for g, row in fedlay.items():
        assert row["clients"] == 8 * g
        assert row["wire_mb_per_dev"] > 0
        bound = 2 * 3 * row["model_mb"]          # flat 2L·model bytes
        assert row["exact_mb_per_client"] <= bound + 1e-6
        if g > 1:
            assert row["exact_mb_per_client"] < bound
    # ISSUE 7: the codec axis pins sync_bytes_per_client(codec=)
    # against the HLO-measured compressed round (gap = lane padding)
    codec = {r["codec"]: r for r in data["rows"]
             if r["table"] == "sync_collectives_codec"}
    assert set(codec) >= {"uncompressed", "bf16", "int8-block",
                          "int4-block", "topk"}
    for r in codec.values():
        assert abs(r["wire_mb_per_dev"] - r["predicted_mb_per_client"]) \
            <= 0.05 * r["predicted_mb_per_client"] + 1e-3, r
    assert codec["int8-block"]["wire_reduction"] >= 3.5
    assert codec["int4-block"]["wire_reduction"] >= 4.0
    assert codec["topk"]["wire_reduction"] >= 4.0


def test_benchmarks_quick_fig20_json():
    """fig20 through the --json path: both engines at small n with the
    vec-vs-object parity row True, and comm rows including the cohort
    active_clients closed form."""
    res = _run("--only", "fig20", "--json")
    assert res.returncode == 0, res.stderr[-2000:]
    with open(os.path.join(REPO, "BENCH_fig20.json")) as f:
        data = json.load(f)
    assert not data["failed"] and data["quick"]
    rows = data["rows"]
    engines = {r["engine"] for r in rows if r["table"] == "fig20_protocol"}
    assert engines == {"object", "vec"}
    parity = [r for r in rows if r["table"] == "fig20_parity"]
    assert parity and all(r["tables_equal"] for r in parity)
    cohort = [r for r in rows if r["table"] == "fig20_comm"
              and r["strategy"] == "fedlay_cohort"]
    assert cohort and all(r["active_clients"] >= 1 for r in cohort)


def test_benchmarks_quick_cohort_stream_json():
    """The ISSUE 6 acceptance pins through the --json path: the device
    cohort round equals the dense oracle within 1e-6 across >= 3 cohort
    compositions with 0 retraces, and the K-sweep streaming rows also
    never retrace."""
    res = _run("--only", "cohort_stream", "--json")
    assert res.returncode == 0, res.stderr[-2000:]
    with open(os.path.join(REPO, "BENCH_cohort_stream.json")) as f:
        data = json.load(f)
    assert not data["failed"] and data["quick"]
    rows = data["rows"]
    oracle = [r for r in rows if r["table"] == "cohort_oracle"]
    assert len(oracle) >= 4           # 3 compositions + full-vs-dense pin
    assert all(r["within_1e6"] == 1 for r in oracle), oracle
    assert all(r["retraces"] == 0 for r in oracle)
    stream = [r for r in rows if r["table"] == "cohort_stream"]
    assert len({r["k"] for r in stream}) >= 3
    assert all(r["retraces"] == 0 for r in stream), stream
    assert all(r["streamed_in"] ==
               r["restored"] + r["donor_seeded"] + r["fresh"]
               for r in stream)


def test_benchmarks_quick_serve_load_json():
    """The ISSUE 9 acceptance pins through the --json path: per-slot-pos
    flash_decode equals the cache_attention oracle within 1e-5, empty
    slots return exactly zero, both admission policies replay the
    Poisson trace with 0 decode retraces after warmup across >= 3
    distinct batch occupancies, and continuous batching sustains at
    least static-batch throughput."""
    res = _run("--only", "serve_load", "--json")
    assert res.returncode == 0, res.stderr[-2000:]
    with open(os.path.join(REPO, "BENCH_serve_load.json")) as f:
        data = json.load(f)
    assert not data["failed"] and data["quick"]
    rows = data["rows"]
    parity = [r for r in rows if r["table"] == "serve_parity"]
    assert parity and all(r["within_1e5"] == 1 for r in parity), parity
    assert all(r["empty_slot_zero"] == 1 for r in parity)
    load = {r["policy"]: r for r in rows if r["table"] == "serve_load"}
    for policy in ("continuous", "static"):
        assert load[policy]["retraces"] == 0, load
        assert load[policy]["distinct_occupancies"] >= 3, load
        assert load[policy]["p99_ms"] >= load[policy]["p50_ms"] > 0
    assert load["continuous_vs_static"]["continuous_wins"] == 1, load


def test_baseline_malformed_artifact_warns_and_skips(capsys):
    """ISSUE 10 satellite: --baseline must degrade to "no comparison"
    (warn on stderr, return None) on a missing, truncated, non-object,
    or bad-rows BENCH artifact instead of crashing the gate."""
    import json as _json
    sys.path.insert(0, REPO)
    try:
        from benchmarks.run import _load_baseline
    finally:
        sys.path.remove(REPO)
    name = "zz_unit_malformed"          # never committed, never tracked
    path = os.path.join(REPO, f"BENCH_{name}.json")
    try:
        # missing artifact: clean None, no warning
        assert _load_baseline(name, quick=True) is None
        assert "WARNING" not in capsys.readouterr().err

        with open(path, "w") as f:      # truncated JSON
            f.write('{"rows": [')
        assert _load_baseline(name, quick=True) is None
        assert "skipping comparison" in capsys.readouterr().err

        with open(path, "w") as f:      # valid JSON, not an object
            _json.dump([1, 2, 3], f)
        assert _load_baseline(name, quick=True) is None
        err = capsys.readouterr().err
        assert "WARNING" in err and "expected a JSON object" in err

        with open(path, "w") as f:      # rows that aren't objects
            _json.dump({"quick": True, "failed": False,
                        "rows": [1, 2]}, f)
        assert _load_baseline(name, quick=True) is None
        assert "malformed rows" in capsys.readouterr().err

        with open(path, "w") as f:      # healthy artifact still loads
            _json.dump({"quick": True, "failed": False,
                        "rows": [{"table": "t", "x": 1}]}, f)
        assert _load_baseline(name, quick=True) == [{"table": "t", "x": 1}]
        # mode mismatch / failed runs stay silently incomparable
        assert _load_baseline(name, quick=False) is None
    finally:
        if os.path.exists(path):
            os.remove(path)
