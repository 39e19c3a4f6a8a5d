"""Benchmark harness front door — one module per paper table/figure plus
the roofline and the beyond-paper collective comparison.

  PYTHONPATH=src python -m benchmarks.run [--full] [--only fig3,fig8]
                                          [--json] [--baseline]

Default is quick mode (CPU-friendly); --full reproduces the paper-scale
settings.  Output: CSV rows ``table,key=value,...``.  With ``--json``
each benchmark additionally writes a machine-readable
``BENCH_<name>.json`` at the repo root (rows + wall time + mode + the
run's :mod:`repro.obs` telemetry block) and appends a slim record to
the ``BENCH_history.jsonl`` append-log (tracked in git, so the perf
trajectory accumulates across commits; render it with
``python -m benchmarks.report --history``).  Every benchmark runs
under a scoped telemetry bus + round ledger, so any instrumented loop
it drives lands its counters in the JSON for free.
``--baseline`` (implies ``--json``) compares
against the committed ``git HEAD`` copy of each ``BENCH_<name>.json``
(falling back to the artifact on disk when untracked) and exits nonzero
when any perf field regresses by more than 25% (lower-is-better
fields: ``seconds`` / ``*_ms``; higher-is-better: ``*_per_s`` /
``*speedup``; rows are matched by their non-perf identity fields).
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time
import traceback
from typing import Dict, List, Optional, Tuple

from repro import obs
from repro.launch.compile_cache import enable_compile_cache

from . import (churn_swap, cohort_stream, common, crosspod, fault_storm,
               fig3_topology, fig8_churn, fig11_noniid, fig12_async,
               fig13_locality, fig15_compute_cost, fig16_confidence,
               fig18_churn_accuracy, fig20_scalability, mix_fusion,
               roofline, serve_load, slot_runtime, sync_collectives,
               table3_accuracy)

MODULES = {
    "fig3": fig3_topology,
    "fig8": fig8_churn,
    "table3": table3_accuracy,
    "fig11": fig11_noniid,
    "fig12": fig12_async,
    "fig13": fig13_locality,
    "fig15": fig15_compute_cost,
    "fig16": fig16_confidence,
    "fig18": fig18_churn_accuracy,
    "fig20": fig20_scalability,
    "roofline": roofline,
    "sync_collectives": sync_collectives,
    "crosspod": crosspod,
    "churn_swap": churn_swap,
    "slot_runtime": slot_runtime,
    "mix_fusion": mix_fusion,
    "cohort_stream": cohort_stream,
    "serve_load": serve_load,
    "fault_storm": fault_storm,
}

REPO_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
HISTORY = os.path.join(REPO_ROOT, "BENCH_history.jsonl")

#: Regression gate for --baseline: new must stay within 25% of committed.
REGRESSION_TOLERANCE = 0.25


def _write_json(name: str, *, quick: bool, seconds: float, failed: bool,
                rows, telemetry: Optional[Dict] = None) -> str:
    path = os.path.join(REPO_ROOT, f"BENCH_{name}.json")
    payload = {"benchmark": name, "quick": quick,
               "seconds": round(seconds, 2), "failed": failed, "rows": rows}
    if telemetry:
        payload["telemetry"] = telemetry
    with open(path, "w") as f:
        json.dump(payload, f, indent=2)
        f.write("\n")
    return path


def _git_sha() -> Optional[str]:
    try:
        out = subprocess.run(["git", "rev-parse", "--short", "HEAD"],
                             cwd=REPO_ROOT, capture_output=True, text=True,
                             timeout=10)
        return out.stdout.strip() or None if out.returncode == 0 else None
    except Exception:
        return None


def _append_history(name: str, *, quick: bool, seconds: float, failed: bool,
                    rows) -> None:
    """One line per benchmark run: the perf trajectory across commits."""
    record = {"ts": round(time.time(), 1), "git_sha": _git_sha(),
              "benchmark": name, "quick": quick,
              "seconds": round(seconds, 2), "failed": failed,
              "rows": rows}
    with open(HISTORY, "a") as f:
        f.write(json.dumps(record) + "\n")


# --------------------------------------------------------------------------
# --baseline: compare perf fields against the committed BENCH artifacts
# --------------------------------------------------------------------------

def perf_direction(key: str) -> Optional[int]:
    """+1: higher is better; -1: lower is better; None: not a perf
    field (identity or accuracy data, never gated).  Bytes-on-the-wire
    fields (``*_bytes``, ``*_mb``) are lower-is-better; compression
    ratios (``*_reduction``) higher-is-better."""
    if (key == "seconds" or key.endswith("_ms") or key.endswith("_bytes")
            or key.endswith("_mb")):
        return -1
    if (key.endswith("speedup") or key.endswith("_per_s")
            or key.endswith("_reduction")):
        return +1
    return None


def _row_identity(row: Dict) -> Tuple:
    """A row's match key: its table plus every non-perf str/bool/int
    field (floats are measurements, not identity)."""
    return tuple(sorted(
        (k, v) for k, v in row.items()
        if perf_direction(k) is None and isinstance(v, (str, bool, int))))


def compare_rows(baseline_rows: List[Dict], new_rows: List[Dict],
                 tolerance: float = REGRESSION_TOLERANCE) -> List[str]:
    """Regression messages for every matched row whose perf field got
    more than ``tolerance`` worse than the baseline.  Unmatched rows
    (new tables, changed identities) are never regressions."""
    by_id: Dict[Tuple, Dict] = {}
    for row in baseline_rows:
        by_id.setdefault(_row_identity(row), row)
    out = []
    for row in new_rows:
        base = by_id.get(_row_identity(row))
        if base is None:
            continue
        for key, new in row.items():
            direction = perf_direction(key)
            base_v = base.get(key)
            if (direction is None or not isinstance(new, (int, float))
                    or not isinstance(base_v, (int, float))
                    or base_v <= 0 or new <= 0):
                continue
            ratio = new / base_v
            worse = ratio > 1 + tolerance if direction < 0 \
                else ratio < 1 / (1 + tolerance)
            if worse:
                ident = ",".join(f"{k}={v}" for k, v in _row_identity(row))
                out.append(f"{ident}: {key} {base_v} -> {new} "
                           f"({ratio:.2f}x, tolerance {tolerance:.0%})")
    return out


def _baseline_warn(name: str, reason: str) -> None:
    print(f"# WARNING baseline {name}: {reason}; skipping comparison",
          file=sys.stderr, flush=True)


def _load_baseline(name: str, quick: bool) -> Optional[List[Dict]]:
    """The committed (git HEAD) BENCH_<name>.json rows, falling back to
    the artifact currently on disk (e.g. a CI-downloaded baseline) when
    the file is not tracked; None unless comparable (same mode, not a
    failed run).

    A missing artifact is a clean None (there is simply no baseline
    yet); an *unreadable or malformed* one — truncated JSON, a non-dict
    document, rows that aren't objects — warns and returns None so one
    bad artifact degrades to "no comparison" instead of crashing the
    whole ``--baseline`` gate."""
    data = None
    try:
        out = subprocess.run(
            ["git", "show", f"HEAD:BENCH_{name}.json"], cwd=REPO_ROOT,
            capture_output=True, text=True, timeout=10)
    except Exception:
        out = None
    if out is not None and out.returncode == 0:
        try:
            data = json.loads(out.stdout)
        except ValueError:
            _baseline_warn(name, "committed artifact is not valid JSON")
            return None
    if data is None:
        path = os.path.join(REPO_ROOT, f"BENCH_{name}.json")
        if not os.path.exists(path):
            return None
        try:
            with open(path) as f:
                data = json.load(f)
        except (OSError, ValueError) as exc:
            _baseline_warn(name, f"unreadable artifact on disk ({exc})")
            return None
    if not isinstance(data, dict):
        _baseline_warn(
            name, f"malformed artifact (expected a JSON object, "
            f"got {type(data).__name__})")
        return None
    if data.get("failed") or data.get("quick") != quick:
        return None
    rows = data.get("rows")
    if rows is None:
        return None
    if (not isinstance(rows, list)
            or not all(isinstance(r, dict) for r in rows)):
        _baseline_warn(name, "malformed rows (expected a list of objects)")
        return None
    return rows or None


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--full", action="store_true",
                    help="paper-scale settings (slow)")
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of benchmarks")
    ap.add_argument("--json", action="store_true",
                    help="also write BENCH_<name>.json at the repo root "
                         "and append to BENCH_history.jsonl")
    ap.add_argument("--baseline", action="store_true",
                    help="compare against the committed BENCH_<name>.json "
                         "and exit nonzero on >25%% perf regression "
                         "(implies --json)")
    args = ap.parse_args()
    if args.baseline:
        args.json = True
    enable_compile_cache()

    names = list(MODULES) if not args.only else args.only.split(",")
    unknown = [n for n in names if n not in MODULES]
    if unknown:
        ap.error(f"unknown benchmark(s) {unknown}; "
                 f"choose from {', '.join(MODULES)}")
    failures = []
    regressions: List[str] = []
    for name in names:
        mod = MODULES[name]
        t0 = time.time()
        print(f"# === {name} ===", flush=True)
        baseline = (_load_baseline(name, quick=not args.full)
                    if args.baseline else None)
        if args.json:
            common.start_json_capture()
        bus = obs.Telemetry()
        ledger = obs.RoundLedger(bus=bus)
        try:
            with obs.telemetry(bus), obs.round_ledger(ledger):
                mod.run(quick=not args.full)
        except Exception:  # noqa: BLE001 — keep the harness going
            failures.append(name)
            traceback.print_exc()
        finally:
            if args.json:
                rows = common.end_json_capture()
                seconds = time.time() - t0
                telem: Optional[Dict] = {}
                counters = bus.summary()
                if counters.get("counters") or counters.get("gauges"):
                    telem["bus"] = counters
                if len(ledger):
                    telem["rounds"] = ledger.summary()
                path = _write_json(name, quick=not args.full,
                                   seconds=seconds,
                                   failed=name in failures, rows=rows,
                                   telemetry=telem or None)
                _append_history(name, quick=not args.full, seconds=seconds,
                                failed=name in failures, rows=rows)
                print(f"# wrote {os.path.relpath(path, REPO_ROOT)} "
                      f"(+ BENCH_history.jsonl)", flush=True)
                if baseline is not None and name not in failures:
                    found = compare_rows(baseline, rows)
                    for msg in found:
                        print(f"# REGRESSION {name}: {msg}",
                              file=sys.stderr, flush=True)
                    regressions.extend(f"{name}: {m}" for m in found)
                elif args.baseline and baseline is None:
                    print(f"# no comparable committed baseline for {name}",
                          flush=True)
        print(f"# {name} done in {time.time() - t0:.1f}s", flush=True)
    if failures:
        print(f"# FAILED: {failures}", file=sys.stderr)
        return 1
    if regressions:
        print(f"# {len(regressions)} perf regression(s) vs committed "
              f"baseline", file=sys.stderr)
        return 2
    return 0


if __name__ == "__main__":
    sys.exit(main())
