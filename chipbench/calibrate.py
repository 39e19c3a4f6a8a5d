"""Readings that the limits of a cell are set from, in one process.

  python chipbench/calibrate.py --workload <name> --seeds 1,2,3 \\
      --control-seeds 1,2,3 [--seconds 1] [--out <file>]

For every seed, one whole run of the cell (short window) gives the
program's gaps from the reference.  For every control seed, the
reference is run again one precision down (float8, the control) and
with each planted fault that the cell can have (half of each row's
positions left out of the loss; the mixing round left out; AdamW at
twice its learning rate), and their gaps from the reference are read.
A step that returns its state unchanged reads 1 on ``grad_gap`` and
``grad_err`` by construction and is not run.  Beside the compared
numbers each reading gives the median leaf's ``grad_err`` and the
worst leaf's ``update_gap``.  Prints one JSON object per reading and a
summary.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def medians(got, ref) -> dict:
    """The median leaf's ``grad_err`` and the worst leaf's
    ``update_gap``, beside the compared numbers."""
    from chipbench import harness
    ref_grad = ref.grad_norms()
    return {"grad_err_median": harness.grad_err(got, ref,
                                                stat=statistics.median),
            "update_gap_worst": harness.leaf_gap(got.delta, ref.delta,
                                                 ref_grad)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--out")
    args = ap.parse_args(argv)

    import jax
    from chipbench import harness
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    cell = harness.load_cell(args.workload)
    devices = jax.devices()
    rows = []

    def emit(row):
        rows.append(row)
        print(json.dumps(row), flush=True)

    seeds = [int(s) for s in args.seeds.split(",") if s]
    for seed in seeds:
        readings: dict = {}
        t0 = time.perf_counter()
        result = harness.run(cell, seed, args.seconds, False, devices, t0,
                             log=lambda m: print(m, file=sys.stderr),
                             readings=readings)
        emit({"kind": "program", "seed": seed,
              "correct": result["correct"],
              "gaps": dict({k: c["value"]
                            for k, c in result["checks"].items()},
                           **medians(readings["program"],
                                     readings["reference"])),
              "losses": readings["program"].losses,
              "reference_losses": readings["reference"].losses,
              "metrics": {k: m["value"] for k, m in result["metrics"].items()},
              "peak": result["device"]["memory_peak_bytes"]})
    half = cell.traffic["seq_len"] // 2
    variants = {"control_fp8": dict(quant="fp8"),
                "fault_half_batch": dict(positions=half),
                "fault_no_mixing": dict(mix=False),
                "fault_double_lr": dict(lr_scale=2.0)}
    for seed in [int(s) for s in args.control_seeds.split(",") if s]:
        ref = harness.reference_readings(cell, seed, devices)
        for kind, kw in variants.items():
            got = harness.reference_readings(cell, seed, devices, **kw)
            emit({"kind": kind, "seed": seed,
                  "gaps": dict(harness.gaps(got, ref), **medians(got, ref))})
            del got
        del ref

    summary = {}
    for kind in ["program"] + list(variants):
        gs = [r["gaps"] for r in rows if r["kind"] == kind]
        if gs:
            summary[kind] = {k: [min(g[k] for g in gs), max(g[k] for g in gs)]
                             for k in gs[0]}
    emit({"kind": "summary", "workload": cell.name, "ranges": summary,
          "seconds": time.perf_counter() - T_START})
    if args.out:
        with open(args.out, "w") as f:
            json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
