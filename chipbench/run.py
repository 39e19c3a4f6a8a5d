"""Run one benchmark cell once and print its result line.

  python chipbench/run.py --workload <name> --seed <n> --seconds <s> \\
      --trace <0|1>

Set-up (weights from the seed, compile or cache load, three gossip
rounds read for the check, warm-up until a round compiles nothing) is
timed from process start; then ``SlotTrainLoop.run`` rounds run back to
back for ``--seconds``.  ``--trace 0`` prints the cell's end-to-end
metrics, ``--trace 1`` profiles the window and prints its per-layer
metrics.  After the window the plain reference replays the first three
rounds and ``correct`` says whether the program stayed within the
cell's limits.  The last line of standard output is one JSON object;
the compared numbers close standard error.  Anything but a TPU with the
cell's chips exits nonzero and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from chipbench import harness
    cell = harness.load_cell(args.workload)

    import jax
    from repro.launch.compile_cache import enable_compile_cache

    devices = jax.devices()
    tag = (f"[{devices[0].platform} {devices[0].device_kind} "
           f"x{len(devices)}]")

    def log(msg: str) -> None:
        print(f"{tag} {cell.name}: {msg}", file=sys.stderr, flush=True)

    if devices[0].platform != "tpu":
        log("needs a TPU; no result")
        return 2
    if len(devices) < cell.chips:
        log(f"needs {cell.chips} chips; no result")
        return 2
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    result = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                         devices, T_START, log=log)
    for name, c in result["checks"].items():
        log(f"check {name}={c['value']!r} limit={c['limit']!r}")
    log(f"correct={result['correct']}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
