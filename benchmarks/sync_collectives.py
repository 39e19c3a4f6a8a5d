"""Beyond-paper table: FedLay-as-gradient-sync vs all-reduce on the TPU
path — compiled wire bytes of one DFL round, measured from the HLO of
the actual shard_map programs (8 host devices, subprocess so the parent
jax stays at its own device count).

ISSUE 4 adds the ``--clients-per-device`` axis: with G > 1 local
clients per device (``num_clients = 8·G``), intra-device mixing edges
never reach the wire, so measured collective-permute bytes drop below
the flat-layout 2L·model bound.  Each row carries the analytic
prediction (``sync_bytes_per_client`` grouped accounting) next to the
HLO-measured bytes so the model and the compiler stay reconciled.

ISSUE 7 adds the wire-codec axis (``sync_collectives_codec`` rows):
one fedlay ``fuse="flat"`` round per :mod:`repro.wire.codec` codec,
pinning the codec-aware ``sync_bytes_per_client(..., codec=)`` closed
form against the HLO-measured collective-permute bytes (the small
residual gap is the FlatSpec 128-lane padding, which the closed form
prices at the unpadded element count).

The subprocess is a host-device collective-counting probe: it runs with
``JAX_PLATFORMS=cpu`` on 8 forced host devices, so on a chip machine it
never reaches for the chip the parent process holds.

  PYTHONPATH=src python -m benchmarks.sync_collectives \
      [--clients-per-device 1,2,4] [--quick]
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import textwrap
from typing import Sequence

from .common import emit

_PROBE = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json, sys
    import jax, jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core.mixing import build_permute_schedule, grouped_routing
    from repro.dist.compat import make_client_mesh, shard_map
    from repro.dist.sync import make_mixer, sync_bytes_per_client
    from repro.launch.hlo_stats import collective_stats

    cfg = json.loads(sys.argv[1])
    dim, spaces, groups = cfg["dim"], cfg["spaces"], cfg["groups"]
    devices = 8
    mesh = make_client_mesh(devices, "data")
    out = []
    for G in groups:
        n = devices * G
        sched = build_permute_schedule(n, spaces)
        for strategy in ("fedlay", "allreduce", "ring"):
            mixer = make_mixer(strategy, sched, "data", n,
                               clients_per_device=G)

            def body(x, w, s):
                return mixer({"m": x}, w, s)["m"]

            f = jax.jit(shard_map(body, mesh=mesh,
                                  in_specs=(P("data"), P("data"),
                                            P("data")),
                                  out_specs=P("data"), check_vma=False))
            lowered = f.lower(
                jax.ShapeDtypeStruct((n, dim), jnp.float32),
                jax.ShapeDtypeStruct((n, 2 * spaces), jnp.float32),
                jax.ShapeDtypeStruct((n,), jnp.float32))
            hlo = lowered.compile().as_text()
            st = collective_stats(hlo)
            model_bytes = 4 * dim
            row = {"strategy": strategy, "clients_per_device": G,
                   "clients": n,
                   "wire_bytes_per_dev": st.wire_bytes_per_device,
                   "model_bytes_per_client": sync_bytes_per_client(
                       strategy, model_bytes, n, spaces,
                       clients_per_device=G),
                   "counts": st.counts}
            if strategy == "fedlay":
                rt = grouped_routing(sched, G)
                row["cross_edges"] = rt.cross_edges
                row["ppermute_rounds_max"] = rt.max_rounds
            out.append(row)

    # wire-codec axis: fedlay flat round per codec, G = 1
    from repro.dist.flat import FlatSpec
    from repro.wire.codec import get_codec
    codec_rows = []
    n = devices
    sched = build_permute_schedule(n, spaces)
    nflat = FlatSpec.for_tree(
        {"m": jax.ShapeDtypeStruct((1, dim), jnp.float32)}).size
    w_sds = jax.ShapeDtypeStruct((n, 2 * spaces), jnp.float32)
    s_sds = jax.ShapeDtypeStruct((n,), jnp.float32)
    x_sds = jax.ShapeDtypeStruct((n, dim), jnp.float32)
    for name in cfg.get("codecs", []):
        codec = get_codec(name)
        ef = codec is not None and codec.error_feedback
        mixer = make_mixer("fedlay", sched, "data", n, fuse="flat",
                           codec=name)
        if ef:
            def body_ef(x, w, s, r, mixer=mixer):
                out_t, r = mixer({"m": x}, w, s, r)
                return out_t["m"], r
            f = jax.jit(shard_map(
                body_ef, mesh=mesh,
                in_specs=(P("data"), P("data"), P("data"),
                          P("data", None)),
                out_specs=(P("data"), P("data", None)), check_vma=False))
            lowered = f.lower(x_sds, w_sds, s_sds,
                              jax.ShapeDtypeStruct((n, nflat),
                                                   jnp.float32))
        else:
            def body_c(x, w, s, mixer=mixer):
                return mixer({"m": x}, w, s)["m"]
            f = jax.jit(shard_map(
                body_c, mesh=mesh,
                in_specs=(P("data"), P("data"), P("data")),
                out_specs=P("data"), check_vma=False))
            lowered = f.lower(x_sds, w_sds, s_sds)
        st = collective_stats(lowered.compile().as_text())
        codec_rows.append({
            "codec": name if name is not None else "uncompressed",
            "wire_bytes_per_dev": st.wire_bytes_per_device,
            "predicted_bytes_per_client": sync_bytes_per_client(
                "fedlay", 4 * dim, n, spaces, codec=name),
            "counts": st.counts})
    print(json.dumps({"rows": out, "codec_rows": codec_rows}))
""")


def run(quick: bool = False,
        clients_per_device: Sequence[int] = ()) -> None:
    groups = list(clients_per_device) or ([1, 2] if quick else [1, 2, 4])
    cfg = {"dim": 250_000 if quick else 1_000_000,
           "spaces": 3, "groups": groups,
           "codecs": [None, "bf16", "int8-block", "int4-block", "topk"]}
    env = dict(os.environ)
    env["PYTHONPATH"] = env.get("PYTHONPATH", "src")
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"       # host devices, never the chip
    res = subprocess.run(
        [sys.executable, "-c", _PROBE, json.dumps(cfg)], env=env,
        capture_output=True, text=True, timeout=600)
    if res.returncode != 0:
        emit("sync_collectives", error=res.stderr[-300:].replace(",", ";")
             .replace("\n", " "))
        return
    data = json.loads(res.stdout.strip().splitlines()[-1])
    codec_rows = data["codec_rows"]
    for row in data["rows"]:
        extra = {}
        if "cross_edges" in row:
            # exact per-client wire bytes for this schedule: one model
            # row per weight>0 cross-device edge.  (The HLO column is a
            # per-device ring-model upper bound — every ppermute op is
            # costed at full operand bytes even on devices its partial
            # perm leaves idle.)
            extra = {"cross_edges": row["cross_edges"],
                     "exact_mb_per_client": round(
                         row["cross_edges"] * 4 * cfg["dim"]
                         / row["clients"] / 1e6, 2),
                     "ppermute_rounds_max": row["ppermute_rounds_max"]}
        emit("sync_collectives", strategy=row["strategy"],
             clients=row["clients"],
             clients_per_device=row["clients_per_device"],
             model_mb=round(4 * cfg["dim"] / 1e6, 2),
             wire_mb_per_dev=round(row["wire_bytes_per_dev"] / 1e6, 2),
             predicted_mb_per_client=round(
                 row["model_bytes_per_client"] / 1e6, 2),
             ops="+".join(f"{k}:{v}" for k, v in row["counts"].items()),
             **extra)
    base = next(r for r in codec_rows if r["codec"] == "uncompressed")
    for row in codec_rows:
        emit("sync_collectives_codec", strategy="fedlay",
             clients=8, codec=row["codec"],
             wire_mb_per_dev=round(row["wire_bytes_per_dev"] / 1e6, 3),
             predicted_mb_per_client=round(
                 row["predicted_bytes_per_client"] / 1e6, 3),
             wire_reduction=round(base["wire_bytes_per_dev"]
                                  / row["wire_bytes_per_dev"], 2)
             if row["wire_bytes_per_dev"] > 0 else -1,
             ops="+".join(f"{k}:{v}" for k, v in row["counts"].items()))


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--clients-per-device", default=None,
                    help="comma-separated G values, e.g. 1,2,4")
    ap.add_argument("--quick", action="store_true",
                    help="CI-sized table (smaller dim, G in {1,2}); the "
                         "bare invocation reproduces the full table, "
                         "matching the other benchmark modules")
    args = ap.parse_args()
    groups = ([int(g) for g in args.clients_per_device.split(",")]
              if args.clients_per_device else ())
    run(quick=args.quick, clients_per_device=groups)


if __name__ == "__main__":
    main()
