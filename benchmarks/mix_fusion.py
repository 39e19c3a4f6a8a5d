"""ISSUE 5 microbenchmark: flat-buffer fused mixing vs the tree walk.

Five sections, one per acceptance claim:

* ``mix_fusion_parity`` — the fused global mixer ≡ the dense
  ``masked_mixing_matrix`` / ``schedule_mixing_matrix`` oracle for
  G ∈ {1, 2, 4}, masked and unmasked (max |err| ≤ 1e-6);
* ``mix_fusion_temps`` — jaxpr accounting on a full-model-sized leaf:
  the tree walk materializes 6L+1 full-model temporaries per round
  (O(2L): take/mul/add per slot), the fused path a constant ~2
  (ravel + one Pallas round kernel) at every L, with peak
  simultaneously-live full-model intermediates 2 vs 1;
* ``mix_fusion_round`` — the deployment-shaped comparison, measured in
  a subprocess on the forced 8-device host mesh (the
  ``sync_collectives`` probe idiom): one shard_map FedLay round over a
  T-leaf model.  The tree walk issues T·2L collective-permutes per
  round, the fused path exactly 2L (one flat row per slot) at
  identical wire bytes — and the per-round wall time follows
  (interleaved medians on the CPU, ``cpu_speedup = tree_ms / flat_ms``);
* ``mix_fusion_memory`` — XLA ``memory_analysis`` temp bytes for the
  two compiled global programs, when the backend reports it;
* ``mix_fusion_codec`` (also runnable alone via ``--codec``) — the wire
  axis: one shard_map FedLay round per :mod:`repro.wire.codec` codec,
  HLO-measured collective-permute bytes per device next to the codec's
  ``wire_bytes`` closed form, per-round wall time, and the reduction
  factors vs the uncompressed ``fuse="flat"`` round (``wire_reduction``
  counts everything on the wire including per-block scales;
  ``payload_reduction`` the value payload alone).

Caveat for reading the timing on CPU: XLA already loop-fuses the
*global-view* tree walk into near-optimal single-pass code on one
device, so the fused path's win there is program structure, not CPU
milliseconds; the wall-clock win shows on the collective-bound
shard_map round (and, on real TPUs, in the kernel's (K+1)·N HBM
traffic).  Quick mode keeps every section seconds-fast.

The ``mix_fusion_round`` and ``mix_fusion_codec`` subprocesses are
host-device collective-counting probes: the child runs with
``JAX_PLATFORMS=cpu`` on 8 forced host devices, so on a chip machine it
never reaches for the chip the parent process holds, and its times are
CPU times, not device times.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap
import time

import numpy as np

from .common import emit

_ROUND_PROBE = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json, sys, time
    import numpy as np
    import jax, jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core.mixing import build_permute_schedule
    from repro.dist.compat import make_client_mesh, shard_map
    from repro.dist.sync import make_mixer
    from repro.launch.hlo_stats import collective_stats

    cfg = json.loads(sys.argv[1])
    L, T, leaf, reps = cfg["spaces"], cfg["leaves"], cfg["leaf"], cfg["reps"]
    n = 8
    mesh = make_client_mesh(n, "data")
    shard = NamedSharding(mesh, P("data"))
    sched = build_permute_schedule(n, L, salt="mix_fusion")
    rng = np.random.default_rng(0)
    tree = {f"l{i}": jax.device_put(
        jnp.asarray(rng.normal(size=(n, leaf)).astype(np.float32)), shard)
        for i in range(T)}
    W = jax.device_put(jnp.asarray(sched.weights), shard)
    S = jax.device_put(jnp.asarray(sched.self_weight), shard)
    specs = jax.tree.map(lambda _: P("data"), tree)

    progs, rows = {}, []
    for name, fuse in (("tree", None), ("flat", "flat")):
        mixer = make_mixer("fedlay", sched, "data", n, fuse=fuse)
        f = jax.jit(shard_map(
            lambda t, w, s, mixer=mixer: mixer(t, w, s), mesh=mesh,
            in_specs=(specs, P("data"), P("data")), out_specs=specs,
            check_vma=False))
        st = collective_stats(f.lower(tree, W, S).compile().as_text())
        rows.append({"path": name,
                     "ppermutes": st.counts.get("collective-permute", 0),
                     "wire_mb_per_dev": round(
                         st.wire_bytes_per_device / 1e6, 4)})
        progs[name] = f
    ts = {k: [] for k in progs}
    for f in progs.values():
        jax.block_until_ready(f(tree, W, S))
    for _ in range(reps):                   # interleaved: shared drift
        for k, f in progs.items():
            t0 = time.perf_counter()
            jax.block_until_ready(f(tree, W, S))
            ts[k].append(time.perf_counter() - t0)
    for row in rows:
        row["cpu_round_ms"] = round(
            float(np.median(ts[row["path"]])) * 1e3, 3)
    print(json.dumps(rows))
""")


_CODEC_PROBE = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json, sys, time
    import numpy as np
    import jax, jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core.mixing import build_permute_schedule
    from repro.dist.compat import make_client_mesh, shard_map
    from repro.dist.flat import FlatSpec
    from repro.dist.sync import make_mixer
    from repro.launch.hlo_stats import collective_stats
    from repro.wire.codec import get_codec

    cfg = json.loads(sys.argv[1])
    L, T, leaf, reps = cfg["spaces"], cfg["leaves"], cfg["leaf"], cfg["reps"]
    n = 8
    mesh = make_client_mesh(n, "data")
    shard = NamedSharding(mesh, P("data"))
    sched = build_permute_schedule(n, L, salt="mix_fusion")
    rng = np.random.default_rng(0)
    tree = {f"l{i}": jax.device_put(
        jnp.asarray(rng.normal(size=(n, leaf)).astype(np.float32)), shard)
        for i in range(T)}
    W = jax.device_put(jnp.asarray(sched.weights), shard)
    S = jax.device_put(jnp.asarray(sched.self_weight), shard)
    specs = jax.tree.map(lambda _: P("data"), tree)
    nflat = FlatSpec.for_tree(tree).size
    res0 = jax.device_put(jnp.zeros((n, nflat), jnp.float32),
                          NamedSharding(mesh, P("data", None)))

    rows, progs, efs = [], {}, {}
    for name in cfg["codecs"]:
        codec = get_codec(name)
        ef = codec is not None and codec.error_feedback
        mixer = make_mixer("fedlay", sched, "data", n, fuse="flat",
                           codec=name)
        if ef:
            f = jax.jit(shard_map(
                lambda t, w, s, r, mixer=mixer: mixer(t, w, s, r),
                mesh=mesh,
                in_specs=(specs, P("data"), P("data"), P("data", None)),
                out_specs=(specs, P("data", None)), check_vma=False))
            hlo = f.lower(tree, W, S, res0).compile().as_text()
        else:
            f = jax.jit(shard_map(
                lambda t, w, s, mixer=mixer: mixer(t, w, s), mesh=mesh,
                in_specs=(specs, P("data"), P("data")), out_specs=specs,
                check_vma=False))
            hlo = f.lower(tree, W, S).compile().as_text()
        st = collective_stats(hlo)
        cname = name if name is not None else "uncompressed"
        wire = (codec or get_codec("none"))
        rows.append({
            "codec": cname,
            "ppermutes": st.counts.get("collective-permute", 0),
            "wire_mb": round(st.wire_bytes_per_device / 1e6, 4),
            "predicted_wire_mb": round(
                2 * L * wire.wire_bytes(nflat) / 1e6, 4),
            "payload_mb": round(
                2 * L * wire.payload_bytes(nflat) / 1e6, 4)})
        progs[cname], efs[cname] = f, ef

    ts = {k: [] for k in progs}
    call = lambda k: (progs[k](tree, W, S, res0) if efs[k]
                      else progs[k](tree, W, S))
    for k in progs:
        jax.block_until_ready(call(k))
    for _ in range(reps):                   # interleaved: shared drift
        for k in progs:
            t0 = time.perf_counter()
            jax.block_until_ready(call(k))
            ts[k].append(time.perf_counter() - t0)
    for row in rows:
        row["cpu_round_ms"] = round(
            float(np.median(ts[row["codec"]])) * 1e3, 3)
    print(json.dumps(rows))
""")


def _var_nbytes(v) -> int:
    aval = getattr(v, "aval", None)
    if aval is None or not hasattr(aval, "shape"):
        return 0
    n = 1
    for d in aval.shape:
        n *= int(d)
    return n * aval.dtype.itemsize


def full_model_temp_stats(fn, args, model_bytes: int, thresh: float = 0.9):
    """(count, peak_live, total_eqns) of full-model-sized intermediates
    in ``fn``'s jaxpr: ``count`` is how many eqn outputs of ≥
    ``thresh·model_bytes`` the round materializes (the HBM-traffic
    proxy: each is one full-model write + later read), ``peak_live``
    how many coexist at the worst program point (the memory proxy).
    The Pallas round kernel is one opaque eqn — its VMEM tiles are not
    HBM temporaries and are not counted."""
    import jax
    jaxpr = jax.make_jaxpr(fn)(*args).jaxpr
    lim = thresh * model_bytes
    last_use = {}
    for idx, eqn in enumerate(jaxpr.eqns):
        for v in eqn.invars:
            if hasattr(v, "count"):
                last_use[v] = idx
    for v in jaxpr.outvars:
        if hasattr(v, "count"):
            last_use[v] = len(jaxpr.eqns)
    count, peak, births = 0, 0, {}
    for idx, eqn in enumerate(jaxpr.eqns):
        for v in eqn.outvars:
            if _var_nbytes(v) >= lim:
                count += 1
                births[v] = idx
        live = sum(1 for v in births if last_use.get(v, -1) > idx)
        peak = max(peak, live)
    return count, peak, len(jaxpr.eqns)


def _parity_section(quick: bool) -> None:
    import jax, jax.numpy as jnp
    from repro.core.mixing import (build_permute_schedule,
                                   masked_mixing_matrix,
                                   schedule_mixing_matrix)
    from repro.dist.sync import global_mixer
    dim = 257 if quick else 4099            # deliberately lane-unaligned
    for G in (1, 2, 4):
        n = 8 * G
        sched = build_permute_schedule(n, 2, salt=f"parity{G}")
        rng = np.random.default_rng(G)
        X = {"a": jnp.asarray(rng.normal(size=(n, dim)).astype(np.float32)),
             "b": jnp.asarray(rng.normal(size=(n, 3, 5)).astype(np.float32))}
        rows = np.concatenate([np.asarray(X["a"]),
                               np.asarray(X["b"]).reshape(n, -1)], axis=1)
        for masked in (False, True):
            if masked:
                mask = (rng.random(n) > 0.4).astype(np.float32)
                mask[0] = 0.0
                ref = masked_mixing_matrix(sched, mask) @ rows
                mix = jax.jit(global_mixer("fedlay", sched, masked=True,
                                           fuse="flat"))
                out = mix(X, jnp.asarray(mask))
            else:
                ref = schedule_mixing_matrix(sched) @ rows
                out = jax.jit(global_mixer("fedlay", sched,
                                           fuse="flat"))(X)
            got = np.concatenate([np.asarray(out["a"]),
                                  np.asarray(out["b"]).reshape(n, -1)],
                                 axis=1)
            emit("mix_fusion_parity", G=G, masked=int(masked),
                 max_abs_err=float(np.abs(got - ref).max()))


def _temps_section(quick: bool) -> None:
    import jax.numpy as jnp
    from repro.core.mixing import build_permute_schedule
    from repro.dist.sync import global_mixer
    C, N = 8, 16384 if quick else 262144
    x = {"w": jnp.zeros((C, N), jnp.float32)}
    model_bytes = C * N * 4
    for L in (1, 2, 3):
        sched = build_permute_schedule(C, L, salt=f"temps{L}")
        for path, fuse in (("tree", None), ("flat", "flat")):
            mix = global_mixer("fedlay", sched, fuse=fuse)
            count, peak, eqns = full_model_temp_stats(mix, (x,),
                                                      model_bytes)
            emit("mix_fusion_temps", path=path, spaces=L, slots=2 * L,
                 full_model_temps=count, peak_live=peak, eqns=eqns)


def _round_section(quick: bool) -> None:
    cfg = {"spaces": 3, "leaves": 24 if quick else 64,
           "leaf": 512 if quick else 4096, "reps": 8 if quick else 20}
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)              # the probe forces its own
    env["JAX_PLATFORMS"] = "cpu"            # host devices, never the chip
    repo = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
    src = os.path.join(repo, "src")
    env["PYTHONPATH"] = src + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    res = subprocess.run(
        [sys.executable, "-c", _ROUND_PROBE, json.dumps(cfg)],
        env=env, capture_output=True, text=True, timeout=600)
    if res.returncode != 0:
        raise RuntimeError(f"round probe failed:\n{res.stderr[-2000:]}")
    rows = json.loads(res.stdout.strip().splitlines()[-1])
    by_path = {r["path"]: r for r in rows}
    speedup = (by_path["tree"]["cpu_round_ms"]
               / by_path["flat"]["cpu_round_ms"])
    for r in rows:
        emit("mix_fusion_round", spaces=cfg["spaces"],
             leaves=cfg["leaves"], leaf_dim=cfg["leaf"], **{
                 k: v for k, v in r.items() if k != "path"},
             path=r["path"], cpu_speedup=round(speedup, 2))


def _memory_section(quick: bool) -> None:
    import jax, jax.numpy as jnp
    from repro.core.mixing import build_permute_schedule
    from repro.dist.sync import global_mixer
    C, N = 8, 16384 if quick else 262144
    x = {"w": jnp.zeros((C, N), jnp.float32)}
    sched = build_permute_schedule(C, 3, salt="mem")
    for path, fuse in (("tree", None), ("flat", "flat")):
        mix = jax.jit(global_mixer("fedlay", sched, fuse=fuse))
        temp = -1
        try:
            mem = mix.lower(x).compile().memory_analysis()
            temp = int(getattr(mem, "temp_size_in_bytes", -1))
        except Exception:                    # backend doesn't report it
            pass
        emit("mix_fusion_memory", path=path, model_mb=round(
            C * N * 4 / 1e6, 3), temp_mb=round(temp / 1e6, 3)
            if temp >= 0 else -1)


def _codec_section(quick: bool) -> None:
    cfg = {"spaces": 3, "leaves": 12 if quick else 48,
           "leaf": 512 if quick else 4096, "reps": 5 if quick else 15,
           "codecs": [None, "bf16", "int8-block", "int4-block", "topk"]}
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)              # the probe forces its own
    env["JAX_PLATFORMS"] = "cpu"            # host devices, never the chip
    repo = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
    src = os.path.join(repo, "src")
    env["PYTHONPATH"] = src + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    res = subprocess.run(
        [sys.executable, "-c", _CODEC_PROBE, json.dumps(cfg)],
        env=env, capture_output=True, text=True, timeout=600)
    if res.returncode != 0:
        raise RuntimeError(f"codec probe failed:\n{res.stderr[-2000:]}")
    rows = json.loads(res.stdout.strip().splitlines()[-1])
    base = next(r for r in rows if r["codec"] == "uncompressed")
    for r in rows:
        emit("mix_fusion_codec", spaces=cfg["spaces"],
             leaves=cfg["leaves"], leaf_dim=cfg["leaf"],
             codec=r["codec"], ppermutes=r["ppermutes"],
             wire_mb=r["wire_mb"],
             predicted_wire_mb=r["predicted_wire_mb"],
             cpu_round_ms=r["cpu_round_ms"],
             wire_reduction=round(
                 base["wire_mb"] / r["wire_mb"], 2)
             if r["wire_mb"] > 0 else -1,
             payload_reduction=round(
                 base["payload_mb"] / r["payload_mb"], 2)
             if r["payload_mb"] > 0 else -1)


def run(quick: bool = False) -> None:
    t0 = time.time()
    _parity_section(quick)
    _temps_section(quick)
    _round_section(quick)
    _memory_section(quick)
    _codec_section(quick)
    emit("mix_fusion_done", seconds=round(time.time() - t0, 1))


if __name__ == "__main__":
    if "--codec" in sys.argv:
        _codec_section(quick="--quick" in sys.argv)
    else:
        run(quick="--quick" in sys.argv)
