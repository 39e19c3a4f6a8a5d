"""End-to-end DFL training driver — the paper's system on the TPU path.

Each device of the mesh's client axis hosts ``--clients-per-device``
FedLay clients (default 1): full model replicas training on their own
non-iid token shards, stacked on a leading local-client dim.  After
every local step the clients mix models over the FedLay overlay —
grouped ``ppermute`` rotations with MEP confidence weights inside
``shard_map``; with G > 1 intra-device edges never touch the wire — or
with the selectable baselines (``allreduce`` = centralized FedAvg
aggregation, ``ring``, ``none`` = isolated local training).

Runs on real multi-device meshes and on CPU via host-platform devices:

  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  PYTHONPATH=src python -m repro.launch.train --clients 8 --steps 200 \
      --sync fedlay --spaces 3

  # 16 clients on 8 devices (2 per device):
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  PYTHONPATH=src python -m repro.launch.train --clients 16 \
      --clients-per-device 2 --steps 200
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import sys
import time
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..core.mixing import build_permute_schedule
from .compile_cache import enable_compile_cache
from ..data.tokens import TokenStream
from ..dist.compat import make_client_mesh, shard_map
from ..dist.sync import make_mixer
from ..models.config import ArchConfig
from ..models.model import init_params, train_loss
from ..optim.optimizers import adamw, apply_updates, clip_by_global_norm


def tiny_lm(vocab: int = 512, d_model: int = 128, layers: int = 4) -> ArchConfig:
    return ArchConfig(name="tiny-lm", family="dense", num_layers=layers,
                      d_model=d_model, num_heads=4, num_kv_heads=2,
                      head_dim=32, d_ff=4 * d_model, vocab_size=vocab,
                      tie_embeddings=True, rope_theta=10_000.0)


def make_dfl_step(cfg: ArchConfig, optimizer, mixer, mesh: Mesh,
                  axis: str = "data", error_feedback: bool = False):
    """One DFL round: local grad step on each client, then overlay mix.
    The leading local-client dim inside shard_map is G (= 1 for the
    flat layout), so the local step vmaps over it.  With
    ``error_feedback`` (lossy wire codec) the step carries the (G, N)
    compression residual through the round."""

    def one(p, o, b):
        loss, grads = jax.value_and_grad(
            lambda q: train_loss(cfg, q, b, remat=False))(p)
        grads, _ = clip_by_global_norm(grads, 1.0)
        updates, o = optimizer.update(grads, o, p)
        return apply_updates(p, updates), o, loss

    spec_c = P(axis)       # leading client dim

    if error_feedback:
        def body_ef(params_l, opt_l, batch_l, w_l, sw_l, res_l):
            params_l, opt_l, loss = jax.vmap(one)(params_l, opt_l, batch_l)
            mixed, res_l = mixer(params_l, w_l, sw_l, res_l)
            mean_loss = jax.lax.pmean(jnp.mean(loss), axis)
            return mixed, opt_l, res_l, mean_loss

        body_sm = shard_map(
            body_ef, mesh=mesh,
            in_specs=(spec_c, spec_c, spec_c, spec_c, spec_c,
                      P(axis, None)),
            out_specs=(spec_c, spec_c, P(axis, None), P()),
            check_vma=False)
        return jax.jit(body_sm)

    def body(params_l, opt_l, batch_l, w_l, sw_l):
        params_l, opt_l, loss = jax.vmap(one)(params_l, opt_l, batch_l)
        mixed = mixer(params_l, w_l, sw_l)
        mean_loss = jax.lax.pmean(jnp.mean(loss), axis)
        return mixed, opt_l, mean_loss

    body_sm = shard_map(
        body, mesh=mesh,
        in_specs=(spec_c, spec_c, spec_c, spec_c, spec_c),
        out_specs=(spec_c, spec_c, P()),
        check_vma=False)
    return jax.jit(body_sm)


def run(args) -> Dict:
    n, G = args.clients, args.clients_per_device
    if n % G:
        raise SystemExit(f"--clients {n} must be a multiple of "
                         f"--clients-per-device {G}")
    mesh = make_client_mesh(n // G, "data")
    cfg = tiny_lm(vocab=args.vocab, d_model=args.d_model, layers=args.layers)

    # per-client params (same init — standard DFL assumption) + opt state
    key = jax.random.PRNGKey(args.seed)
    p0 = init_params(cfg, key)
    optimizer = adamw(args.lr, weight_decay=0.0)
    o0 = optimizer.init(p0)
    stack = lambda t: jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (n,) + x.shape), t)
    params, opt_state = stack(p0), stack(o0)
    shard_c = NamedSharding(mesh, P("data"))
    params = jax.tree.map(lambda x: jax.device_put(x, shard_c), params)
    opt_state = jax.tree.map(lambda x: jax.device_put(x, shard_c), opt_state)

    # FedLay overlay over client ids 0..n-1, compiled to the ppermute
    # schedule (MEP confidence weights from the per-client data skew)
    sched = build_permute_schedule(n, args.spaces)
    codec_name = getattr(args, "codec", None)
    mixer = make_mixer(args.sync, sched, "data", n, clients_per_device=G,
                       fuse=getattr(args, "fuse", None), codec=codec_name)
    weights = jax.device_put(jnp.asarray(sched.weights), shard_c)
    self_w = jax.device_put(jnp.asarray(sched.self_weight), shard_c)

    from ..dist.sync import resolve_wire
    codec, _ = resolve_wire(codec_name, getattr(args, "fuse", None))
    ef = (codec is not None and codec.error_feedback
          and args.sync in ("fedlay", "ring"))
    residual = None
    if ef:
        from ..dist.flat import FlatSpec
        nflat = FlatSpec.for_tree(params).size
        residual = jax.device_put(jnp.zeros((n, nflat), jnp.float32),
                                  NamedSharding(mesh, P("data", None)))

    # non-iid client shards
    streams = [iter(TokenStream(cfg.vocab_size, args.batch, args.seq,
                                seed=args.seed, client=c)) for c in range(n)]

    step_fn = make_dfl_step(cfg, optimizer, mixer, mesh, error_feedback=ef)

    # opt-in observability: --telemetry-out installs a bus + per-round
    # ledger for the run; --profile-dir wraps it in a profiler capture
    from ..obs import (RoundLedger, Telemetry, capture, round_ledger,
                       telemetry)
    from ..dist.sync import sync_bytes_per_client
    telemetry_out = getattr(args, "telemetry_out", None)
    bus = Telemetry() if telemetry_out else None
    ledger = RoundLedger(bus=bus) if telemetry_out else None
    row_elems = sum(int(np.prod(l.shape[1:], dtype=np.int64))
                    for l in jax.tree.leaves(params))
    wire = sync_bytes_per_client(
        args.sync, 4 * row_elems, n, num_spaces=args.spaces,
        clients_per_device=G, codec=codec_name)
    payload = (sync_bytes_per_client(
        args.sync, 4 * row_elems, n, num_spaces=args.spaces,
        clients_per_device=G) if codec_name is not None else wire)

    # crash/resume: --ckpt-dir periodically checkpoints the full
    # training state (params, optimizer state, EF residual) as a
    # flattened leaf list (optimizer states are NamedTuples the ckpt
    # treedef spec doesn't cover) and resumes from the newest
    # checkpoint on startup.  Data streams are deterministic in
    # (seed, client, step), so replaying from step k is exact.
    manager = None
    start_step = 0
    if getattr(args, "ckpt_dir", None):
        from ..ckpt.checkpoint import CheckpointManager
        manager = CheckpointManager(args.ckpt_dir)

        def _state():
            state = {"params": params, "opt_state": opt_state}
            if ef:
                state["residual"] = residual
            return state

        if manager.latest() is not None:
            tree, meta = manager.restore()
            template = _state()
            treedef = jax.tree.structure(template)
            leaves = [jnp.asarray(l) for l in tree["leaves"]]
            state = jax.tree.unflatten(treedef, leaves)
            put = lambda t: jax.tree.map(
                lambda x: jax.device_put(x, shard_c), t)
            params, opt_state = put(state["params"]), put(state["opt_state"])
            if ef:
                residual = jax.device_put(
                    state["residual"], NamedSharding(mesh, P("data", None)))
            start_step = int(meta["step"])
            # fast-forward the deterministic shards to the resume point
            for s in streams:
                for _ in range(start_step):
                    next(s)
            print(f"resumed from {args.ckpt_dir} at step {start_step}",
                  flush=True)

    losses = []
    t0 = time.time()
    with contextlib.ExitStack() as stack_ctx:
        if bus is not None:
            stack_ctx.enter_context(telemetry(bus))
            stack_ctx.enter_context(round_ledger(ledger))
        if getattr(args, "profile_dir", None):
            stack_ctx.enter_context(capture(args.profile_dir))
        for step in range(start_step, args.steps):
            xs, ys = zip(*(next(s) for s in streams))
            batch = {"tokens": jnp.asarray(np.stack(xs)),
                     "labels": jnp.asarray(np.stack(ys))}
            batch = jax.tree.map(lambda x: jax.device_put(x, shard_c), batch)
            if ef:
                params, opt_state, residual, loss = step_fn(
                    params, opt_state, batch, weights, self_w, residual)
            else:
                params, opt_state, loss = step_fn(params, opt_state, batch,
                                                  weights, self_w)
            losses.append(float(loss))
            if ledger is not None:
                bus.count("train.steps")
                ledger.record(round=step, time=time.time() - t0,
                              loop="train", num_alive=n, participating=n,
                              loss=losses[-1],
                              wire_bytes_per_client=wire,
                              payload_bytes_per_client=payload)
            if manager is not None and (
                    (step + 1) % max(getattr(args, "ckpt_every", 0), 1) == 0
                    or step == args.steps - 1):
                leaves = [np.asarray(jax.device_get(l))
                          for l in jax.tree.leaves(_state())]
                manager.save(step + 1, {"leaves": leaves})
            if step % args.log_every == 0 or step == args.steps - 1:
                print(f"step {step:5d}  loss {losses[-1]:.4f}  "
                      f"({(time.time()-t0)/(step+1):.2f}s/step)", flush=True)
    result = {"sync": args.sync, "clients": n, "clients_per_device": G,
              "steps": args.steps, "codec": codec_name,
              "start_step": start_step,
              "first_loss": losses[0] if losses else float("nan"),
              "final_loss": losses[-1] if losses else float("nan"),
              "losses": losses}
    if ledger is not None:
        rows = ledger.to_jsonl(telemetry_out)
        result["telemetry"] = ledger.summary()
        print(f"wrote {rows} round records to {telemetry_out}")
        print(ledger.summary_table())
    if args.out:
        with open(args.out, "w") as f:
            json.dump(result, f)
    return result


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--clients", type=int, default=None,
                    help="total clients (default: --clients-per-device "
                         "× devices)")
    ap.add_argument("--clients-per-device", type=int, default=1,
                    help="G local clients per mesh device "
                         "(total clients = G × devices)")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--sync", default="fedlay",
                    choices=["fedlay", "allreduce", "ring", "none"])
    ap.add_argument("--fuse", default=None,
                    choices=["tree", "flat"],
                    help="mixing-round execution: per-leaf tree walk "
                         "(default) or the flat-buffer Pallas fused path")
    ap.add_argument("--codec", default=None,
                    choices=["none", "bf16", "int8-block", "int4-block",
                             "topk"],
                    help="wire codec for the fedlay/ring gossip payload "
                         "(implies --fuse flat; lossy codecs carry an "
                         "error-feedback residual through the run)")
    ap.add_argument("--spaces", type=int, default=3)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--vocab", type=int, default=512)
    ap.add_argument("--d-model", type=int, default=128)
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=20)
    ap.add_argument("--ckpt-dir", default=None, metavar="DIR",
                    help="crash/resume: checkpoint the training state "
                         "into DIR every --ckpt-every steps and resume "
                         "from the newest checkpoint on startup")
    ap.add_argument("--ckpt-every", type=int, default=50,
                    help="steps between checkpoints (with --ckpt-dir)")
    ap.add_argument("--out", default=None)
    ap.add_argument("--telemetry-out", default=None, metavar="PATH",
                    help="enable the repro.obs plane for this run and "
                         "write the per-round ledger as JSONL to PATH "
                         "(also prints the summary table)")
    ap.add_argument("--profile-dir", default=None, metavar="PATH",
                    help="capture a jax.profiler trace of the run into "
                         "PATH (view with TensorBoard / Perfetto)")
    args = ap.parse_args()
    if args.clients is None:
        args.clients = args.clients_per_device * jax.device_count()
    enable_compile_cache()
    res = run(args)
    print(f"loss {res['first_loss']:.4f} -> {res['final_loss']:.4f}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
