"""Step builders: train / prefill / serve steps with their sharding specs.

Shared by the dry-run (lower+compile against ShapeDtypeStructs), the
real training drivers, and the benchmarks — one definition, everywhere.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..core.mixing import PermuteSchedule
from ..dist.sharding import (batch_spec, cache_specs, dfl_client_count,
                             enforce_divisibility, param_specs)
from ..dist.sync import SYNC_STRATEGIES, global_mixer, ring_schedule
from ..models import decode_step, init_cache, init_params, train_loss
from ..models.config import ArchConfig, InputShape
from ..obs.profile import scope
from ..optim.optimizers import (AdamWState, Optimizer, apply_updates,
                                clip_by_global_norm)


# --------------------------------------------------------------------------
# Standard (centralized-baseline) steps
# --------------------------------------------------------------------------

# Perf knob (§Perf hillclimb): sequence parallelism — shard the sequence
# dim of inter-layer activations over the model axis, so norms/residuals
# and the saved remat stacks are 16× smaller and row-parallel all-reduces
# lower to reduce-scatter + all-gather.  Baseline = False.
SEQ_PARALLEL = False


def _act_specs(mesh: Mesh):
    """(B,S,D) activation spec + (B,S,V) logit spec: batch over all data
    axes, vocab over model (d_model left unsharded; sequence/tensor
    sharding of activations is the SEQ_PARALLEL perf knob)."""
    dp = tuple(a for a in mesh.axis_names if a != "model")
    dp_spec = dp if len(dp) > 1 else dp[0]
    seq = "model" if SEQ_PARALLEL else None
    return P(dp_spec, seq, None), P(dp_spec, None, "model")


def make_train_step(cfg: ArchConfig, optimizer: Optimizer, mesh: Mesh,
                    remat: bool = True) -> Callable:
    act, logit = _act_specs(mesh)

    def train_step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(
            lambda p: train_loss(cfg, p, batch, remat=remat,
                                 act_spec=act, logit_spec=logit))(params)
        grads, gnorm = clip_by_global_norm(grads, 1.0)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = apply_updates(params, updates)
        return params, opt_state, {"loss": loss, "grad_norm": gnorm}
    return train_step


def make_prefill_step(cfg: ArchConfig, mesh: Mesh) -> Callable:
    """Forward-only loss eval at prefill shape (the inference-prefill
    dry-run target: logits over the full sequence)."""
    act, logit = _act_specs(mesh)

    def prefill_step(params, batch):
        loss = train_loss(cfg, params, batch, remat=False,
                          act_spec=act, logit_spec=logit)
        return {"loss": loss}
    return prefill_step


def make_serve_step(cfg: ArchConfig, mesh: Mesh) -> Callable:
    def serve_step(params, cache, batch):
        logits, cache = decode_step(cfg, params, cache, batch["token"])
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), cache
    return serve_step


# --------------------------------------------------------------------------
# Sharding-spec bundles
# --------------------------------------------------------------------------

def opt_state_specs(opt_state_shape, p_specs):
    """Optimizer-state specs mirror the param specs."""
    if isinstance(opt_state_shape, AdamWState):
        return AdamWState(mu=p_specs, nu=p_specs, count=P())
    if opt_state_shape == () or opt_state_shape is None:
        return ()
    return p_specs  # momentum tree


@dataclasses.dataclass
class StepBundle:
    """A jit-ready step with its arg specs (everything the dry-run and
    drivers need)."""
    step: Callable
    in_specs: Tuple
    out_specs: Any
    arg_shapes: Tuple


def train_bundle(cfg: ArchConfig, shape: InputShape, mesh: Mesh,
                 optimizer: Optimizer, dtype=jnp.bfloat16,
                 remat: bool = True, fsdp: Optional[str] = "data") -> StepBundle:
    from ..data.tokens import input_specs as data_specs
    dp = tuple(a for a in mesh.axis_names if a != "model")
    dp_spec = dp if len(dp) > 1 else dp[0]

    params_shape = jax.eval_shape(
        lambda: init_params(cfg, jax.random.PRNGKey(0), dtype=dtype))
    p_specs = param_specs(params_shape, fsdp=fsdp, tp="model")
    p_specs = enforce_divisibility(p_specs, params_shape, dict(mesh.shape))
    opt_shape = jax.eval_shape(optimizer.init, params_shape)
    o_specs = opt_state_specs(opt_shape, p_specs)

    b_shapes = data_specs(cfg, shape, dtype)
    b_spec_all = batch_spec("train", dp_axes=dp, tp="model")
    b_specs = {k: b_spec_all[k] for k in b_shapes}

    step = make_train_step(cfg, optimizer, mesh, remat=remat)
    return StepBundle(
        step=step,
        in_specs=(p_specs, o_specs, b_specs),
        out_specs=(p_specs, o_specs, {"loss": P(), "grad_norm": P()}),
        arg_shapes=(params_shape, opt_shape, b_shapes),
    )


def prefill_bundle(cfg: ArchConfig, shape: InputShape, mesh: Mesh,
                   dtype=jnp.bfloat16) -> StepBundle:
    from ..data.tokens import input_specs as data_specs
    dp = tuple(a for a in mesh.axis_names if a != "model")
    params_shape = jax.eval_shape(
        lambda: init_params(cfg, jax.random.PRNGKey(0), dtype=dtype))
    p_specs = param_specs(params_shape, fsdp="data", tp="model")
    p_specs = enforce_divisibility(p_specs, params_shape, dict(mesh.shape))
    b_shapes = data_specs(cfg, shape, dtype)
    b_spec_all = batch_spec("prefill", dp_axes=dp, tp="model")
    b_specs = {k: b_spec_all[k] for k in b_shapes}
    return StepBundle(
        step=make_prefill_step(cfg, mesh),
        in_specs=(p_specs, b_specs),
        out_specs={"loss": P()},
        arg_shapes=(params_shape, b_shapes),
    )


# Perf knob (§Perf hillclimb): serving keeps params FSDP-sharded over
# the data axis by default (baseline, minimal HBM) — but then EVERY
# decode step all-gathers every layer's weights.  True = weight-
# stationary serving: params sharded over the model axis only
# (replicated across data), trading HBM for zero per-token parameter
# collectives.  Only valid when params_bf16/model_axis fits HBM.
SERVE_WEIGHT_STATIONARY = False


def serve_bundle(cfg: ArchConfig, shape: InputShape, mesh: Mesh,
                 dtype=jnp.bfloat16) -> StepBundle:
    from ..data.tokens import enc_frames_for, input_specs as data_specs
    dp = tuple(a for a in mesh.axis_names if a != "model")
    dp_spec = dp if len(dp) > 1 else dp[0]
    B = shape.global_batch

    params_shape = jax.eval_shape(
        lambda: init_params(cfg, jax.random.PRNGKey(0), dtype=dtype))
    fsdp = None if SERVE_WEIGHT_STATIONARY else "data"
    p_specs = param_specs(params_shape, fsdp=fsdp, tp="model")
    p_specs = enforce_divisibility(p_specs, params_shape, dict(mesh.shape))

    enc_shape = None
    if cfg.enc_dec:
        enc_shape = jax.ShapeDtypeStruct(
            (B, enc_frames_for(cfg, shape.seq_len), cfg.d_model), dtype)
    cache_shape = jax.eval_shape(
        functools.partial(init_cache, cfg, batch=B, cache_len=shape.seq_len,
                          dtype=dtype),
        params_shape, enc_embeds=enc_shape)
    dp_size = 1
    for a in dp:
        dp_size *= mesh.shape[a]
    shard_batch = (B % dp_size == 0)
    c_specs = cache_specs(cache_shape, dp=dp_spec, tp="model",
                          shard_batch=shard_batch)
    c_specs = enforce_divisibility(c_specs, cache_shape, dict(mesh.shape))

    b_shapes = data_specs(cfg, shape, dtype)
    b_specs = {"token": P(dp_spec if shard_batch else None, None)}
    return StepBundle(
        step=make_serve_step(cfg, mesh),
        in_specs=(p_specs, c_specs, b_specs),
        out_specs=(P(dp_spec if shard_batch else None), c_specs),
        arg_shapes=(params_shape, cache_shape, b_shapes),
    )


# --------------------------------------------------------------------------
# DFL-mode training: the paper's technique at production scale.
# Every position of the client axis (= data axis) holds one FedLay
# client's full replica (leading num_clients dim; TP over model inside
# the replica; no FSDP — clients own their weights).  After the local
# step, models mix over the overlay via ``repro.dist.sync.global_mixer``
# (permutation gathers along the client-sharded axis — GSPMD lowers them
# to collective-permutes, i.e. exactly the paper's neighbor-to-neighbor
# exchange).  ``sync`` selects the strategy: "fedlay", "ring",
# "allreduce" (uniform mean = centralized baseline), or "none".
# --------------------------------------------------------------------------

def dfl_train_bundle(cfg: ArchConfig, shape: InputShape, mesh: Mesh,
                     optimizer: Optimizer, dtype=jnp.bfloat16,
                     sync: str = "fedlay", num_spaces: int = 3,
                     remat: bool = True,
                     sched: Optional[PermuteSchedule] = None,
                     masked: bool = False,
                     clients_per_device: int = 1,
                     fuse: Optional[str] = None,
                     codec=None) -> StepBundle:
    """``sched`` overrides the internally built overlay schedule, e.g.
    to bake an :class:`repro.overlay.OverlayController`'s converged NDMP
    schedule into a static bundle; when None the static overlay over
    mesh data positions is built here.  (The live-churn loop,
    :class:`repro.overlay.runtime.ChurnTrainLoop`, instead composes a
    ``sync="none"`` bundle with the controller's hot-swapped mixer, so
    the local step never recompiles on topology change.)

    ``masked=True`` builds the mask-aware step for the fixed-capacity
    slot runtime (:class:`repro.runtime.SlotTrainLoop`) and multirate
    participation: the step signature gains a trailing (C,) float32
    0/1 ``mask`` input — dead or non-participating slots compute but
    their param/optimizer updates are ``where``-gated away, mixing
    drops masked-out sources and renormalizes
    (:func:`repro.dist.sync.global_mixer` ``masked`` path), and the
    reported loss is the masked mean over live slots.  The mask is a
    runtime input, so it changes every step with zero retrace.

    ``clients_per_device`` (G) sizes the client axis at
    ``C = G · num_devices`` (:func:`repro.dist.sharding.dfl_client_count`)
    — the grouped layout: each data-axis device hosts a block-contiguous
    group of G clients, so a simulation (or a capacity-mode slot runtime
    with ``capacity = C``) is no longer capped at the device count.
    GSPMD keeps intra-group mixing edges on-device for free.

    ``fuse="flat"`` (opt-in) swaps the mixing step onto the flat-buffer
    fused hot path: the stacked params tree is raveled once into a
    lane-padded (C, N) buffer and the whole round runs as one Pallas
    :func:`repro.kernels.weighted_mix.gather_mix` kernel
    (:func:`repro.dist.sync.global_mixer` ``fuse`` docs; masked rounds
    stay zero-retrace runtime-mask programs).

    ``codec`` (a :mod:`repro.wire.codec` name or instance) compresses
    the fedlay/ring gossip wire (implies ``fuse="flat"``).  For an
    **error-feedback** codec the step signature grows a trailing
    (C, N) f32 ``residual`` arg and returns the fresh residual —
    ``in_specs``/``arg_shapes``/``out_specs`` all carry the extra leaf,
    sharded over the client axis like every capacity-stacked row.
    allreduce/none sync ignores the codec."""
    from ..core.mixing import build_permute_schedule
    from ..data.tokens import input_specs as data_specs
    if sync not in SYNC_STRATEGIES:
        raise ValueError(
            f"unknown sync strategy {sync!r}; choose from {SYNC_STRATEGIES}")
    dp = tuple(a for a in mesh.axis_names if a != "model")
    client_axis = dp if len(dp) > 1 else dp[0]
    C = dfl_client_count(mesh, clients_per_device)
    if shape.global_batch % C:
        raise ValueError(
            f"global batch {shape.global_batch} does not divide over "
            f"{C} clients ({clients_per_device} per device)")
    # multi-pod: bias 2 of the L ring spaces pod-local (the §Perf Pareto
    # point) so most mixing volume stays on intra-pod links
    pods = mesh.shape.get("pod")
    if sched is not None:
        if sync not in ("fedlay", "ring"):
            raise ValueError(
                f"an explicit schedule only applies to fedlay/ring sync, "
                f"not {sync!r}")
        if sched.num_clients != C:
            raise ValueError(
                f"schedule is for {sched.num_clients} clients, mesh data "
                f"axes hold {C}")
    elif sync == "fedlay":
        sched = build_permute_schedule(
            C, num_spaces, pod_bias=pods if pods and pods > 1 else None,
            pod_bias_spaces=max(1, num_spaces - 1) if pods and pods > 1
            else None)
    elif sync == "ring":
        sched = ring_schedule(C)
    mix = global_mixer(sync, sched, masked=masked,
                       clients_per_device=clients_per_device, fuse=fuse,
                       codec=codec)
    from ..dist.sync import resolve_wire
    wire_codec, _ = resolve_wire(codec, fuse)
    ef = (wire_codec is not None and wire_codec.error_feedback
          and sync in ("fedlay", "ring"))

    params_shape = jax.eval_shape(
        lambda: init_params(cfg, jax.random.PRNGKey(0), dtype=dtype))
    stacked_shape = jax.tree.map(
        lambda l: jax.ShapeDtypeStruct((C,) + l.shape, l.dtype), params_shape)
    p_specs = param_specs(stacked_shape, client_axis=client_axis, tp="model")
    p_specs = enforce_divisibility(p_specs, stacked_shape, dict(mesh.shape))
    opt_shape = jax.eval_shape(jax.vmap(optimizer.init), stacked_shape)
    if isinstance(opt_shape, AdamWState):
        o_specs: Any = AdamWState(mu=p_specs, nu=p_specs, count=P(None))
    else:
        o_specs = opt_state_specs(opt_shape, p_specs)

    b_shapes = data_specs(cfg, shape, dtype)
    # batch (B, S): per-client slice = B/C rows; reshape to (C, B/C, S)
    b_shapes = {k: jax.ShapeDtypeStruct(
        (C, v.shape[0] // C) + v.shape[1:], v.dtype)
        for k, v in b_shapes.items()}
    b_specs = {k: P(client_axis, *([None] * (len(v.shape) - 1)))
               for k, v in b_shapes.items()}

    act = P(None, None, None)

    def per_client_loss(p, b):
        return train_loss(cfg, p, b, remat=remat, act_spec=act)

    # the named scopes label the step's ops on the profiler's device
    # planes (the benchmark's ``optimizer_ms`` and ``fwd_bwd_ms`` read
    # them)
    def local_updates(params, opt_state, batch):
        with scope("step.fwd_bwd"):
            loss, grads = jax.vmap(jax.value_and_grad(per_client_loss))(
                params, batch)
        with scope("step.optimizer"):
            grads, _ = jax.vmap(lambda g: clip_by_global_norm(g, 1.0))(
                grads)
            updates, opt_state = jax.vmap(optimizer.update)(
                grads, opt_state, params)
            params = jax.vmap(apply_updates)(params, updates)
        return params, opt_state, loss

    r_spec = r_shape = None
    if ef:
        from ..dist.flat import FlatSpec
        r_spec = P(client_axis, None)
        r_shape = jax.ShapeDtypeStruct(
            (C, FlatSpec.for_tree(stacked_shape).size), jnp.float32)

    if masked:
        from ..runtime.masked import masked_mean, masked_where

        def masked_local(params, opt_state, batch, mask):
            new_params, new_opt, loss = local_updates(params, opt_state,
                                                      batch)
            with scope("step.optimizer"):
                params = masked_where(mask, new_params, params)
                opt_state = masked_where(mask, new_opt, opt_state)
            return params, opt_state, {"loss": masked_mean(loss, mask),
                                       "num_alive": jnp.sum(mask)}

        if ef:
            def masked_train_step_ef(params, opt_state, batch, mask,
                                     residual):
                params, opt_state, metrics = masked_local(
                    params, opt_state, batch, mask)
                params, residual = mix(params, mask, residual)
                return params, opt_state, metrics, residual

            return StepBundle(
                step=masked_train_step_ef,
                in_specs=(p_specs, o_specs, b_specs, P(client_axis),
                          r_spec),
                out_specs=(p_specs, o_specs,
                           {"loss": P(), "num_alive": P()}, r_spec),
                arg_shapes=(stacked_shape, opt_shape, b_shapes,
                            jax.ShapeDtypeStruct((C,), jnp.float32),
                            r_shape),
            )

        def masked_train_step(params, opt_state, batch, mask):
            params, opt_state, metrics = masked_local(
                params, opt_state, batch, mask)
            params = mix(params, mask)
            return params, opt_state, metrics

        return StepBundle(
            step=masked_train_step,
            in_specs=(p_specs, o_specs, b_specs, P(client_axis)),
            out_specs=(p_specs, o_specs, {"loss": P(), "num_alive": P()}),
            arg_shapes=(stacked_shape, opt_shape, b_shapes,
                        jax.ShapeDtypeStruct((C,), jnp.float32)),
        )

    if ef:
        def train_step_ef(params, opt_state, batch, residual):
            params, opt_state, loss = local_updates(params, opt_state,
                                                    batch)
            params, residual = mix(params, residual)
            return params, opt_state, {"loss": jnp.mean(loss)}, residual

        return StepBundle(
            step=train_step_ef,
            in_specs=(p_specs, o_specs, b_specs, r_spec),
            out_specs=(p_specs, o_specs, {"loss": P()}, r_spec),
            arg_shapes=(stacked_shape, opt_shape, b_shapes, r_shape),
        )

    def train_step(params, opt_state, batch):
        params, opt_state, loss = local_updates(params, opt_state, batch)
        params = mix(params)
        return params, opt_state, {"loss": jnp.mean(loss)}

    return StepBundle(
        step=train_step,
        in_specs=(p_specs, o_specs, b_specs),
        out_specs=(p_specs, o_specs, {"loss": P()}),
        arg_shapes=(stacked_shape, opt_shape, b_shapes),
    )


def bundle_for(cfg: ArchConfig, shape: InputShape, mesh: Mesh,
               optimizer: Optional[Optimizer] = None,
               dtype=jnp.bfloat16) -> StepBundle:
    if shape.kind == "train":
        assert optimizer is not None
        return train_bundle(cfg, shape, mesh, optimizer, dtype=dtype)
    if shape.kind == "prefill":
        return prefill_bundle(cfg, shape, mesh, dtype=dtype)
    return serve_bundle(cfg, shape, mesh, dtype=dtype)


def jit_bundle(bundle: StepBundle, mesh: Mesh):
    to_shard = lambda tree: jax.tree.map(
        lambda s: NamedSharding(mesh, s), tree,
        is_leaf=lambda x: isinstance(x, P))
    return jax.jit(bundle.step,
                   in_shardings=to_shard(bundle.in_specs),
                   out_shardings=to_shard(bundle.out_specs))
