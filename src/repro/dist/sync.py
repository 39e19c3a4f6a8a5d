"""FedLay mixing compiled onto the device mesh (the paper's NDMP tables
as static collectives).

The control plane (``repro.core.ndmp``) converges neighbor tables
host-side; ``repro.core.mixing.build_permute_schedule`` (static mesh
layout) or ``repro.core.mixing.schedule_from_addresses`` (the live NDMP
alive set, via :class:`repro.overlay.OverlayController`) freezes them
into a :class:`~repro.core.mixing.PermuteSchedule` (2L ring rotations +
MEP confidence weights).  Schedules hash by content, so the overlay
controller keys its mixer compile cache on them and hot-swaps the
programs built here between training steps under churn.  This module
turns a schedule into device programs two ways:

* :func:`fedlay_mix` / :func:`make_mixer` — the explicit ``shard_map``
  path: with the 1:1 layout (one client per device) one
  ``jax.lax.ppermute`` per (space × direction) slot; with the **grouped
  layout** (``clients_per_device = G > 1``) each device holds G
  clients' replicas as a leading local-client dim, intra-device edges
  become local gathers (zero network bytes), and cross-device edges run
  as the edge-colored batched ppermute rounds of
  :func:`repro.core.mixing.grouped_routing`.  Verified equal to the
  dense ``schedule_mixing_matrix`` / ``masked_mixing_matrix`` products
  in ``tests/test_dist.py`` and ``tests/test_grouped.py``.
* :func:`global_mixer` — the global-view (auto-sharded jit) path used by
  ``repro.launch.steps.dfl_train_bundle``: permutation gathers along the
  leading client axis, which GSPMD lowers to collective-permutes when
  that axis is client-sharded.  Layout-agnostic: with ``num_clients =
  G · num_devices`` rows client-sharded over the mesh, GSPMD routes
  on-device rows locally for free.

**The grouped ``(G, ...)`` contract** (shard_map path): the client axis
maps onto devices block-contiguously — client ``i`` lives on device
``i // G`` at local row ``i % G``; every tree leaf carries a leading
local-client dim of size G, ``weights`` is the local (G, 2L) slice of
the schedule's weight table and ``self_weight`` the local (G,) slice
(i.e. the (n, 2L)/(n,) host tables sharded over the client axis), and
``mask`` — when given — the local (G,) slice of the (n,) participation
mask.  ``G == 1`` degenerates to the original one-ppermute-per-slot
program.

**The flat-buffer fused hot path** (``fuse="flat"``, opt-in on both
mixer families): instead of walking the tree once per leaf per slot —
which materializes up to 2L full-model temporaries per round — the
params tree is raveled once into a contiguous lane-padded (B, N)
buffer (:class:`repro.dist.flat.FlatSpec`: per-leaf dtype-preserving
lane-aligned offsets) and the whole round runs on that buffer with the
:mod:`repro.kernels.weighted_mix` Pallas kernels:

* shard_map path — each ppermute moves one flat row; every received
  row streams into the accumulator via the incremental
  :func:`~repro.kernels.weighted_mix.mix_accumulate` entry, so only
  {own, acc, current receive} ever exist at once, independent of 2L;
* global path — one :func:`~repro.kernels.weighted_mix.gather_mix`
  kernel per round over the resident (C, N) population buffer: static
  source rows (the schedule's perms), runtime weight table.  Masking
  (dead capacity slots, multirate skips) only rewrites the (C, 2L+1)
  weight table — renormalizing over surviving sources, identity rows
  for dead clients — with **zero retrace**.  Note GSPMD treats the
  kernel as opaque, so the fused global path shines where the
  population buffer is resident per process (slot runtime, capacity
  controllers); wire-optimal multi-device mixing stays with the
  shard_map path.

Both fused paths are pinned ≡ the dense ``masked_mixing_matrix`` /
``schedule_mixing_matrix`` oracles (and the tree walk) in
``tests/test_flat.py``.

Plus :func:`sync_bytes_per_client`, the paper's per-round communication
accounting (§IV-D / Fig. 20) shared by the scalability benchmarks —
grouped mixing pays network bytes only for cross-device edges.
"""

from __future__ import annotations

from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core.mixing import PermuteSchedule, check_group_size, grouped_routing
from ..kernels.weighted_mix import gather_mix, mix_accumulate
from ..obs.events import get_telemetry
from ..obs.profile import scope
from ..wire.codec import WireCodec, get_codec
from .flat import FlatSpec, map_dtype_buffers

#: Sync strategies understood by both mixer factories.
SYNC_STRATEGIES = ("fedlay", "allreduce", "ring", "none")

#: Mixing-round execution modes: ``None``/``"tree"`` — the per-leaf jnp
#: tree walk; ``"flat"`` — the FlatSpec + Pallas fused hot path.
FUSE_MODES = (None, "tree", "flat")


def check_fuse(fuse: Optional[str]) -> Optional[str]:
    """Validate a fuse mode and normalize the default spelling
    (``"tree"`` ≡ ``None``, the unfused walk)."""
    if fuse not in FUSE_MODES:
        raise ValueError(
            f"unknown fuse mode {fuse!r}; choose from {FUSE_MODES}")
    return None if fuse == "tree" else fuse


def resolve_wire(codec, fuse: Optional[str]
                 ) -> "tuple[Optional[WireCodec], Optional[str]]":
    """Normalize the ``(codec, fuse)`` knob pair shared by every mixing
    entry point.  Codecs operate on the flat row buffer
    (:mod:`repro.wire.codec` wire-format contract), so any codec —
    including the exact ``"none"`` — implies ``fuse="flat"``; without a
    codec the fuse mode passes through unchanged (``None`` stays the
    tree walk, byte-identical to pre-codec behavior)."""
    fuse = check_fuse(fuse)
    codec = get_codec(codec)
    if codec is not None:
        fuse = "flat"
    return codec, fuse


def ring_schedule(num_clients: int) -> PermuteSchedule:
    """The identity-ring overlay as a PermuteSchedule: one space, simple
    average over {self, predecessor, successor} (degenerates correctly
    at n ≤ 2, where the two directions collide)."""
    n = num_clients
    pred = tuple((i - 1) % n for i in range(n))
    succ = tuple((i + 1) % n for i in range(n))
    weights = np.zeros((n, 2), dtype=np.float64)
    self_w = np.ones((n,), dtype=np.float64)
    for i in range(n):
        seen = {i}
        for k, src in enumerate((pred[i], succ[i])):
            if src not in seen:
                weights[i, k] = 1.0
                seen.add(src)
    total = self_w + weights.sum(axis=1)
    weights /= total[:, None]
    self_w /= total
    return PermuteSchedule(num_clients=n, num_spaces=1, perms=(pred, succ),
                           weights=weights.astype(np.float32),
                           self_weight=self_w.astype(np.float32))


def fedlay_mix(tree, sched: PermuteSchedule, weights: jnp.ndarray,
               self_weight: jnp.ndarray, axis_name: str,
               mask: Optional[jnp.ndarray] = None,
               fuse: Optional[str] = None,
               codec=None, residual: Optional[jnp.ndarray] = None):
    """One FedLay mixing round inside ``shard_map``.

    ``tree`` leaves carry a leading local-client dim of size G (the
    module-level grouped ``(G, ...)`` contract: client ``i`` lives on
    device ``i // G``, so ``sched.num_clients == G · axis_size``);
    ``weights`` is the local (G, 2L) confidence-weight slice and
    ``self_weight`` the local (G,) self weight.  Equivalent to the dense
    ``W @ X`` of ``schedule_mixing_matrix(sched)``.

    With ``G == 1`` (the original 1:1 layout) each slot is one
    ``ppermute`` of the full local replica.  With ``G > 1`` edges whose
    source lives on the same device are local gathers (zero network
    bytes) and cross-device edges run as the edge-colored ppermute
    rounds of :func:`repro.core.mixing.grouped_routing` — at most ~G
    batched single-row permutes per slot, moving exactly the weight>0
    cross edges.

    ``mask`` (optional, local (G,) 0/1 float) makes the round mask-aware:
    a masked-out client (dead capacity slot, or a slow client skipping
    this collective under multirate participation) keeps its own model,
    and live clients drop its contribution and renormalize over the
    surviving weights — the per-device image of
    :func:`repro.core.mixing.masked_mixing_matrix`.  The mask rides the
    same routing as the models, so masking adds scalar permutes, not a
    retrace.

    ``fuse="flat"`` (opt-in) runs the round on the flat-buffer fused
    hot path (module docstring): the tree is raveled once into a
    lane-padded (G, N) buffer, each slot's receive moves that one row
    and streams straight into the Pallas
    :func:`~repro.kernels.weighted_mix.mix_accumulate` accumulator —
    same routing, same weights, same mask semantics, O(1) live
    full-model temporaries instead of one per leaf per slot.

    ``codec`` (a :mod:`repro.wire.codec` name or instance; implies the
    flat path) compresses the wire: each slot's receive routes the
    *encoded* parts of the own flat row — int8 payload + per-block
    scales, or top-k (values, indices) — through exactly the same
    ppermute/grouped routing, and the receive folds them into the f32
    accumulator via the codec's fused
    :meth:`~repro.wire.codec.WireCodec.accumulate` (the decompressed 2L
    stack never exists).  The self term always uses the true local row
    (it is never on the wire), so exact codecs reproduce the
    uncompressed round bit-for-bit and lossy ones stay within the
    codec's documented per-element tolerance of the dense oracle.  For
    an error-feedback codec, ``residual`` ((G, N) f32) is required and
    the call returns ``(tree, new_residual)``: the wire carries
    ``enc(buf + residual)``; masked-out rows keep their residual
    unchanged (they send nothing anyone counts).
    """
    codec, fuse = resolve_wire(codec, fuse)
    ef = codec is not None and codec.error_feedback
    if ef and residual is None:
        raise ValueError(
            f"codec {codec.name!r} uses error feedback; pass the (G, N) "
            f"residual state (and consume the returned new residual)")
    G = jax.tree.leaves(tree)[0].shape[0]
    # psum of a literal is evaluated statically under shard_map tracing,
    # so a schedule/mesh layout mismatch fails loudly at trace time
    # instead of silently mixing zeros on the surplus devices.
    axis_size = jax.lax.psum(1, axis_name)
    if isinstance(axis_size, int) and sched.num_clients != G * axis_size:
        raise ValueError(
            f"schedule is for {sched.num_clients} clients but the "
            f"grouped layout holds {G} × {axis_size} devices on axis "
            f"{axis_name!r}")
    masked = mask is not None

    if G == 1:
        # 1:1 layout: one full-replica ppermute per slot (the original
        # program; grouped routing degenerates to this anyway, but the
        # direct form keeps existing compiled programs byte-stable).
        def receive(x, k):
            return jax.lax.ppermute(x, axis_name,
                                    perm=sched.ppermute_pairs(k))
    else:
        rt = grouped_routing(sched, G)
        i = jax.lax.axis_index(axis_name)

        def receive(x, k):
            isrc = jnp.asarray(rt.intra_src[k])[i]          # (G,)
            ion = jnp.asarray(rt.intra_on[k])[i]            # (G,)
            shape = (G,) + (1,) * (x.ndim - 1)
            recv = jnp.take(x, isrc, axis=0) * ion.reshape(shape).astype(
                x.dtype)
            for rnd in rt.rounds[k]:
                row = jnp.take(x, jnp.asarray(rnd.send_row)[i], axis=0)
                got = jax.lax.ppermute(row, axis_name,
                                       perm=list(rnd.pairs))
                on = jnp.asarray(rnd.recv_on)[i].astype(x.dtype)
                recv = recv.at[jnp.asarray(rnd.recv_slot)[i]].add(got * on)
            return recv

    if masked:
        m = mask.astype(jnp.float32)
        eff = [weights[:, k].astype(jnp.float32) * receive(m, k)
               for k in range(sched.num_slots)]
        total = self_weight.astype(jnp.float32) + sum(eff)
        ok = (m > 0) & (total > 0)
        safe = jnp.where(total > 0, total, 1.0)
        self_w = (self_weight.astype(jnp.float32) / safe)
        slot_w = [e / safe for e in eff]
    else:
        self_w = self_weight
        slot_w = [weights[:, k] for k in range(sched.num_slots)]

    if fuse == "flat":
        spec = FlatSpec.for_tree(tree)
        buf = spec.ravel(tree)                       # (G, N) lane-padded
        if codec is not None:
            # trace-time tick: codec paths run inside jit, so these
            # count (re)compiles of the codec program — steady state
            # with a warm MixerCache adds zero.
            bus = get_telemetry()
            bus.count("wire.encodes")
            bus.count("wire.decodes", sched.num_slots)
            with scope(f"wire.{codec.name}.encode"):
                if ef:
                    if residual.shape != buf.shape:
                        raise ValueError(
                            f"residual shape {residual.shape} != flat "
                            f"buffer {buf.shape}")
                    wire, res = codec.encode_ef(buf + residual)
                    if masked:
                        res = jnp.where((m > 0)[:, None], res, residual)
                else:
                    wire, res = codec.encode(buf), None
            acc = mix_accumulate(None, buf, self_w)
            for k in range(sched.num_slots):
                with scope(f"fedlay_mix.slot{k}"):
                    wk = tuple(receive(part, k) for part in wire)
                    acc = codec.accumulate(acc, wk, slot_w[k])
            if masked:
                acc = jnp.where(ok[:, None], acc, buf)
            out = spec.unravel(acc)
            return (out, res) if ef else out
        acc = mix_accumulate(None, buf, self_w)
        for k in range(sched.num_slots):
            with scope(f"fedlay_mix.slot{k}"):
                acc = mix_accumulate(acc, receive(buf, k), slot_w[k])
        if masked:
            acc = jnp.where(ok[:, None], acc, buf)
        return spec.unravel(acc)

    def mix_leaf(leaf):
        shape = (G,) + (1,) * (leaf.ndim - 1)
        acc = leaf * self_w.reshape(shape).astype(leaf.dtype)
        for k in range(sched.num_slots):
            recv = receive(leaf, k)
            w = slot_w[k].reshape(shape).astype(leaf.dtype)
            acc = acc + recv * w
        if masked:
            acc = jnp.where(ok.reshape(shape), acc, leaf)
        return acc

    return jax.tree.map(mix_leaf, tree)


def make_mixer(strategy: str, sched: Optional[PermuteSchedule],
               axis_name: str, num_clients: int,
               clients_per_device: int = 1,
               fuse: Optional[str] = None,
               codec=None) -> Callable:
    """Build a ``shard_map``-body mixer ``(tree, weights, self_w) -> tree``
    for one sync strategy over the client axis ``axis_name``.

    ``num_clients`` is the **total** client count; with
    ``clients_per_device = G > 1`` the mesh axis holds ``num_clients / G``
    devices and tree leaves carry the grouped leading (G, ...) dim (the
    module-level contract).  ``fuse="flat"`` selects the flat-buffer
    fused hot path for the fedlay/ring rounds (module docstring);
    allreduce/none have no per-slot accumulate to fuse and ignore it.

    ``codec`` (:mod:`repro.wire.codec`) compresses the fedlay/ring
    gossip wire (implies ``fuse="flat"``; see :func:`fedlay_mix`).  For
    an error-feedback codec the mixer signature grows a trailing
    residual: ``(tree, weights, self_w, residual) -> (tree, residual)``.
    allreduce reduces in-network (no per-neighbor wire to compress) and
    none sends nothing, so both ignore ``codec``.

    * ``fedlay``   — static ppermutes from ``sched`` (paper §III); with
      G > 1, intra-device sub-mixing + edge-colored cross-device rounds;
    * ``allreduce``— uniform mean over all clients (centralized image;
      local G-row mean, then ``pmean`` over devices);
    * ``ring``     — identity-ring neighbor average (ignores ``sched``'s
      weights; uses its own uniform ring schedule over all clients);
    * ``none``     — isolated local training.
    """
    G = clients_per_device
    check_group_size(num_clients, G)
    codec, fuse = resolve_wire(codec, fuse)
    ef = (codec is not None and codec.error_feedback
          and strategy in ("fedlay", "ring"))

    if strategy == "none":
        return lambda tree, weights, self_w: tree

    if strategy == "allreduce":
        def allreduce_mixer(tree, weights, self_w):
            def mean_leaf(leaf):
                m = jnp.mean(leaf.astype(jnp.float32), axis=0, keepdims=True)
                m = jax.lax.pmean(m, axis_name)
                return jnp.broadcast_to(m.astype(leaf.dtype), leaf.shape)
            return jax.tree.map(mean_leaf, tree)
        return allreduce_mixer

    if strategy == "ring":
        ring = ring_schedule(num_clients)
        ring_w = jnp.asarray(ring.weights)
        ring_s = jnp.asarray(ring.self_weight)

        if ef:
            def ring_mixer_ef(tree, weights, self_w, residual):
                i = jax.lax.axis_index(axis_name)
                w = jax.lax.dynamic_slice_in_dim(ring_w, i * G, G, axis=0)
                s = jax.lax.dynamic_slice_in_dim(ring_s, i * G, G, axis=0)
                return fedlay_mix(tree, ring, w, s, axis_name, fuse=fuse,
                                  codec=codec, residual=residual)
            return ring_mixer_ef

        def ring_mixer(tree, weights, self_w):
            i = jax.lax.axis_index(axis_name)
            w = jax.lax.dynamic_slice_in_dim(ring_w, i * G, G, axis=0)
            s = jax.lax.dynamic_slice_in_dim(ring_s, i * G, G, axis=0)
            return fedlay_mix(tree, ring, w, s, axis_name, fuse=fuse,
                              codec=codec)
        return ring_mixer

    if strategy == "fedlay":
        if sched is None:
            raise ValueError("fedlay mixer needs a PermuteSchedule")
        if sched.num_clients != num_clients:
            raise ValueError(
                f"schedule is for {sched.num_clients} clients, "
                f"mesh axis {axis_name!r} holds {num_clients} "
                f"(= {num_clients // G} devices × {G})")
        if ef:
            return lambda tree, weights, self_w, residual: fedlay_mix(
                tree, sched, weights, self_w, axis_name, fuse=fuse,
                codec=codec, residual=residual)
        return lambda tree, weights, self_w: fedlay_mix(
            tree, sched, weights, self_w, axis_name, fuse=fuse, codec=codec)

    raise ValueError(
        f"unknown sync strategy {strategy!r}; choose from {SYNC_STRATEGIES}")


def global_mixer(strategy: str,
                 sched: Optional[PermuteSchedule] = None,
                 masked: bool = False,
                 clients_per_device: int = 1,
                 fuse: Optional[str] = None,
                 codec=None,
                 flat_io: bool = False) -> Callable:
    """Build a global-view mixer ``params -> params`` over the leading
    client axis (for auto-sharded jit, e.g. ``dfl_train_bundle``).

    For ``fedlay``/``ring`` each of the 2L slots is a permutation gather
    ``params[perm_k]`` along the client dim — GSPMD lowers it to a
    collective-permute when that dim is client-sharded, i.e. exactly the
    neighbor exchange :func:`fedlay_mix` spells out by hand.

    The global view is grouped-layout agnostic: the program operates on
    all ``sched.num_clients`` rows and GSPMD routes whatever fraction of
    each permutation stays on-device for free, so ``clients_per_device``
    is validation-only here — it asserts the client count divides into
    groups of G (``num_clients = G · num_devices``) so the caller's
    client-sharded leading axis actually lands G rows per device.

    With ``masked=True`` the returned callable is ``(params, mask) ->
    params`` where ``mask`` is a (C,) 0/1 float *runtime input* (no
    retrace when it changes): masked-out rows keep their own model, live
    rows drop masked-out sources and renormalize — the device image of
    :func:`repro.core.mixing.masked_mixing_matrix`.  This is the seam
    the fixed-capacity slot runtime (dead slots) and multirate
    participation (slow clients skipping a collective) both plug into.
    Masked fedlay/ring mixers additionally accept a keyword-only
    ``edge_mask`` — a (C, 2L) 0/1 runtime input that drops individual
    unreachable edges before renormalizing (degraded rounds under
    :mod:`repro.faults`); like ``mask`` it is a runtime value, so fault
    storms never retrace.

    ``fuse="flat"`` (fedlay/ring) replaces the per-leaf permutation
    gathers with **one Pallas kernel per round** over the raveled
    (C, N) population buffer
    (:func:`repro.kernels.weighted_mix.gather_mix`): the schedule's
    perms become a static (C, 2L+1) source-row table (column 0 = self)
    and the confidence weights a runtime (C, 2L+1) table.  The masked
    variant only rewrites that weight table — renormalized over
    surviving sources, identity rows for dead/starved clients — so the
    mask stays a zero-retrace runtime input.  allreduce/none have no
    per-slot accumulate to fuse and ignore ``fuse``.

    ``codec`` (:mod:`repro.wire.codec`; implies ``fuse="flat"``)
    compresses the fedlay/ring round: the population buffer is encoded
    once per round and the neighbor term mixes the *encoded* form
    through the codec's fused
    :meth:`~repro.wire.codec.WireCodec.gather` (int8: the
    :func:`repro.kernels.wire_codec.gather_mix_int8` round-matrix
    kernel dequantizing tiles in VMEM), while the self term always uses
    the true row.  For an error-feedback codec the signature grows a
    trailing (C, N) f32 residual and returns ``(params, residual)``
    (masked rows keep their residual).  allreduce/none ignore ``codec``
    (no per-neighbor wire).

    ``flat_io=True`` (fedlay/ring flat path only) makes the mixer
    operate **directly on the (C, N) flat buffer** instead of a params
    tree — the resident-flat-params mode of
    :class:`repro.runtime.SlotTrainLoop`, which keeps the population
    raveled across steps so steady-state training never pays per-round
    ravel/unravel copies.  Same signatures with ``params`` replaced by
    the buffer.
    """
    codec, fuse = resolve_wire(codec, fuse)
    if flat_io:
        if fuse != "flat" or strategy not in ("fedlay", "ring"):
            raise ValueError(
                "flat_io mixers operate on the raveled buffer: they "
                "require fuse='flat' (or a codec) and a fedlay/ring "
                "strategy")
    if sched is not None:
        check_group_size(sched.num_clients, clients_per_device)
    elif clients_per_device < 1:
        raise ValueError("clients_per_device must be >= 1")
    if strategy == "none":
        if masked:
            return lambda params, mask, *, edge_mask=None: params
        return lambda params: params

    if strategy == "allreduce":
        def allreduce(params):
            return jax.tree.map(
                lambda l: jnp.broadcast_to(
                    jnp.mean(l.astype(jnp.float32), axis=0,
                             keepdims=True).astype(l.dtype), l.shape),
                params)

        def allreduce_masked(params, mask, *, edge_mask=None):
            # allreduce has no per-edge structure; a degraded node is a
            # node-level fault (fold it into ``mask``), so edge_mask is
            # accepted for signature parity and ignored
            m = mask.astype(jnp.float32)
            denom = jnp.maximum(jnp.sum(m), 1.0)

            def mean_leaf(leaf):
                shape = (leaf.shape[0],) + (1,) * (leaf.ndim - 1)
                mm = m.reshape(shape)
                mean = jnp.sum(leaf.astype(jnp.float32) * mm, axis=0,
                               keepdims=True) / denom
                out = jnp.broadcast_to(mean.astype(leaf.dtype), leaf.shape)
                return jnp.where(mm > 0, out, leaf)
            return jax.tree.map(mean_leaf, params)
        return allreduce_masked if masked else allreduce

    if strategy in ("fedlay", "ring"):
        if sched is None:
            raise ValueError(f"{strategy} mixer needs a PermuteSchedule")
        C = sched.num_clients
        perms = jnp.asarray(np.array(sched.perms), jnp.int32)   # (2L, C)
        weights = jnp.asarray(sched.weights)                    # (C, 2L)
        self_w = jnp.asarray(sched.self_weight)                 # (C,)

        def masked_tables(mask, edge_mask=None):
            """(sw (C,), ew (C, 2L), ok (C,)) of mask-renormalized
            weights — shared by the tree-walk and fused masked
            variants so their semantics cannot drift apart.

            ``edge_mask`` is an optional (C, 2L) 0/1 runtime input
            (degraded rounds, :mod:`repro.faults`): entry [i, k] = 0
            drops the edge from slot i's k-th source *before*
            renormalizing, so unreachable neighbors are renormalized
            away exactly like dead ones.  A fully isolated live row
            (all edges down) degenerates to total = self_w > 0 and
            keeps its own model."""
            m = mask.astype(jnp.float32)
            # source contributions gated by the source's mask, rows
            # renormalized over what survives
            eff = weights * jnp.take(m, perms, axis=0).T
            if edge_mask is not None:
                eff = eff * edge_mask.astype(jnp.float32)
            total = self_w + eff.sum(axis=1)
            ok = (m > 0) & (total > 0)
            safe = jnp.where(total > 0, total, 1.0)
            return self_w / safe, eff / safe[:, None], ok

        if fuse == "flat":
            # (C, 2L+1) static source rows: self first, then the 2L
            # schedule sources — one gather_mix kernel mixes the round.
            srcs = np.concatenate(
                [np.arange(C)[:, None], np.array(sched.perms).T], axis=1)
            base_table = jnp.concatenate(
                [self_w[:, None], weights], axis=1).astype(jnp.float32)
            ef = codec is not None and codec.error_feedback

            def round_flat(buf, table, ok=None, residual=None):
                """One fused round on the (C, N) buffer → (out, res).
                Codec-free: one gather_mix over the full table (identity
                rows where ~ok).  With a codec: the self column uses the
                true rows, neighbors mix the encoded buffer through the
                codec's fused gather; EF encodes buf+residual and
                returns the fresh residual (mask-gating is the
                caller's)."""
                if codec is None:
                    if ok is not None:
                        ident = jnp.zeros_like(table).at[:, 0].set(1.0)
                        table = jnp.where(ok[:, None], table, ident)
                    with scope("global_mixer.gather_mix"):
                        return gather_mix(buf, srcs, table), None
                bus = get_telemetry()           # trace-time tick (see
                bus.count("wire.encodes")       # fedlay_mix): counts
                bus.count("wire.decodes")       # codec (re)compiles
                with scope(f"wire.{codec.name}.encode"):
                    if ef:
                        wire, res = codec.encode_ef(buf + residual)
                    else:
                        wire, res = codec.encode(buf), None
                with scope(f"global_mixer.{codec.name}.gather"):
                    out = mix_accumulate(None, buf, table[:, 0])
                    out = out + codec.gather(wire, srcs[:, 1:],
                                             table[:, 1:], buf.shape[1])
                if ok is not None:
                    out = jnp.where(ok[:, None], out, buf)
                return out, res

            def mix_buf(buf):
                return round_flat(buf, base_table)[0]

            def mix_buf_masked(buf, mask, *, edge_mask=None):
                sw, ew, ok = masked_tables(mask, edge_mask)
                table = jnp.concatenate([sw[:, None], ew], axis=1)
                return round_flat(buf, table, ok=ok)[0]

            def mix_buf_ef(buf, residual):
                return round_flat(buf, base_table, residual=residual)

            def mix_buf_masked_ef(buf, mask, residual, *, edge_mask=None):
                sw, ew, ok = masked_tables(mask, edge_mask)
                table = jnp.concatenate([sw[:, None], ew], axis=1)
                out, res = round_flat(buf, table, ok=ok, residual=residual)
                # masked-out rows (dead slots, multirate skips) keep
                # their residual: they contributed nothing this round
                res = jnp.where((mask > 0)[:, None], res, residual)
                return out, res

            inner = {(False, False): mix_buf,
                     (True, False): mix_buf_masked,
                     (False, True): mix_buf_ef,
                     (True, True): mix_buf_masked_ef}[(masked, ef)]
            if flat_io:
                return inner

            if ef:
                def mix_flat_ef(params, *rest, **kw):
                    spec = FlatSpec.for_tree(params)
                    out, res = inner(spec.ravel(params), *rest, **kw)
                    return spec.unravel(out), res
                return mix_flat_ef

            def mix_flat(params, *rest, **kw):
                if codec is not None:
                    spec = FlatSpec.for_tree(params)
                    return spec.unravel(inner(spec.ravel(params), *rest,
                                              **kw))
                # codec-free rounds mix each leaf dtype in a buffer of
                # that dtype: the kernel accumulates in f32 and rounds
                # once either way, so results are those of one f32
                # buffer, while a bf16 model's round holds a bf16
                # population (half the HBM) instead of an f32 one
                return map_dtype_buffers(
                    params, lambda buf: inner(buf, *rest, **kw))
            return mix_flat

        def mix(params):
            def mix_leaf(leaf):
                shape = (C,) + (1,) * (leaf.ndim - 1)
                acc = leaf * self_w.reshape(shape).astype(leaf.dtype)
                for k in range(sched.num_slots):
                    recv = jnp.take(leaf, perms[k], axis=0)  # permutation
                    w = weights[:, k].reshape(shape)
                    acc = acc + recv * w.astype(leaf.dtype)
                return acc
            return jax.tree.map(mix_leaf, params)

        def mix_masked(params, mask, *, edge_mask=None):
            sw, ew, ok = masked_tables(mask, edge_mask)

            def mix_leaf(leaf):
                shape = (C,) + (1,) * (leaf.ndim - 1)
                acc = leaf * sw.reshape(shape).astype(leaf.dtype)
                for k in range(sched.num_slots):
                    recv = jnp.take(leaf, perms[k], axis=0)
                    acc = acc + recv * ew[:, k].reshape(shape).astype(
                        leaf.dtype)
                return jnp.where(ok.reshape(shape), acc, leaf)
            return jax.tree.map(mix_leaf, params)
        return mix_masked if masked else mix

    raise ValueError(
        f"unknown sync strategy {strategy!r}; choose from {SYNC_STRATEGIES}")


def sync_bytes_per_client(strategy: str, model_bytes: int, num_clients: int,
                          num_spaces: Optional[int] = None,
                          clients_per_device: int = 1,
                          active_clients: Optional[int] = None,
                          codec=None) -> float:
    """*Network* bytes each **active** client sends per mixing round
    (paper §IV-D accounting).  With the grouped layout
    (``clients_per_device = G``) edges between clients co-hosted on one
    device cost 0 network bytes, so every strategy's wire cost shrinks —
    to exactly 0 when the whole active set shares one device.

    ``active_clients = K`` models cohort streaming
    (:mod:`repro.scale.cohort`): only K of the ``num_clients`` capacity
    slots participate, the round's overlay is rebuilt over the cohort
    (induced-subgraph schedule), and the SlotMap packs the cohort into
    the lowest slots — so the closed forms are the full-participation
    forms with K in place of n.  The observed FedLay degree is capped by
    the cohort: ``min(2L, K−1)`` (K−1 peers exist at all; tiny cohorts
    cannot realize 2L distinct neighbors).  Default ``None`` means full
    participation (K = n), reproducing the original forms exactly.

    * ``fedlay``: degree ≤ min(2L, K−1), each ring neighbor uniform over
      the other K−1 active clients ⇒ expected
      ``min(2L, K−1) · (K−G)/(K−1) · model_bytes`` — at K = n, G = 1
      this is the paper's constant-in-n headline ``2L·model_bytes``
      (exact per-schedule counts:
      :attr:`repro.core.mixing.GroupedRouting.cross_edges`, the
      regression oracle in ``tests/test_grouped.py``);
    * ``ring``: two neighbors; block-contiguous packing makes the
      cohort ring device-contiguous, so only ``2·D_K`` of the ``2K``
      messages cross the ``D_K = ⌈K/G⌉`` occupied devices ⇒
      ``2·D_K/K · model_bytes`` per active client (``2/G`` at K = n);
    * ``complete``: all K−1 active peers, K−G of them remote;
    * ``allreduce``: device-local reduce first (free), then a
      bandwidth-optimal ring all-reduce over the ``D_K`` occupied
      devices, amortized over the active clients per device:
      ``2·(D_K−1)/D_K · D_K/K · model_bytes``;
    * ``none``: no communication.

    ``codec`` (a name or :class:`repro.wire.codec.WireCodec`) replaces
    the gossip payload with its wire image: ``model_bytes`` is
    interpreted as the f32 flat row (``model_bytes / 4`` elements) and
    every peer-to-peer strategy (fedlay / ring / complete) ships
    ``codec.wire_bytes(elements)`` instead.  ``allreduce`` ignores the
    codec — in-network reduction has no per-edge wire image to
    compress.
    """
    n, G = num_clients, clients_per_device
    check_group_size(n, G)
    codec = get_codec(codec)
    if codec is not None and strategy in ("fedlay", "ring", "complete"):
        model_bytes = codec.wire_bytes(int(round(model_bytes / 4.0)))
    K = n if active_clients is None else int(active_clients)
    if not 1 <= K <= n:
        raise ValueError(f"active_clients {K} out of range for "
                         f"{n} clients")
    d_k = -(-K // G)                 # occupied devices, lowest-slot packing
    if strategy == "fedlay":
        if num_spaces is None:
            raise ValueError("fedlay accounting needs num_spaces")
        if K <= 1 or d_k == 1:
            return 0.0
        degree = min(2 * num_spaces, K - 1)
        return degree * model_bytes * (K - G) / (K - 1)
    if strategy == "ring":
        return 0.0 if d_k == 1 else 2.0 * d_k * model_bytes / K
    if strategy == "complete":
        return float(max(K - G, 0)) * model_bytes
    if strategy in ("allreduce", "fedavg"):
        return 2.0 * (d_k - 1) / d_k * d_k * model_bytes / K \
            if d_k > 1 else 0.0
    if strategy == "none":
        return 0.0
    raise ValueError(
        f"unknown sync strategy {strategy!r}; choose from "
        f"{SYNC_STRATEGIES + ('complete', 'fedavg')}")
