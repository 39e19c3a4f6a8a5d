"""Pallas TPU kernels: MEP confidence-weighted model aggregation.

The FedLay/MEP hot path on device is ``w_u ← Σ_k c_k · W_k`` over the
own model plus the (up to 2L) neighbor models received via ppermute —
a purely memory-bound reduction over K same-shaped parameter vectors.
A naive jnp implementation materializes a full-model temporary per
neighbor; the kernels here stream lane-aligned tiles through VMEM and
write each output tile exactly once.  Three entries, matching the three
shapes the mixing paths produce (see :mod:`repro.dist.sync`):

* :func:`weighted_mix` — the stacked form ``(K, N) × (K,) → (N,)``,
  optionally masked (``mask=``): masked-out models are dropped and the
  surviving weights renormalized, the kernel image of
  :func:`repro.core.mixing.masked_mixing_matrix` row semantics.
  HBM traffic = (K + 1)·N·sizeof(dtype) — optimal.
* :func:`mix_accumulate` — the incremental form
  ``acc ← acc + w·x`` over ``(B, N)`` row buffers, so a mixing round
  folds each ppermute-received buffer into the accumulator as it
  arrives (receive overlapped with accumulation) instead of stacking
  2L full-model temporaries.  ``acc=None`` is the fused init
  ``acc ← w·x`` (the self-weight term).
* :func:`gather_mix` — the whole-round form for a resident ``(C, N)``
  flat population buffer: out row ``i`` = Σ_k ``weights[i, k] ·
  buf[srcs[i, k]]`` with **host-static** source rows (the schedule's
  perms are static per compiled mixer) and runtime weights (so churn
  masks renormalize with zero retrace).  One kernel per mixing round:
  each column tile of the population is read once and serves every
  output row — HBM traffic 2·C·N regardless of the overlay degree, and
  no materialized receive temporaries at all.

Grids are 1-D over ceil(N/BN) lane-aligned tiles; K (≤ ~13: self + 2L
neighbors) and C (clients per controller, ≤ a few dozen) ride whole in
VMEM per tile.  BN need not divide N: Pallas masks the part of the last
tile that hangs past the array (columns are independent, so it never
reaches an in-range output).  A lane-aligned buffer — every
:class:`repro.dist.flat.FlatSpec` buffer — is therefore read and written
in place; only a width that is not a lane multiple pays a padded copy.
The MXU is idle — these kernels live on the VPU — so tiles are sized
for bandwidth, not matmul alignment.  ``interpret``
defaults to auto (:func:`repro.kernels.interpret.resolve_interpret`):
compiled on TPU, interpreted (still traceable under jit/shard_map)
everywhere else.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from ..obs.profile import scope
from .interpret import resolve_interpret

#: TPU vector lane width — every block's minor dim must be a multiple.
LANE = 128
#: f32 sublane count — VMEM pads a tile's second-minor dim to it.
SUBLANE = 8


def aligned_block_n(n: int, block_n: int, lane: int = LANE) -> int:
    """The lane-aligned tile size actually used for an (K, n) mix.

    The smallest multiple of ``lane`` covering ``n``, capped at
    ``block_n`` (itself rounded up to a lane multiple).  A bare
    ``min(block_n, n)`` is TPU-invalid whenever ``lane < n < block_n``
    with ``n % lane != 0`` — it only ever worked in interpret mode."""
    need = -(-n // lane) * lane
    cap = max(lane, -(-block_n // lane) * lane)
    return min(cap, need)


def _default_block_n(n: int, rows: int, interp: bool) -> int:
    """Tile-width default shared by the mix entries.

    Tiling exists to fit VMEM, so it only applies to the compiled
    kernel: a ~2 MB f32 tile budget per (rows, bn) operand as VMEM
    holds it, rows rounded up to the 8-sublane tile
    (bn ≈ 2^19 / rows_padded elements).  Interpret mode has no VMEM —
    and its grid loop copies operands per cell — so it runs the whole
    (lane-padded) vector as one grid cell."""
    if interp:
        return max(LANE, n)
    rows_padded = -(-max(rows, 1) // SUBLANE) * SUBLANE
    return max(LANE, (2 ** 19 // rows_padded) // LANE * LANE)


def _pad_cols(x: jnp.ndarray, width: int) -> jnp.ndarray:
    """Zero-pad the columns of a 2-D ``x`` to ``width`` (no copy when
    there is nothing to pad)."""
    pad = width - x.shape[1]
    return jnp.pad(x, ((0, 0), (0, pad))) if pad else x


def _lane_pad(x: jnp.ndarray) -> jnp.ndarray:
    """Pad the columns to a lane multiple (a no-op for every FlatSpec
    buffer, which is lane-aligned by construction)."""
    return _pad_cols(x, -(-x.shape[1] // LANE) * LANE)


def _mix_kernel(models_ref, weights_ref, out_ref):
    # models_ref: (K, BN); weights_ref: (K, 1); out: (BN,)
    w = weights_ref[...].astype(jnp.float32)            # (K, 1)
    m = models_ref[...].astype(jnp.float32)             # (K, BN)
    out_ref[...] = jnp.sum(m * w, axis=0).astype(out_ref.dtype)


def weighted_mix(models: jnp.ndarray, weights: jnp.ndarray, *,
                 mask: Optional[jnp.ndarray] = None,
                 block_n: Optional[int] = None,
                 interpret: Optional[bool] = None) -> jnp.ndarray:
    """models: (K, N) stacked flat model vectors; weights: (K,).

    Returns Σ_k weights[k]·models[k] as (N,) in models.dtype.
    N is padded to a lane multiple (128) internally.

    ``mask`` (optional (K,) 0/1 float) drops masked-out models and
    renormalizes the surviving weights to sum to the original total
    mass fraction 1 — i.e. effective weights ``w·m / Σ(w·m)`` — the
    kernel image of one :func:`repro.core.mixing.masked_mixing_matrix`
    row over its gathered sources.  A fully masked-out stack yields
    zeros (callers gate that case, exactly like the dense oracle's
    dead-row identity).  The renormalization is K scalar ops outside
    the kernel, so masking never retraces or re-tiles.
    """
    interp = resolve_interpret(interpret)
    K, N = models.shape
    if block_n is None:
        block_n = _default_block_n(N, K, interp)
    if mask is not None:
        eff = weights.astype(jnp.float32) * mask.astype(jnp.float32)
        total = jnp.sum(eff)
        weights = jnp.where(total > 0, eff / jnp.where(total > 0, total, 1.0),
                            jnp.zeros_like(eff))
    bn = aligned_block_n(N, block_n)
    models = _lane_pad(models)
    Np = models.shape[1]
    w2 = weights.reshape(K, 1).astype(jnp.float32)

    out = pl.pallas_call(
        _mix_kernel,
        grid=(pl.cdiv(Np, bn),),
        in_specs=[
            pl.BlockSpec((K, bn), lambda i: (0, i)),
            pl.BlockSpec((K, 1), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bn,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((Np,), models.dtype),
        interpret=interp,
    )(models, w2)
    return out if Np == N else out[:N]


def _accum_kernel(acc_ref, x_ref, w_ref, out_ref):
    # acc/x: (B, BN); w: (B, 1) — one fused multiply-add per tile, the
    # output tile written exactly once.
    w = w_ref[...].astype(jnp.float32)
    out_ref[...] = (acc_ref[...].astype(jnp.float32)
                    + x_ref[...].astype(jnp.float32) * w).astype(
                        out_ref.dtype)


def _scale_kernel(x_ref, w_ref, out_ref):
    w = w_ref[...].astype(jnp.float32)
    out_ref[...] = (x_ref[...].astype(jnp.float32) * w).astype(out_ref.dtype)


def mix_accumulate(acc: Optional[jnp.ndarray], x: jnp.ndarray,
                   w: jnp.ndarray, block_n: Optional[int] = None,
                   interpret: Optional[bool] = None) -> jnp.ndarray:
    """Incremental mixing accumulate: ``acc + w[:, None]·x`` over (B, N)
    row buffers with per-row weights (B,), tiled so each output tile is
    written once and nothing but the running accumulator is ever
    materialized.  ``acc=None`` is the init form ``w[:, None]·x`` (the
    self-weight term of a mixing round), so a full round is

        acc = mix_accumulate(None, own, self_w)
        for each slot k:  acc = mix_accumulate(acc, receive(k), w_k)

    — receives overlap with accumulation; at any instant only {own,
    acc, current receive} exist, independent of the overlay degree 2L.
    """
    interp = resolve_interpret(interpret)
    B, N = x.shape
    if block_n is None:
        block_n = _default_block_n(N, B, interp)
    bn = aligned_block_n(N, block_n)
    xs = _lane_pad(x)
    Np = xs.shape[1]
    grid = (pl.cdiv(Np, bn),)
    w2 = w.reshape(B, 1).astype(jnp.float32)
    row_spec = pl.BlockSpec((B, bn), lambda i: (0, i))
    w_spec = pl.BlockSpec((B, 1), lambda i: (0, 0))
    if acc is None:
        with scope("kernels.mix_accumulate.init"):
            out = pl.pallas_call(
                _scale_kernel,
                grid=grid,
                in_specs=[row_spec, w_spec],
                out_specs=row_spec,
                out_shape=jax.ShapeDtypeStruct((B, Np), x.dtype),
                interpret=interp,
            )(xs, w2)
        return out if Np == N else out[:, :N]
    accs = _lane_pad(acc)
    with scope("kernels.mix_accumulate"):
        out = pl.pallas_call(
            _accum_kernel,
            grid=grid,
            in_specs=[row_spec, row_spec, w_spec],
            out_specs=row_spec,
            out_shape=jax.ShapeDtypeStruct((B, Np), acc.dtype),
            interpret=interp,
        )(accs, xs, w2)
    return out if Np == N else out[:, :N]


def round_matrix(C: int, srcs, weights: jnp.ndarray) -> jnp.ndarray:
    """Scatter a (C, K1) ``(srcs, weights)`` gather table into the dense
    (C, C) round-mixing matrix ``W[i, srcs[i, k]] += weights[i, k]``
    (duplicate sources add).  ``srcs`` host-static (validated eagerly)
    or traced (the cohort-streaming case) — shared by
    :func:`gather_mix` and the int8 wire-codec sibling
    :func:`repro.kernels.wire_codec.gather_mix_int8`."""
    static_srcs = not isinstance(srcs, jax.core.Tracer)
    if static_srcs:
        srcs = np.asarray(srcs, np.int64)
        if srcs.min() < 0 or srcs.max() >= C:
            raise ValueError(f"source rows out of range for {C} clients")
    if srcs.shape[0] != C or weights.shape != srcs.shape:
        raise ValueError(
            f"srcs {srcs.shape} / weights {weights.shape} do not match "
            f"{(C,)} clients")
    rows = np.broadcast_to(np.arange(C)[:, None], srcs.shape)
    return jnp.zeros((C, C), jnp.float32).at[rows, srcs].add(
        weights.astype(jnp.float32))


def _gather_mix_kernel(W_ref, models_ref, out_ref):
    # W: (C, C) round-mixing matrix (stationary across tiles);
    # models: (C, BN) — the whole population's column tile, read once
    # and serving every output row via one MXU matmul.  HIGHEST: at the
    # default precision the MXU rounds f32 operands to bf16, which cost
    # f32 models ~3 significant digits per round on a v5e.
    out_ref[...] = jnp.dot(
        W_ref[...], models_ref[...].astype(jnp.float32),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST).astype(out_ref.dtype)


def gather_mix(buf: jnp.ndarray, srcs, weights: jnp.ndarray,
               block_n: Optional[int] = None,
               interpret: Optional[bool] = None) -> jnp.ndarray:
    """One whole mixing round over a resident flat population buffer.

    ``buf`` (C, N): every client's raveled model; ``srcs`` (C, K1) int
    source rows (column 0 is conventionally the client itself, the rest
    its schedule sources — duplicates are fine, their weights just
    add); ``weights`` (C, K1) runtime float row-mixing weights.
    ``srcs`` may be host-static (numpy: validated eagerly, the per-
    compiled-mixer schedule case) **or traced** (jnp under jit: the
    cohort-streaming case, where the round's source table is data — any
    sequence of cohort compositions reuses one compiled program, since
    the kernel only ever sees the scattered (C, C) matrix; out-of-range
    traced sources are the caller's contract).  Returns (C, N) in
    ``buf.dtype`` with

        out[i] = Σ_k weights[i, k] · buf[srcs[i, k]]

    The (srcs, weights) table is scattered into the dense (C, C)
    round-mixing matrix W (a tiny runtime op — the schedule bounds its
    row support at K1 nonzeros) and the kernel runs one stationary
    ``W @ tile`` matmul per (C, bn) column tile: the tile is read once
    and serves all C output rows — no gather op, no materialized
    receive temporaries — so HBM traffic is 2·C·N regardless of the
    overlay degree, and masking only changes the runtime weight table
    (zero retrace; the source table is static per compiled mixer,
    churn swaps whole programs via the
    :class:`repro.overlay.controller.MixerCache`).  Sized for one
    controller's population (C ≲ a few hundred: the C² matmul flops
    stay far below the memory bound): the C-row tile must fit VMEM —
    the default ``block_n=None`` budgets the compiled tile at ~2 MB
    (bn ≈ 2^19/C elements; shrink for larger C) and runs interpret
    mode as a single cell (no VMEM to fit).

    Degraded-round contract (:mod:`repro.faults`): unreachable edges
    never reach this kernel as structure — the masked mixers zero the
    affected entries of the runtime ``weights`` table (after
    renormalizing the survivors, see
    ``repro.dist.sync.global_mixer``'s ``masked_tables``), so a link
    outage, straggler, or partition round runs the *same* compiled
    program with a different weight table: zero retraces, same
    MixerCache entry.
    """
    interp = resolve_interpret(interpret)
    C, N = buf.shape
    if block_n is None:
        block_n = _default_block_n(N, C, interp)
    W = round_matrix(C, srcs, weights)
    bn = aligned_block_n(N, block_n)
    bufs = _lane_pad(buf)
    Np = bufs.shape[1]

    with scope("kernels.gather_mix"):
        out = pl.pallas_call(
            _gather_mix_kernel,
            grid=(pl.cdiv(Np, bn),),
            in_specs=[
                pl.BlockSpec((C, C), lambda i: (0, 0)),
                pl.BlockSpec((C, bn), lambda i: (0, i)),
            ],
            out_specs=pl.BlockSpec((C, bn), lambda i: (0, i)),
            out_shape=jax.ShapeDtypeStruct((C, Np), buf.dtype),
            # in place: out tile i depends only on in tile i, so a
            # buffer that dies here (a round's raveled temp) is reused
            # instead of holding a second (C, N) population in HBM
            input_output_aliases={1: 0},
            interpret=interp,
        )(W, bufs)
    return out if Np == N else out[:, :N]
