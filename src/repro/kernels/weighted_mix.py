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
  no materialized receive temporaries at all.  It mixes the codec and
  ``flat_io`` rows, and codec-free rounds above
  :data:`LEAF_MIX_MAX_CLIENTS` clients.
* :func:`gather_mix_leaf` — the same round on one parameter leaf in
  its own shape and layout, for codec-free rounds of up to
  :data:`LEAF_MIX_MAX_CLIENTS` clients: the (C, C) round matrix
  (:func:`round_matrix`) in SMEM, the sum over sources an f32 VPU
  multiply-add, the leaf tiled over its own last two dimensions, so a
  round ravels, pads and concatenates nothing.

The flat entries' grids are 1-D over ceil(N/BN) lane-aligned tiles; K
(≤ ~13: self + 2L neighbors) and C (clients per controller, ≤ a few
dozen) ride whole in VMEM per tile.  BN need not divide N: Pallas masks
the part of the last tile that hangs past the array (columns are
independent, so it never reaches an in-range output).  A lane-aligned
buffer — every :class:`repro.dist.flat.FlatSpec` buffer — is therefore
read and written in place; only a width that is not a lane multiple
pays a padded copy.
The MXU is idle — these kernels live on the VPU — so tiles are sized
for bandwidth, not matmul alignment.  ``interpret``
defaults to auto (:func:`repro.kernels.interpret.resolve_interpret`):
compiled on TPU, interpreted (still traceable under jit/shard_map)
everywhere else.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.layout import Layout
from jax.experimental.pallas import tpu as pltpu

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


#: Largest client count whose round the leaf-native mix
#: (:func:`gather_mix_leaf`) runs on the VPU under the memory bound.
#: Per parameter it moves 2·C·s bytes (s the itemsize) and issues about
#: 2·C² + C f32 vector operations over 1,024 lanes (C² multiplies,
#: C² − C adds, 2·C converts).  ``chipbench/peaks.json`` gives a v5e
#: 819 GB/s of HBM and 197 TFLOP/s of bf16 MXU, i.e. 4 MXUs × 128² × 2
#: flops a cycle at 1.5 GHz: at k vector operations a cycle the VPU's
#: time, (2·C² + C) / (k × 1,024 × 1.5e9) s, stays under the memory
#: time, 2·C·s / 819e9 s, while 2·C + 1 ≤ 3.75·k·s.  For bf16 (s = 2)
#: that is C ≤ 3 at k = 1, C ≤ 7 at k = 2 and C ≤ 10 at k = 3.  On a
#: v5e the kernel mixed mamba2-370m's bf16 ``in_proj`` at 79 % of the
#: HBM roofline with C = 2, 80 % with C = 4 and 72–76 % with C = 8, so
#: the VPU keeps pace up to 8; above it the (C, N) kernel
#: (:func:`gather_mix`) mixes the round with its C² flops on the MXU.
LEAF_MIX_MAX_CLIENTS = 8

#: VMEM bytes of one leaf-mix block: all C clients' slabs as VMEM lays
#: them out (rows padded to the dtype's sublane tile, columns to the
#: lane), double-buffered on the way in and out, beside the kernel's f32
#: temporaries — within the v5e's 16 MiB of scoped VMEM.
LEAF_BLOCK_BYTES = 2 ** 20


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _sublanes(dtype) -> int:
    """Rows of one native TPU tile of ``dtype``: 8 for 32-bit, 16 for
    16-bit values."""
    return SUBLANE * 4 // jnp.dtype(dtype).itemsize


def _layout_device():
    return jax.devices()[0]


def _default_order(shape, dtype) -> tuple:
    """The dimensions of an array of ``shape``, major to minor, as the
    device's default layout stores them.  A TPU picks them to spare
    padding: bf16 (C, 48, 1024, 4384) is stored with 1024 minor
    ({2,3,1,0}), (C, 3, 2560) with the client dimension second-minor."""
    dev = _layout_device()
    pjrt = dev.client.get_default_layout(np.dtype(dtype), tuple(shape), dev)
    return tuple(Layout.from_pjrt_layout(pjrt).major_to_minor)


def _leaf_view(shape, axis: int, dtype) -> tuple:
    """A leaf in storage order (client dimension at ``axis``) as the
    kernel reads it, without a relayout.  A (C,) leaf becomes (C, 1).
    The dimensions between the client one and the minor one merge into
    one row dimension only where that is a bitcast — when the
    second-minor dimension is a whole number of sublane tiles, so that
    no tile straddles a leading index ((C, 48, 4384, 1024) →
    (C, 210432, 1024); (C, 48, 4, 2304) stays)."""
    if len(shape) == 1:
        return shape + (1,)
    if axis < len(shape) - 3 and shape[-2] % _sublanes(dtype) == 0:
        return (*shape[:axis + 1], int(np.prod(shape[axis + 1:-1])),
                shape[-1])
    return tuple(shape)


def _leaf_blocks(view, axis: int, dtype, budget):
    """(block_shape, grid, kernel_axis) tiling a leaf view in its own
    layout: the client dimension (``axis``) whole in every block; the
    last two dimensions whole, or cut into sublane-tile rows and then
    lane-multiple columns until all C clients' slabs fit ``budget``
    bytes as VMEM pads them; the other leading dimensions ride the grid
    one index at a time (squeezed).  ``kernel_axis`` is where the client
    dimension sits in the kernel's (squeezed) block."""
    nd, C = len(view), view[axis]
    itemsize, sub = jnp.dtype(dtype).itemsize, _sublanes(dtype)
    r, l = view[-2], view[-1]
    mult = C if axis < nd - 2 else 1

    def nbytes(tr, tl):
        return mult * _round_up(tr, sub) * _round_up(tl, LANE) * itemsize

    tr, tl = r, l
    if axis != nd - 2 and nbytes(r, l) > budget and r > sub:
        row = nbytes(sub, l) // sub
        tr = max(sub, budget // row // sub * sub)
    if axis != nd - 1 and nbytes(tr, l) > budget and l > LANE:
        col = nbytes(tr, LANE) // LANE
        tl = min(l, max(LANE, budget // col // LANE * LANE))
    block = [pl.squeezed] * (nd - 2) + [tr, tl]
    block[axis] = C
    grid = tuple(pl.cdiv(view[d], 1 if block[d] is pl.squeezed else
                         block[d]) for d in range(nd) if d != axis)
    kernel_axis = axis - sum(b is pl.squeezed for b in block[:axis])
    return tuple(block), grid, kernel_axis


def _leaf_mix_kernel(W_ref, x_ref, out_ref, *, axis: int):
    # W: (C, C) f32 in SMEM; x/out: one block of every client's slab of
    # the leaf, clients along ``axis``.  out[i] = Σ_k W[i, k]·x[k]: C²
    # scalar × vector multiply-adds on the VPU in f32, one rounding on
    # the store.
    C = x_ref.shape[axis]

    def client(k):
        return (slice(None),) * axis + (pl.ds(k, 1),)

    xs = [x_ref[client(k)].astype(jnp.float32) for k in range(C)]
    for i in range(C):
        acc = W_ref[i, 0] * xs[0]
        for k in range(1, C):
            acc = acc + W_ref[i, k] * xs[k]
        out_ref[client(i)] = acc.astype(out_ref.dtype)


def gather_mix_leaf(x: jnp.ndarray, W: jnp.ndarray,
                    block_bytes: Optional[int] = None,
                    interpret: Optional[bool] = None) -> jnp.ndarray:
    """One leaf's share of a mixing round, in the leaf's own layout.

    ``x`` (C, ...): every client's copy of one parameter leaf; ``W``
    (C, C) f32 runtime round-mixing matrix (:func:`round_matrix`).
    Returns ``out[i] = Σ_k W[i, k]·x[k]`` in ``x.dtype`` — the contract
    of :func:`gather_mix` with the (srcs, weights) table already
    scattered, accumulated in f32 and rounded once — without raveling.
    Mixing is elementwise over a client's parameters, so the kernel
    reads the leaf in the order its default layout stores it
    (:func:`_default_order`; the transpose is a bitcast) and tiles it
    over its own last two dimensions (:func:`_leaf_view`,
    :func:`_leaf_blocks`): nothing is padded, concatenated, sliced or
    relaid out at population size, and each block of all C clients is
    read once and written once (HBM traffic 2·C·size, as the flat
    kernel's).  ``W`` rides in SMEM as a runtime input, so masks stay
    zero-retrace.  The sum over the C sources is an unrolled VPU
    multiply-add, for C ≤ :data:`LEAF_MIX_MAX_CLIENTS`.  The output is
    a fresh buffer: the mixer's input is not donated, and aliasing it
    would cost XLA a full copy of the leaf."""
    interp = resolve_interpret(interpret)
    C = x.shape[0]
    if W.shape != (C, C):
        raise ValueError(f"W {W.shape} does not match {C} clients")
    if block_bytes is None:
        # interpret mode has no VMEM to fit, and its grid loop copies
        # operands per cell: one cell holds the whole leaf
        block_bytes = np.inf if interp else LEAF_BLOCK_BYTES
    order = _default_order(x.shape, x.dtype)
    stored = jnp.transpose(x, order)
    axis = order.index(0)
    view = _leaf_view(stored.shape, axis, x.dtype)
    block, grid, kernel_axis = _leaf_blocks(view, axis, x.dtype,
                                            block_bytes)
    spec = pl.BlockSpec(block, lambda *g: tuple(
        0 if d == axis else g[d - (d > axis)] for d in range(len(view))))
    with scope("kernels.gather_mix"):
        out = pl.pallas_call(
            functools.partial(_leaf_mix_kernel, axis=kernel_axis),
            grid=grid,
            in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), spec],
            out_specs=spec,
            out_shape=jax.ShapeDtypeStruct(view, x.dtype),
            interpret=interp,
        )(W.astype(jnp.float32), stored.reshape(view))
    return jnp.transpose(out.reshape(stored.shape), np.argsort(order))
