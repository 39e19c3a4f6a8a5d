"""Pallas quantize/dequantize kernels for the wire codecs.

The wire-compression subsystem (:mod:`repro.wire.codec`) compresses the
flat-row gossip payload of :mod:`repro.dist.sync`'s ``fuse="flat"``
paths.  Its hot codec — ``int8-block`` symmetric per-block quantization
— is implemented here as a kernel pair plus two **fused receive**
entries, so the decompressed model never exists in HBM:

* :func:`quantize_block` — encode: per-block symmetric scales
  ``s = max|block| / levels`` (stored in ``scale_dtype``, typically
  bf16) and ``q = round(x / s) ∈ [-levels, levels]`` as int8.  With
  ``with_residual=True`` the error-feedback residual ``x - q·s`` is
  produced *in the same kernel* while the input tile is live in VMEM —
  the EF update never re-reads or re-decodes the encoded buffer.
* :func:`dequantize_block` — the standalone decode (tests, generic
  codec fallbacks): ``q·s`` broadcast per block.
* :func:`dequant_accumulate` — the fused receive of the shard_map
  mixing path: ``acc + w[:, None] · dequant(q, s)`` in one kernel, the
  int8 sibling of :func:`repro.kernels.weighted_mix.mix_accumulate`.
  Each ppermute-received *compressed* row folds straight into the f32
  accumulator; only {own, acc, current int8 receive} are ever live, and
  the decompressed 2L stack is never materialized.
* :func:`gather_mix_int8` — the fused receive of the global round-matrix
  path: the int8 sibling of
  :func:`repro.kernels.weighted_mix.gather_mix`.  Each (C, bn) column
  tile of the *compressed* population buffer is dequantized in VMEM and
  immediately consumed by the stationary ``W @ tile`` matmul — HBM reads
  the int8 payload (4× fewer bytes than f32), HBM writes only the f32
  output.

**Block layout contract** (shared with :mod:`repro.wire.codec`): an
(B, N) f32 buffer is split along columns into ``NB = ceil(N / block)``
blocks of ``block`` elements (the tail zero-padded — zeros quantize to
0 and decode to 0, so padding is exact); ``q`` is (B, NB·block) int8
and ``scales`` (B, NB) with ``scales[b, j]`` scaling columns
``j·block : (j+1)·block``.  Quantization uses the *stored* (rounded to
``scale_dtype``) scale, so encode and decode agree exactly and the
error is bounded by ``s/2 ≤ max|block|/(2·levels) · (1 + ε_scale)`` per
element.  All-zero blocks store scale 0 and decode to exact zeros; a
stored scale that underflows to 0 quantizes through a safe scale of 1
(q rounds to 0, the residual carries the value).

Grids are 1-D over lane-aligned column tiles sized by the shared ~2 MB
budget of :func:`repro.kernels.weighted_mix._default_block_n`, rounded
to a multiple of block·LANE so per-tile scale columns stay whole and
lane-aligned; the last tile may overhang the width (masked by Pallas).
Interpret mode (the CPU test mesh) runs a single cell.  The compiled
TPU path wants ``block`` a multiple of :data:`~repro.kernels.weighted_mix.LANE`
(the int8 min tile is (32, 128) — see the accelerator guide);
odd block sizes still work everywhere interpret mode runs.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from .interpret import resolve_interpret
from .weighted_mix import LANE, _default_block_n, _pad_cols, round_matrix


def padded_width(n: int, block: int) -> int:
    """The wire width of an ``n``-column buffer: ``ceil(n/block)·block``
    — what :func:`quantize_block` actually puts on the wire."""
    if block < 1:
        raise ValueError("block must be >= 1")
    return -(-n // block) * block


def _tile_width(np_: int, rows: int, block: int, interp: bool) -> int:
    """Columns per grid cell: the whole (block-padded) width in
    interpret mode or when it fits one ``block·LANE`` unit; else the
    largest multiple of ``block·LANE`` within the ~2 MB budget, so each
    tile's (rows, bn/block) scale block keeps a lane-multiple minor dim.
    The grid is ``cdiv(np_, bn)``: Pallas masks the overhang of the last
    tile, and quantization blocks never straddle a tile, so the
    overhang never reaches an in-range output."""
    unit = block * LANE
    if interp or np_ <= unit:
        return np_
    budget = _default_block_n(np_, rows, False)
    return max(unit, budget // unit * unit)


def quantize_block(x: jnp.ndarray, *, block: int = 128, levels: int = 127,
                   scale_dtype=jnp.bfloat16, with_residual: bool = False,
                   interpret: Optional[bool] = None):
    """Encode ``x`` (B, N) float → ``(q, scales[, residual])``.

    ``q`` (B, NB·block) int8 in [-levels, levels]; ``scales`` (B, NB)
    in ``scale_dtype`` (the stored scale — decode multiplies by exactly
    this, so the pair is self-consistent); ``residual`` (B, N) f32
    ``x - q·s`` when ``with_residual`` (the error-feedback term, fused
    so the decode never re-runs).  See the module docstring for the
    block layout contract.
    """
    interp = resolve_interpret(interpret)
    B, N = x.shape
    if levels < 1:
        raise ValueError("levels must be >= 1")
    sdt = jnp.dtype(scale_dtype)
    Np = padded_width(N, block)
    xs = _pad_cols(x.astype(jnp.float32), Np)
    bn = _tile_width(Np, B, block, interp)
    nb = bn // block

    def kernel(x_ref, q_ref, s_ref, *res_ref):
        xv = x_ref[...].astype(jnp.float32).reshape(B, nb, block)
        amax = jnp.max(jnp.abs(xv), axis=2)                 # (B, nb)
        s = (amax / levels).astype(sdt)                     # stored scale
        s_used = jnp.where(s.astype(jnp.float32) > 0,
                           s.astype(jnp.float32), 1.0)
        q = jnp.clip(jnp.round(xv / s_used[:, :, None]), -levels, levels)
        q_ref[...] = q.reshape(B, bn).astype(jnp.int8)
        s_ref[...] = s
        if res_ref:
            res_ref[0][...] = (xv - q * s_used[:, :, None]).reshape(B, bn)

    row_spec = pl.BlockSpec((B, bn), lambda i: (0, i))
    s_spec = pl.BlockSpec((B, nb), lambda i: (0, i))
    out_shape = [jax.ShapeDtypeStruct((B, Np), jnp.int8),
                 jax.ShapeDtypeStruct((B, Np // block), sdt)]
    out_specs = [row_spec, s_spec]
    if with_residual:
        out_shape.append(jax.ShapeDtypeStruct((B, Np), jnp.float32))
        out_specs.append(row_spec)
    out = pl.pallas_call(
        kernel, grid=(pl.cdiv(Np, bn),), in_specs=[row_spec],
        out_specs=out_specs, out_shape=out_shape, interpret=interp)(xs)
    if with_residual:
        return out[0], out[1], out[2][:, :N]
    return out[0], out[1]


def dequantize_block(q: jnp.ndarray, scales: jnp.ndarray, *,
                     block: int = 128,
                     interpret: Optional[bool] = None) -> jnp.ndarray:
    """Decode ``(q, scales)`` → (B, NB·block) f32 (the standalone half
    of the pair; the mixing paths prefer the fused
    :func:`dequant_accumulate` / :func:`gather_mix_int8` receives)."""
    interp = resolve_interpret(interpret)
    B, Nq = q.shape
    if Nq % block or scales.shape != (B, Nq // block):
        raise ValueError(
            f"q {q.shape} / scales {scales.shape} do not agree with "
            f"block {block}")
    bn = _tile_width(Nq, B, block, interp)
    nb = bn // block

    def kernel(q_ref, s_ref, out_ref):
        s = s_ref[...].astype(jnp.float32)
        deq = q_ref[...].astype(jnp.float32).reshape(B, nb, block) \
            * s[:, :, None]
        out_ref[...] = deq.reshape(B, bn)

    out = pl.pallas_call(
        kernel, grid=(pl.cdiv(Nq, bn),),
        in_specs=[pl.BlockSpec((B, bn), lambda i: (0, i)),
                  pl.BlockSpec((B, nb), lambda i: (0, i))],
        out_specs=pl.BlockSpec((B, bn), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((B, Nq), jnp.float32),
        interpret=interp)(q, scales)
    return out


def dequant_accumulate(acc: Optional[jnp.ndarray], q: jnp.ndarray,
                       scales: jnp.ndarray, w: jnp.ndarray, *,
                       block: int = 128,
                       interpret: Optional[bool] = None) -> jnp.ndarray:
    """Fused dequantize + mixing accumulate:
    ``acc + w[:, None] · dequant(q, scales)`` over (B, N) rows — the
    int8 receive of the shard_map flat path.  The dequantized tile
    exists only in VMEM while the accumulator tile is live; ``acc=None``
    is the init form ``w[:, None] · dequant(q, scales)``.  Returns
    (B, N) where N = acc's width (≤ the wire width; the wire's
    block-padding columns are dropped), or the full wire width for
    ``acc=None``."""
    interp = resolve_interpret(interpret)
    B, Nq = q.shape
    if Nq % block or scales.shape != (B, Nq // block):
        raise ValueError(
            f"q {q.shape} / scales {scales.shape} do not agree with "
            f"block {block}")
    bn = _tile_width(Nq, B, block, interp)
    nb = bn // block
    w2 = w.reshape(B, 1).astype(jnp.float32)
    N = Nq if acc is None else acc.shape[1]
    if N > Nq:
        raise ValueError(f"acc width {N} exceeds wire width {Nq}")

    def kernel(*refs):
        if acc is None:
            q_ref, s_ref, w_ref, out_ref = refs
            base = 0.0
        else:
            acc_ref, q_ref, s_ref, w_ref, out_ref = refs
            base = acc_ref[...].astype(jnp.float32)
        s = s_ref[...].astype(jnp.float32)
        deq = q_ref[...].astype(jnp.float32).reshape(B, nb, block) \
            * s[:, :, None]
        out_ref[...] = (base + w_ref[...] * deq.reshape(B, bn)).astype(
            out_ref.dtype)

    row_spec = pl.BlockSpec((B, bn), lambda i: (0, i))
    s_spec = pl.BlockSpec((B, nb), lambda i: (0, i))
    w_spec = pl.BlockSpec((B, 1), lambda i: (0, 0))
    if acc is None:
        out = pl.pallas_call(
            kernel, grid=(pl.cdiv(Nq, bn),),
            in_specs=[row_spec, s_spec, w_spec], out_specs=row_spec,
            out_shape=jax.ShapeDtypeStruct((B, Nq), jnp.float32),
            interpret=interp)(q, scales, w2)
        return out
    accs = _pad_cols(acc, Nq)
    out = pl.pallas_call(
        kernel, grid=(pl.cdiv(Nq, bn),),
        in_specs=[row_spec, row_spec, s_spec, w_spec], out_specs=row_spec,
        out_shape=jax.ShapeDtypeStruct((B, Nq), acc.dtype),
        interpret=interp)(accs, q, scales, w2)
    return out[:, :N]


def gather_mix_int8(q: jnp.ndarray, scales: jnp.ndarray, srcs,
                    weights: jnp.ndarray, *, block: int = 128,
                    interpret: Optional[bool] = None) -> jnp.ndarray:
    """Int8-aware round-matrix mixing: the compressed-population sibling
    of :func:`repro.kernels.weighted_mix.gather_mix`.

    ``q``/``scales`` are the :func:`quantize_block` encoding of the
    (C, N) population buffer; ``srcs``/``weights`` the (C, K1) source
    rows (host-static or traced) and runtime weights.  The (srcs,
    weights) table scatters into the dense (C, C) round matrix W and
    each column tile runs dequantize → ``W @ tile`` with the
    dequantized tile never leaving VMEM.  HBM traffic: C·N int8 + C·NB
    scales read, C·N f32 written — the read side is ~4× lighter than
    the uncompressed kernel.  Returns (C, NB·block) f32 (block-padded
    wire width; callers slice to N)."""
    interp = resolve_interpret(interpret)
    C, Nq = q.shape
    if Nq % block or scales.shape != (C, Nq // block):
        raise ValueError(
            f"q {q.shape} / scales {scales.shape} do not agree with "
            f"block {block}")
    W = round_matrix(C, srcs, weights)
    bn = _tile_width(Nq, C, block, interp)
    nb = bn // block

    def kernel(W_ref, q_ref, s_ref, out_ref):
        s = s_ref[...].astype(jnp.float32)
        deq = q_ref[...].astype(jnp.float32).reshape(C, nb, block) \
            * s[:, :, None]
        out_ref[...] = jnp.dot(W_ref[...], deq.reshape(C, bn),
                               preferred_element_type=jnp.float32,
                               precision=jax.lax.Precision.HIGHEST)

    out = pl.pallas_call(
        kernel, grid=(pl.cdiv(Nq, bn),),
        in_specs=[pl.BlockSpec((C, C), lambda i: (0, 0)),
                  pl.BlockSpec((C, bn), lambda i: (0, i)),
                  pl.BlockSpec((C, nb), lambda i: (0, i))],
        out_specs=pl.BlockSpec((C, bn), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((C, Nq), jnp.float32),
        interpret=interp)(W, q, scales)
    return out
