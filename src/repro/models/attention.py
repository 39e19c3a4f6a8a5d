"""Attention: GQA (full-causal, sliding-window, qk-norm), blockwise
memory-bounded prefill, single-token decode against a KV cache, and
cross-attention for the enc-dec (audio) family.

The train/prefill path is *blockwise* (double ``lax.scan`` over query and
KV chunks with online softmax) so peak activation memory is
O(chunk²·heads) instead of O(seq²·heads) — this is what lets the 32k
prefill dry-run fit a v5e HBM budget without a fused kernel, and it is
the exact algorithm our Pallas ``flash_decode`` kernel implements for the
serving hot path.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..obs.profile import scope
from .layers import apply_rope, dense_init, matmul, rmsnorm, rmsnorm_init

NEG_INF = -1e30


def _scan(body, init, xs):
    from . import model as _m
    n = jax.tree.leaves(xs)[0].shape[0]
    return jax.lax.scan(body, init, xs, unroll=n if _m.SCAN_UNROLL else 1)


# --------------------------------------------------------------------------
# Parameters
# --------------------------------------------------------------------------

def gqa_init(key, d_model: int, num_heads: int, num_kv_heads: int,
             head_dim: int, qk_norm: bool = False, dtype=jnp.float32) -> dict:
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], d_model, num_heads * head_dim, dtype),
        "wk": dense_init(ks[1], d_model, num_kv_heads * head_dim, dtype),
        "wv": dense_init(ks[2], d_model, num_kv_heads * head_dim, dtype),
        "wo": dense_init(ks[3], num_heads * head_dim, d_model, dtype),
    }
    if qk_norm:
        p["q_norm"] = rmsnorm_init(head_dim, dtype)
        p["k_norm"] = rmsnorm_init(head_dim, dtype)
    return p


def _project_qkv(p: dict, x: jnp.ndarray, num_heads: int, num_kv_heads: int,
                 head_dim: int, positions: jnp.ndarray, rope_theta: float,
                 rms_eps: float) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    B, S, _ = x.shape
    q = matmul(x, p["wq"]).reshape(B, S, num_heads, head_dim)
    k = matmul(x, p["wk"]).reshape(B, S, num_kv_heads, head_dim)
    v = matmul(x, p["wv"]).reshape(B, S, num_kv_heads, head_dim)
    if "q_norm" in p:
        q = rmsnorm(p["q_norm"], q, rms_eps)
        k = rmsnorm(p["k_norm"], k, rms_eps)
    q = apply_rope(q, positions, rope_theta)
    k = apply_rope(k, positions, rope_theta)
    return q, k, v


# --------------------------------------------------------------------------
# Blockwise causal attention (train / prefill)
# --------------------------------------------------------------------------

# dry-run depth probes override the block size so fully-unrolled probe
# modules stay a tractable number of blocks (FLOPs are chunk-invariant)
CHUNK_OVERRIDE: Optional[int] = None


def _pick_chunk(seq: int, preferred: int = 1024) -> int:
    c = min(seq, CHUNK_OVERRIDE or preferred)
    while seq % c:
        c //= 2
    return max(c, 1)


def _blockwise_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                         window: Optional[int] = None,
                         chunk: Optional[int] = None,
                         causal: bool = True) -> jnp.ndarray:
    """Causal (optionally sliding-window) GQA attention.

    q: (B, S, Hq, hd); k, v: (B, S, Hkv, hd) with Hq % Hkv == 0.
    Returns (B, S, Hq, hd).  Peak memory O(B · Hq · chunk²).
    """
    B, S, Hq, hd = q.shape
    Hkv = k.shape[2]
    G = Hq // Hkv
    C = chunk or _pick_chunk(S)
    nq = S // C
    scale = hd ** -0.5

    # (nq, B, C, Hkv, G, hd) chunked views
    qc = q.reshape(B, nq, C, Hkv, G, hd).transpose(1, 0, 2, 3, 4, 5)
    kc = k.reshape(B, nq, C, Hkv, hd).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(B, nq, C, Hkv, hd).transpose(1, 0, 2, 3, 4)
    pos = jnp.arange(S, dtype=jnp.int32).reshape(nq, C)

    def q_block(_, qi):
        qb, qpos, iq = qi                       # (B,C,Hkv,G,hd), (C,), scalar
        m0 = jnp.full((B, C, Hkv, G), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, C, Hkv, G), jnp.float32)
        a0 = jnp.zeros((B, C, Hkv, G, hd), jnp.float32)

        def kv_block(carry, kj):
            m, l, acc = carry
            kb, vb, kpos = kj                   # (B,C,Hkv,hd), (C,)
            s = jnp.einsum("bqhgd,bkhd->bqhgk", qb.astype(jnp.float32),
                           kb.astype(jnp.float32)) * scale
            if causal:
                mask = kpos[None, :] <= qpos[:, None]
            else:
                mask = jnp.ones((C, C), bool)
            if window is not None:
                mask &= kpos[None, :] > qpos[:, None] - window
            s = jnp.where(mask[None, :, None, None, :], s, NEG_INF)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            alpha = jnp.exp(m - m_new)
            l_new = l * alpha + p.sum(axis=-1)
            acc_new = acc * alpha[..., None] + jnp.einsum(
                "bqhgk,bkhd->bqhgd", p, vb.astype(jnp.float32))
            return (m_new, l_new, acc_new), None

        (m, l, acc), _ = _scan(kv_block, (m0, l0, a0), (kc, vc, pos))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return None, out

    _, out = _scan(q_block, None,
                          (qc, pos, jnp.arange(nq, dtype=jnp.int32)))
    # (nq, B, C, Hkv, G, hd) -> (B, S, Hq, hd)
    out = out.transpose(1, 0, 2, 3, 4, 5).reshape(B, S, Hq, hd)
    return out.astype(q.dtype)


def blockwise_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                        window: Optional[int] = None,
                        chunk: Optional[int] = None,
                        causal: bool = True) -> jnp.ndarray:
    """Flash-attention memory behavior: never save the O(S·chunk) score
    blocks for backward — recompute the blockwise pass from (q, k, v).
    Ops under the named scope ``model.attention``."""
    import functools
    inner = functools.partial(_blockwise_attention, window=window,
                              chunk=chunk, causal=causal)
    inner = jax.checkpoint(inner, policy=jax.checkpoint_policies.nothing_saveable)
    with scope("model.attention"):
        return inner(q, k, v)


def gqa_apply(p: dict, x: jnp.ndarray, *, num_heads: int, num_kv_heads: int,
              head_dim: int, rope_theta: float, rms_eps: float = 1e-5,
              window: Optional[int] = None, causal: bool = True,
              positions: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Full train/prefill GQA self-attention block body (no residual)."""
    B, S, _ = x.shape
    if positions is None:
        positions = jnp.arange(S, dtype=jnp.int32)[None, :].repeat(B, 0)
    q, k, v = _project_qkv(p, x, num_heads, num_kv_heads, head_dim,
                           positions, rope_theta, rms_eps)
    out = blockwise_attention(q, k, v, window=window, causal=causal)
    return matmul(out.reshape(B, S, num_heads * head_dim), p["wo"])


# --------------------------------------------------------------------------
# Decode: one token against a (possibly ring-buffered) KV cache
# --------------------------------------------------------------------------

# When True, gqa_decode dispatches cache attention (window=None path) to
# the Pallas flash_decode kernel instead of the pure-jnp oracle — the
# serving plane's --kernel flag.  Trace-time knob: flip it before the
# decode step is jitted.  Off by default (on CPU the kernel runs in
# interpret mode: correct but slow).
DECODE_KERNEL = False


def init_kv_cache(batch: int, cache_len: int, num_kv_heads: int,
                  head_dim: int, dtype=jnp.float32) -> dict:
    return {
        "k": jnp.zeros((batch, cache_len, num_kv_heads, head_dim), dtype),
        "v": jnp.zeros((batch, cache_len, num_kv_heads, head_dim), dtype),
    }


def _check_cache_overflow(pos, cache_len: int) -> None:
    """Raise when a concrete prefix-cache position is past the end.

    The old path silently let ``dynamic_update_slice`` clamp the write
    to the last slot, overwriting whatever was there — a wrong-answer
    bug, not an error.  ``pos`` is only checkable when concrete (eager
    decode, host-driven loops); under jit the serving plane guards
    host-side (:class:`repro.runtime.serving.ServeLoop` tracks per-slot
    positions) because a traced value cannot raise.  Ring-buffer reuse
    is the *windowed* path — prefix caches never wrap."""
    if isinstance(pos, jax.core.Tracer):
        return
    p = np.asarray(pos)
    if p.size and int(p.max()) >= cache_len:
        raise ValueError(
            f"decode position {int(p.max())} overflows the {cache_len}-slot "
            f"prefix KV cache; grow cache_len (or use a sliding window — "
            f"ring-buffer reuse is the windowed path)")


def _positions_vector(pos, batch: int) -> jnp.ndarray:
    """Normalize scalar-or-(B,) ``pos`` to a (B,) int32 vector."""
    pos = jnp.asarray(pos, jnp.int32)
    if pos.ndim > 1 or (pos.ndim == 1 and pos.shape[0] != batch):
        raise ValueError(
            f"pos must be a scalar or a ({batch},) per-slot vector, got "
            f"shape {pos.shape}")
    return jnp.broadcast_to(pos.reshape(-1), (batch,))


def gqa_decode(p: dict, x: jnp.ndarray, cache: dict, pos: jnp.ndarray, *,
               num_heads: int, num_kv_heads: int, head_dim: int,
               rope_theta: float, rms_eps: float = 1e-5,
               window: Optional[int] = None) -> Tuple[jnp.ndarray, dict]:
    """One-token decode.  x: (B, 1, D); ``pos``: scalar int32 absolute
    position shared by the batch, or a per-slot (B,) vector (continuous
    batching: every request sits at its own depth; rows with pos < 0
    are empty slots — nothing valid, zero attention output, and the
    row's write lands harmlessly inside its own dead cache row).  The
    cache holds ``cache_len`` slots; with a sliding window the cache is
    a ring buffer of exactly ``window`` slots.  Without a window a
    concrete pos >= cache_len raises instead of silently overwriting
    the last slot.  Returns (attn_out (B,1,D), new_cache).
    """
    B = x.shape[0]
    cache_len = cache["k"].shape[1]
    if window is None:
        _check_cache_overflow(pos, cache_len)
    pos = jnp.asarray(pos, jnp.int32)
    pos_vec = _positions_vector(pos, B)
    q, k, v = _project_qkv(p, x, num_heads, num_kv_heads, head_dim,
                           pos_vec[:, None], rope_theta, rms_eps)
    kd, vd = k.astype(cache["k"].dtype), v.astype(cache["v"].dtype)
    if pos.ndim == 0:
        # legacy whole-batch position: one slice write for all rows
        slot = pos % cache_len if window is not None else pos
        ck = jax.lax.dynamic_update_slice(cache["k"], kd, (0, slot, 0, 0))
        cv = jax.lax.dynamic_update_slice(cache["v"], vd, (0, slot, 0, 0))
    else:
        slot = (pos_vec % cache_len if window is not None
                else jnp.clip(pos_vec, 0, cache_len - 1))
        write = jax.vmap(
            lambda c, u, s: jax.lax.dynamic_update_slice(c, u, (s, 0, 0)))
        ck = write(cache["k"], kd, slot)
        cv = write(cache["v"], vd, slot)
    if DECODE_KERNEL and window is None:
        from ..kernels.flash_decode import flash_decode
        out = flash_decode(q[:, 0], ck, cv, pos)[:, None]
    else:
        out = cache_attention(q, ck, cv, pos, window=window)
    out = matmul(out.reshape(B, 1, num_heads * head_dim), p["wo"])
    return out, {"k": ck, "v": cv}


def cache_attention(q: jnp.ndarray, ck: jnp.ndarray, cv: jnp.ndarray,
                    pos: jnp.ndarray, window: Optional[int] = None) -> jnp.ndarray:
    """q: (B, 1, Hq, hd) vs cache (B, L, Hkv, hd) → (B, 1, Hq, hd).

    ``pos`` is a scalar or per-slot (B,) vector.  Validity: slot i
    holds absolute position i (no window) or is valid iff the ring
    buffer has written it within the last ``window`` steps; rows with
    pos < 0 are empty serving slots and return exactly zero (softmax
    multiplied by the row's validity — matching the kernel's masked
    online softmax).  This is the pure-jnp oracle of the Pallas
    ``flash_decode`` kernel.

    With ``layers.F32_DOT_OUTPUT`` (baseline) the cache is upcast to f32
    before the contractions — faithful to naive serving code, but it
    materializes (and reshards) a 2× copy of the whole cache every
    token.  The bf16c perf knob contracts directly against the bf16
    cache with f32 accumulation — the Pallas kernel's exact dataflow.
    """
    from .layers import F32_DOT_OUTPUT
    B, _, Hq, hd = q.shape
    L, Hkv = ck.shape[1], ck.shape[2]
    G = Hq // Hkv
    qg = q.reshape(B, Hkv, G, hd)
    if F32_DOT_OUTPUT:
        s = jnp.einsum("bhgd,bkhd->bhgk", qg.astype(jnp.float32),
                       ck.astype(jnp.float32))
    else:
        s = jnp.einsum("bhgd,bkhd->bhgk", qg.astype(ck.dtype), ck,
                       preferred_element_type=jnp.float32)
    s = s * (hd ** -0.5)
    pos_vec = _positions_vector(pos, B)
    idx = jnp.arange(L, dtype=jnp.int32)
    if window is None:
        valid = idx[None, :] <= pos_vec[:, None]                     # (B, L)
    else:
        # ring buffer: all slots valid once pos+1 >= L; before that,
        # slots <= pos (empty rows pos < 0 stay all-invalid)
        valid = ((idx[None, :] <= pos_vec[:, None])
                 | (pos_vec[:, None] + 1 >= L))
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1) * valid[:, None, None, :]
    if F32_DOT_OUTPUT:
        out = jnp.einsum("bhgk,bkhd->bhgd", p, cv.astype(jnp.float32))
    else:
        out = jnp.einsum("bhgk,bkhd->bhgd", p.astype(cv.dtype), cv,
                         preferred_element_type=jnp.float32)
    return out.reshape(B, 1, Hq, hd).astype(q.dtype)


def gqa_prefill(p: dict, x: jnp.ndarray, cache: dict, *, num_heads: int,
                num_kv_heads: int, head_dim: int, rope_theta: float,
                rms_eps: float = 1e-5,
                window: Optional[int] = None) -> Tuple[jnp.ndarray, dict]:
    """Whole-prompt prefill: one batched pass over x (B, P, D) that
    writes every position's K/V into the cache and attends causally
    within the prompt — replacing P single-token ``gqa_decode``
    dispatches.  Fresh-cache semantics (positions 0..P-1).  With a
    sliding window whose ring is shorter than P, only the last
    ``cache_len`` positions are written, laid out at their ring slots
    (pos % cache_len) so subsequent ``gqa_decode`` steps continue the
    ring seamlessly.  Returns (attn_out (B,P,D), new_cache)."""
    B, P, _ = x.shape
    cache_len = cache["k"].shape[1]
    if window is None and P > cache_len:
        raise ValueError(
            f"prompt length {P} overflows the {cache_len}-slot prefix KV "
            f"cache")
    positions = jnp.broadcast_to(jnp.arange(P, dtype=jnp.int32)[None, :],
                                 (B, P))
    q, k, v = _project_qkv(p, x, num_heads, num_kv_heads, head_dim,
                           positions, rope_theta, rms_eps)
    kd, vd = k.astype(cache["k"].dtype), v.astype(cache["v"].dtype)
    if P > cache_len:
        # ring layout of the last cache_len positions: slot s holds the
        # unique position in [P - cache_len, P) with pos % cache_len == s
        order = np.argsort(np.arange(P - cache_len, P) % cache_len)
        ck = kd[:, P - cache_len:][:, order]
        cv = vd[:, P - cache_len:][:, order]
    else:
        ck = jax.lax.dynamic_update_slice(cache["k"], kd, (0, 0, 0, 0))
        cv = jax.lax.dynamic_update_slice(cache["v"], vd, (0, 0, 0, 0))
    out = blockwise_attention(q, k, v, window=window, causal=True)
    out = matmul(out.reshape(B, P, num_heads * head_dim), p["wo"])
    return out, {"k": ck, "v": cv}


# --------------------------------------------------------------------------
# Cross-attention (enc-dec audio family)
# --------------------------------------------------------------------------

def cross_init(key, d_model: int, num_heads: int, num_kv_heads: int,
               head_dim: int, dtype=jnp.float32) -> dict:
    return gqa_init(key, d_model, num_heads, num_kv_heads, head_dim,
                    qk_norm=False, dtype=dtype)


def cross_apply(p: dict, x: jnp.ndarray, memory_kv: Tuple[jnp.ndarray, jnp.ndarray],
                *, num_heads: int, num_kv_heads: int, head_dim: int) -> jnp.ndarray:
    """Decoder cross-attention into precomputed encoder memory K/V.

    x: (B, S, D); memory k/v: (B, M, Hkv, hd).  No RoPE across modalities
    (positions are encoder-internal), no causal mask.
    """
    B, S, _ = x.shape
    mk, mv = memory_kv
    Hkv = mk.shape[2]
    G = num_heads // Hkv
    q = matmul(x, p["wq"]).reshape(B, S, Hkv, G, head_dim)
    s = jnp.einsum("bqhgd,bkhd->bqhgk", q.astype(jnp.float32),
                   mk.astype(jnp.float32)) * (head_dim ** -0.5)
    w = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bqhgk,bkhd->bqhgd", w, mv.astype(jnp.float32))
    out = out.reshape(B, S, num_heads * head_dim).astype(x.dtype)
    return matmul(out, p["wo"])


def cross_memory(p: dict, enc_out: jnp.ndarray, *, num_kv_heads: int,
                 head_dim: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Precompute encoder memory K/V once per sequence (prefill/serve)."""
    B, M, _ = enc_out.shape
    k = matmul(enc_out, p["wk"]).reshape(B, M, num_kv_heads, head_dim)
    v = matmul(enc_out, p["wv"]).reshape(B, M, num_kv_heads, head_dim)
    return k, v
