"""Mamba2 SSD — state-space duality, chunked dual form (arXiv:2405.21060).

Train/prefill uses the chunked SSD algorithm: intra-chunk attention-like
matmuls (MXU-friendly) + an inter-chunk state recurrence carried by
``lax.scan`` — O(S·Q) memory instead of O(S²).  Decode is the O(1)
recurrent step on a (B, H, P, N) state, which is what makes the
``long_500k`` shape native for the SSM and hybrid architectures.

This pure-jnp implementation is also the oracle for the Pallas
``ssd_scan`` kernel.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..obs.profile import scope
from .config import SSMConfig
from .layers import dense_init, matmul, rmsnorm, rmsnorm_init


def mamba_init(key, d_model: int, s: SSMConfig, dtype=jnp.float32) -> dict:
    ks = jax.random.split(key, 4)
    di = s.d_inner(d_model)
    nh = s.nheads(d_model)
    conv_ch = di + 2 * s.d_state
    return {
        # in_proj → [z (di), x (di), B (N), C (N), dt (nh)]
        "in_proj": dense_init(ks[0], d_model, 2 * di + 2 * s.d_state + nh, dtype),
        "conv_w": (jax.random.normal(ks[1], (s.d_conv, conv_ch), jnp.float32)
                   * (1.0 / s.d_conv)).astype(dtype),
        "conv_b": jnp.zeros((conv_ch,), dtype),
        "dt_bias": jnp.zeros((nh,), jnp.float32),
        "A_log": jnp.log(jnp.arange(1, nh + 1, dtype=jnp.float32)),
        "D": jnp.ones((nh,), jnp.float32),
        "norm": rmsnorm_init(di, dtype),
        "out_proj": dense_init(ks[3], di, d_model, dtype),
    }


def _split_proj(proj: jnp.ndarray, di: int, n: int, nh: int):
    z = proj[..., :di]
    x = proj[..., di:2 * di]
    Bm = proj[..., 2 * di:2 * di + n]
    Cm = proj[..., 2 * di + n:2 * di + 2 * n]
    dt = proj[..., 2 * di + 2 * n:]
    assert dt.shape[-1] == nh
    return z, x, Bm, Cm, dt


def causal_conv(xbc: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray,
                init: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Depthwise causal conv over (B, S, Ch) with taps (K, Ch)."""
    K = w.shape[0]
    pad = xbc if init is not None else jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
    if init is not None:
        pad = jnp.concatenate([init, xbc], axis=1)
    out = sum(pad[:, i:i + xbc.shape[1], :] * w[i][None, None, :]
              for i in range(K))
    return jax.nn.silu(out + b[None, None, :])


def _ssd_chunked(x: jnp.ndarray, dt: jnp.ndarray, A: jnp.ndarray,
                 Bm: jnp.ndarray, Cm: jnp.ndarray, chunk: int,
                 init_state: Optional[jnp.ndarray] = None
                 ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Chunked SSD.

    x: (B, S, H, P); dt: (B, S, H) (post-softplus); A: (H,) negative;
    Bm, Cm: (B, S, N) single-group.  Returns (y (B,S,H,P), final state
    (B,H,P,N)).
    """
    Bsz, S, H, P = x.shape
    N = Bm.shape[-1]
    Q = min(chunk, S)
    while S % Q:
        Q //= 2
    nc = S // Q

    dA = dt * A[None, None, :]                       # (B,S,H) log-decay
    xdt = x * dt[..., None]                          # dt-weighted input
    # chunked views
    dAc = dA.reshape(Bsz, nc, Q, H)
    xc = xdt.reshape(Bsz, nc, Q, H, P)
    Bc = Bm.reshape(Bsz, nc, Q, N)
    Cc = Cm.reshape(Bsz, nc, Q, N)

    cs = jnp.cumsum(dAc, axis=2)                     # (B,nc,Q,H) inclusive
    seg = cs[:, :, :, None, :] - cs[:, :, None, :, :]   # (B,nc,Qi,Qj,H)
    causal = jnp.tril(jnp.ones((Q, Q), bool))
    # mask BEFORE exp: upper-triangular entries have large positive
    # exponents whose inf would poison gradients through jnp.where.
    seg = jnp.where(causal[None, None, :, :, None], seg, -jnp.inf)
    L = jnp.exp(seg)

    # intra-chunk: y_ij = (C_i·B_j)·L_ij·xdt_j
    cb = jnp.einsum("bcin,bcjn->bcij", Cc.astype(jnp.float32),
                    Bc.astype(jnp.float32))
    y_diag = jnp.einsum("bcij,bcijh,bcjhp->bcihp", cb, L,
                        xc.astype(jnp.float32))

    # per-chunk end state: S_c = Σ_j exp(cs_end - cs_j)·B_j ⊗ xdt_j
    decay_to_end = jnp.exp(cs[:, :, -1:, :] - cs)    # (B,nc,Q,H)
    chunk_state = jnp.einsum("bcjn,bcjh,bcjhp->bchpn",
                             Bc.astype(jnp.float32), decay_to_end,
                             xc.astype(jnp.float32))
    chunk_decay = jnp.exp(cs[:, :, -1, :])           # (B,nc,H) total decay

    s0 = (jnp.zeros((Bsz, H, P, N), jnp.float32) if init_state is None
          else init_state.astype(jnp.float32))

    def step(state, inputs):
        st_c, dec_c = inputs                         # (B,H,P,N), (B,H)
        prev = state
        new = prev * dec_c[:, :, None, None] + st_c
        return new, prev

    from . import model as _m
    final, prev_states = jax.lax.scan(
        step, s0, (chunk_state.transpose(1, 0, 2, 3, 4),
                   chunk_decay.transpose(1, 0, 2)),
        unroll=nc if _m.SCAN_UNROLL else 1)
    prev_states = prev_states.transpose(1, 0, 2, 3, 4)  # (B,nc,H,P,N)

    # inter-chunk: y_i += C_i · prev_state · exp(cs_i)
    y_off = jnp.einsum("bcin,bcih,bchpn->bcihp", Cc.astype(jnp.float32),
                       jnp.exp(cs), prev_states)
    y = (y_diag + y_off).reshape(Bsz, S, H, P)
    return y.astype(x.dtype), final


def ssd_chunked(x, dt, A, Bm, Cm, chunk: int, init_state=None):
    """Flash-style memory: the O(Q²) intra-chunk decay matrices are
    recomputed in the backward pass, never saved.  Ops under the named
    scope ``model.ssd``."""
    import functools
    inner = functools.partial(_ssd_chunked, chunk=chunk)
    inner = jax.checkpoint(inner, policy=jax.checkpoint_policies.nothing_saveable)
    with scope("model.ssd"):
        if init_state is None:
            return inner(x, dt, A, Bm, Cm)
        return inner(x, dt, A, Bm, Cm, init_state=init_state)


def mamba_apply(p: dict, xin: jnp.ndarray, s: SSMConfig,
                rms_eps: float = 1e-5) -> jnp.ndarray:
    """Full Mamba2 block body (no residual).  xin: (B, S, D)."""
    Bsz, S, D = xin.shape
    di = s.d_inner(D)
    nh = s.nheads(D)
    proj = matmul(xin, p["in_proj"])
    z, x, Bm, Cm, dt = _split_proj(proj, di, s.d_state, nh)
    xbc = causal_conv(jnp.concatenate([x, Bm, Cm], axis=-1),
                      p["conv_w"], p["conv_b"])
    x, Bm, Cm = xbc[..., :di], xbc[..., di:di + s.d_state], xbc[..., di + s.d_state:]
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])
    A = -jnp.exp(p["A_log"])
    xh = x.reshape(Bsz, S, nh, s.headdim)
    y, _ = ssd_chunked(xh, dt, A, Bm, Cm, s.chunk)
    y = (y + p["D"][None, None, :, None] * xh).astype(xin.dtype)
    y = y.reshape(Bsz, S, di)
    y = rmsnorm(p["norm"], y * jax.nn.silu(z), rms_eps)
    return matmul(y, p["out_proj"])


# --------------------------------------------------------------------------
# O(1) decode step
# --------------------------------------------------------------------------

def init_ssm_cache(batch: int, d_model: int, s: SSMConfig,
                   dtype=jnp.float32) -> dict:
    di = s.d_inner(d_model)
    nh = s.nheads(d_model)
    return {
        "state": jnp.zeros((batch, nh, s.headdim, s.d_state), jnp.float32),
        "conv": jnp.zeros((batch, s.d_conv - 1, di + 2 * s.d_state), dtype),
    }


def mamba_prefill(p: dict, xin: jnp.ndarray, cache: dict, s: SSMConfig,
                  rms_eps: float = 1e-5) -> Tuple[jnp.ndarray, dict]:
    """Whole-prompt Mamba2 prefill: one chunked-SSD pass over xin
    (B, P, D) that also captures the recurrent state after the last
    token and the conv tail (the last d_conv-1 *pre-activation* conv
    channels) — the exact cache ``mamba_decode`` expects, replacing P
    recurrent single-token dispatches.  Fresh-cache semantics (the
    incoming cache must be zeros).  Returns (out (B,P,D), new_cache)."""
    Bsz, S, D = xin.shape
    di = s.d_inner(D)
    nh = s.nheads(D)
    proj = matmul(xin, p["in_proj"])
    z, x, Bm, Cm, dt = _split_proj(proj, di, s.d_state, nh)
    xbc_raw = jnp.concatenate([x, Bm, Cm], axis=-1)          # (B,S,ch)
    xbc = causal_conv(xbc_raw, p["conv_w"], p["conv_b"])
    x, Bm, Cm = xbc[..., :di], xbc[..., di:di + s.d_state], xbc[..., di + s.d_state:]
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])
    A = -jnp.exp(p["A_log"])
    xh = x.reshape(Bsz, S, nh, s.headdim)
    y, final = ssd_chunked(xh, dt, A, Bm, Cm, s.chunk)
    y = (y + p["D"][None, None, :, None] * xh).astype(xin.dtype)
    y = y.reshape(Bsz, S, di)
    y = rmsnorm(p["norm"], y * jax.nn.silu(z), rms_eps)
    # conv tail: last d_conv-1 raw (pre-silu) rows, zero-padded on the
    # left exactly as the causal conv saw them
    K = p["conv_w"].shape[0]
    padded = jnp.concatenate(
        [jnp.zeros((Bsz, K - 1, xbc_raw.shape[-1]), xbc_raw.dtype), xbc_raw],
        axis=1)
    new_cache = {"state": final, "conv": padded[:, -(K - 1):, :]
                 .astype(cache["conv"].dtype)}
    return matmul(y, p["out_proj"]), new_cache


def mamba_decode(p: dict, xin: jnp.ndarray, cache: dict, s: SSMConfig,
                 rms_eps: float = 1e-5) -> Tuple[jnp.ndarray, dict]:
    """One-token recurrent step.  xin: (B, 1, D)."""
    Bsz, _, D = xin.shape
    di = s.d_inner(D)
    nh = s.nheads(D)
    proj = matmul(xin, p["in_proj"])
    z, x, Bm, Cm, dt = _split_proj(proj, di, s.d_state, nh)
    xbc_new = jnp.concatenate([x, Bm, Cm], axis=-1)      # (B,1,ch)
    conv_win = jnp.concatenate([cache["conv"], xbc_new], axis=1)  # (B,K,ch)
    out = jnp.einsum("bkc,kc->bc", conv_win, p["conv_w"]) + p["conv_b"]
    xbc = jax.nn.silu(out)[:, None, :]
    x, Bm, Cm = xbc[..., :di], xbc[..., di:di + s.d_state], xbc[..., di + s.d_state:]
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])[:, 0]   # (B,H)
    A = -jnp.exp(p["A_log"])
    xh = x.reshape(Bsz, nh, s.headdim).astype(jnp.float32)
    dA = jnp.exp(dt * A[None, :])                        # (B,H)
    dBx = jnp.einsum("bhp,bn,bh->bhpn", xh, Bm[:, 0].astype(jnp.float32), dt)
    state = cache["state"] * dA[:, :, None, None] + dBx
    y = jnp.einsum("bhpn,bn->bhp", state, Cm[:, 0].astype(jnp.float32))
    y = (y + p["D"][None, :, None] * xh).astype(xin.dtype)
    y = y.reshape(Bsz, 1, di)
    y = rmsnorm(p["norm"], y * jax.nn.silu(z), rms_eps)
    new_cache = {"state": state, "conv": conv_win[:, 1:, :]}
    return matmul(y, p["out_proj"]), new_cache
