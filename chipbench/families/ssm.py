"""Mamba2 (SSD) language model: plain reference, weights, model FLOPs.

Follows arXiv:2405.21060 (Mamba2 block, single B/C group): per layer
``x + out_proj(rmsnorm(ssd(conv(in_proj(rmsnorm(x)))) * silu(z)))``,
tied embeddings, final RMSNorm.  The SSD is written in its quadratic
"masked attention" form, ``y_t = sum_{s<=t} (C_t . B_s)
exp(sum_{r=s+1..t} dt_r A) dt_s x_s + D x_t``, which is the definition
the chunked algorithm under test factors.

``init`` lays the weights out as the program's parameter tree (keys,
shapes and dtypes); the values are this module's own, drawn from the
key: Mamba2's published initialisation of ``A`` (uniform in [1, 16]),
``dt`` (log-uniform in [1e-3, 1e-1]) and ``D`` (ones).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .common import (dot, exact, layer_scan, normal, padded_vocab, rmsnorm,
                     uniform, xent)


def sizes(m: dict):
    d, s = m["d_model"], m["ssm"]
    di = s["expand"] * d
    return d, di, di // s["headdim"], s["d_state"], s["d_conv"]


def init(m: dict, key, dtype=jnp.bfloat16) -> dict:
    d, di, nh, n, k = sizes(m)
    L = m["num_layers"]
    conv_ch = di + 2 * n
    p = "seg0/sub0/mamba/"
    dt = jnp.exp(jax.random.uniform(jax.random.fold_in(key, 1), (L, nh),
                                    jnp.float32, jnp.log(1e-3),
                                    jnp.log(1e-1)))
    return {
        "embed": normal(key, "embed", (padded_vocab(m["vocab_size"]), d),
                        0.02, dtype),
        "final_norm": jnp.ones((d,), dtype),
        "seg0": {"sub0": {
            "norm1": jnp.ones((L, d), dtype),
            "mamba": {
                "in_proj": uniform(key, p + "in_proj",
                                   (L, d, 2 * di + 2 * n + nh), d, dtype),
                "conv_w": uniform(key, p + "conv_w", (L, k, conv_ch), k,
                                  dtype),
                "conv_b": uniform(key, p + "conv_b", (L, conv_ch), k, dtype),
                # softplus(dt_bias) == dt
                "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                "A_log": jnp.log(jax.random.uniform(
                    jax.random.fold_in(key, 2), (L, nh), jnp.float32,
                    1.0, 16.0)),
                "D": jnp.ones((L, nh), jnp.float32),
                "norm": jnp.ones((L, di), dtype),
                "out_proj": uniform(key, p + "out_proj", (L, di, d), di,
                                    dtype),
            }}},
    }


def _ssd(x, dt, A, Bm, Cm, q):
    """x (B,S,H,P), dt (B,S,H), A (H,), Bm/Cm (B,S,N) -> (B,S,H,P),
    one head at a time so that one (S, S) decay matrix is alive."""
    S = x.shape[1]
    cb = dot("btn,bsn->bts", Cm, Bm, q)
    causal = jnp.tril(jnp.ones((S, S), bool))

    def head(args):
        xh, dth, ah = args                                # (B,S,P), (B,S)
        cs = jnp.cumsum(dth * ah, axis=1)
        seg = cs[:, :, None] - cs[:, None, :]             # (B,t,s)
        decay = jnp.exp(jnp.where(causal, seg, -jnp.inf))
        return dot("bts,bsp->btp", cb * decay, xh * dth[..., None], q)
    y = jax.lax.map(jax.checkpoint(head), (x.transpose(2, 0, 1, 3),
                                           dt.transpose(2, 0, 1), A))
    return y.transpose(1, 2, 0, 3)


def _block(m: dict, q):
    d, di, nh, n, k = sizes(m)
    eps = m["rms_eps"]
    hp = m["ssm"]["headdim"]

    def body(x, lp):
        mp = lp["mamba"]
        B, S, _ = x.shape
        proj = dot("bsd,de->bse", rmsnorm(x, lp["norm1"], eps),
                   mp["in_proj"], q)
        z, xs = proj[..., :di], proj[..., di:2 * di]
        Bm = proj[..., 2 * di:2 * di + n]
        Cm = proj[..., 2 * di + n:2 * di + 2 * n]
        dt = proj[..., 2 * di + 2 * n:]
        xbc = jnp.concatenate([xs, Bm, Cm], axis=-1)
        w = mp["conv_w"].astype(jnp.float32)
        padded = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
        conv = sum(padded[:, i:i + S] * w[i] for i in range(k))
        xbc = jax.nn.silu(conv + mp["conv_b"].astype(jnp.float32))
        xs, Bm, Cm = xbc[..., :di], xbc[..., di:di + n], xbc[..., di + n:]
        dt = jax.nn.softplus(dt + mp["dt_bias"])
        A = -jnp.exp(mp["A_log"])
        xh = xs.reshape(B, S, nh, hp)
        y = _ssd(xh, dt, A, Bm, Cm, q) + mp["D"][:, None] * xh
        y = rmsnorm(y.reshape(B, S, di) * jax.nn.silu(z), mp["norm"], eps)
        return x + dot("bse,ed->bsd", y, mp["out_proj"], q)
    return body


def loss(m: dict, params: dict, tokens, labels, q=exact, positions=None):
    """Mean next-token loss of one client's batch (B, S)."""
    V = m["vocab_size"]
    table = params["embed"][:V].astype(jnp.float32)
    x = q(table)[tokens]
    x = layer_scan(_block(m, q), x, params["seg0"]["sub0"])
    h = rmsnorm(x, params["final_norm"], m["rms_eps"])
    return xent(dot("bsd,vd->bsv", h, table, q), labels, positions)


def flops_per_token(m: dict, seq: int) -> float:
    """Forward + backward model FLOPs per trained token (3x forward, no
    recompute).  The SSD term is that of the chunked algorithm at the
    configuration's chunk length: intra-chunk C.B scores and their
    product with x, chunk states and their read-out."""
    d, di, nh, n, k = sizes(m)
    Q = min(m["ssm"]["chunk"], seq)
    proj = 2 * d * (2 * di + 2 * n + nh) + 2 * di * d
    conv = 2 * k * (di + 2 * n)
    ssd = 2 * Q * n + 2 * Q * di + 4 * n * di
    logits = 2 * d * m["vocab_size"]
    return 3.0 * (m["num_layers"] * (proj + conv + ssd) + logits)


def param_count(m: dict) -> int:
    d, di, nh, n, k = sizes(m)
    per_layer = (d + d * (2 * di + 2 * n + nh) + k * (di + 2 * n)
                 + (di + 2 * n) + 3 * nh + di + di * d)
    return m["vocab_size"] * d + d + m["num_layers"] * per_layer
