"""Dense GQA transformer with qk-norm (Qwen3): plain reference, weights,
model FLOPs.

Per layer ``x + wo(attn(rope(qnorm(wq h)), rope(knorm(wk h)), wv h))``
then ``x + w_down(silu(w_gate h) * w_up h)``, each ``h`` a pre-RMSNorm;
rotate-half RoPE, causal softmax attention with grouped K/V heads, tied
embeddings, final RMSNorm.  Attention is the plain (S, S) softmax.

``init`` lays the weights out as the program's parameter tree; the
values are this module's own, drawn from the key.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .common import (dot, exact, layer_scan, normal, padded_vocab, rmsnorm,
                     uniform, xent)


def init(m: dict, key, dtype=jnp.bfloat16) -> dict:
    d, L, ff = m["d_model"], m["num_layers"], m["d_ff"]
    hd, hq, hkv = m["head_dim"], m["num_heads"], m["num_kv_heads"]
    a, f = "seg0/sub0/attn/", "seg0/sub0/mlp/"
    return {
        "embed": normal(key, "embed", (padded_vocab(m["vocab_size"]), d),
                        0.02, dtype),
        "final_norm": jnp.ones((d,), dtype),
        "seg0": {"sub0": {
            "norm1": jnp.ones((L, d), dtype),
            "norm2": jnp.ones((L, d), dtype),
            "attn": {
                "wq": uniform(key, a + "wq", (L, d, hq * hd), d, dtype),
                "wk": uniform(key, a + "wk", (L, d, hkv * hd), d, dtype),
                "wv": uniform(key, a + "wv", (L, d, hkv * hd), d, dtype),
                "wo": uniform(key, a + "wo", (L, hq * hd, d), hq * hd,
                              dtype),
                "q_norm": jnp.ones((L, hd), dtype),
                "k_norm": jnp.ones((L, hd), dtype),
            },
            "mlp": {
                "w_gate": uniform(key, f + "w_gate", (L, d, ff), d, dtype),
                "w_up": uniform(key, f + "w_up", (L, d, ff), d, dtype),
                "w_down": uniform(key, f + "w_down", (L, ff, d), ff, dtype),
            }}},
    }


def _rope(x, theta: float):
    """Rotate-half RoPE over (B, S, H, hd) at positions 0..S-1."""
    S, hd = x.shape[1], x.shape[-1]
    freqs = theta ** -(jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs   # (S, hd/2)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _block(m: dict, q):
    hd, hq, hkv = m["head_dim"], m["num_heads"], m["num_kv_heads"]
    eps, theta = m["rms_eps"], m["rope_theta"]

    def body(x, lp):
        ap, fp = lp["attn"], lp["mlp"]
        B, S, _ = x.shape
        h = rmsnorm(x, lp["norm1"], eps)
        qh = dot("bsd,de->bse", h, ap["wq"], q).reshape(B, S, hq, hd)
        kh = dot("bsd,de->bse", h, ap["wk"], q).reshape(B, S, hkv, hd)
        vh = dot("bsd,de->bse", h, ap["wv"], q).reshape(B, S, hkv, hd)
        qh = _rope(rmsnorm(qh, ap["q_norm"], eps), theta)
        kh = _rope(rmsnorm(kh, ap["k_norm"], eps), theta)
        # query head j reads K/V head j // (hq // hkv); one K/V group at
        # a time, so that one group's (S, S) scores are alive
        qg = qh.reshape(B, S, hkv, hq // hkv, hd).transpose(2, 0, 1, 3, 4)
        causal = jnp.tril(jnp.ones((S, S), bool))

        def group(args):
            qq, kk, vv = args                 # (B,S,G,hd), (B,S,hd) x2
            s = dot("bqgd,bkd->bgqk", qq, kk, q) * hd ** -0.5
            w = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
            return dot("bgqk,bkd->bqgd", w, vv, q)
        o = jax.lax.map(jax.checkpoint(group), (
            qg, kh.transpose(2, 0, 1, 3), vh.transpose(2, 0, 1, 3)))
        o = o.transpose(1, 2, 0, 3, 4).reshape(B, S, hq * hd)
        x = x + dot("bse,ed->bsd", o, ap["wo"], q)
        h = rmsnorm(x, lp["norm2"], eps)
        g = jax.nn.silu(dot("bsd,df->bsf", h, fp["w_gate"], q))
        u = dot("bsd,df->bsf", h, fp["w_up"], q)
        return x + dot("bsf,fd->bsd", g * u, fp["w_down"], q)
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
    recompute): projections, SwiGLU, causal attention (a query attends
    (S+1)/2 keys on average: scores and values) and the logits."""
    d, ff = m["d_model"], m["d_ff"]
    hd, hq, hkv = m["head_dim"], m["num_heads"], m["num_kv_heads"]
    proj = 2 * d * (hq + 2 * hkv) * hd + 2 * hq * hd * d
    mlp = 6 * d * ff
    attn = 4 * hq * hd * (seq + 1) / 2
    logits = 2 * d * m["vocab_size"]
    return 3.0 * (m["num_layers"] * (proj + mlp + attn) + logits)


def param_count(m: dict) -> int:
    d, ff = m["d_model"], m["d_ff"]
    hd, hq, hkv = m["head_dim"], m["num_heads"], m["num_kv_heads"]
    per_layer = (2 * d + d * (hq + 2 * hkv) * hd + hq * hd * d + 2 * hd
                 + 3 * d * ff)
    return m["vocab_size"] * d + d + m["num_layers"] * per_layer
