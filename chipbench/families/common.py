"""Plain float32 building blocks shared by the reference families.

Nothing here imports the program under test.  Every contraction goes
through :func:`dot`, which takes the rounding ``q``: the identity for
the reference, :func:`fp8` for the control (the reference computed one
precision below the configuration's bf16: every contraction's operands
rounded to float8 e4m3 with per-tensor scales).
"""

from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def exact(x):
    return x


def _round(x, dtype):
    """Per-tensor scaled round trip of ``x`` through a float8 ``dtype``."""
    top = float(jnp.finfo(dtype).max)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
    return (x / scale).astype(dtype).astype(jnp.float32) * scale


def fp8(x):
    """``x`` rounded to float8 e4m3 (per-tensor scale) in the forward
    pass; the gradient passes through unrounded."""
    return x + jax.lax.stop_gradient(_round(x, jnp.float8_e4m3fn) - x)


def dot(eq: str, a, b, q=exact):
    return jnp.einsum(eq, q(a.astype(jnp.float32)), q(b.astype(jnp.float32)),
                      precision=HIGHEST)


def rmsnorm(x, g, eps: float):
    x = x.astype(jnp.float32)
    return (x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
            * g.astype(jnp.float32))


def xent(logits, labels, positions=None):
    """Mean next-token cross-entropy; ``positions`` keeps only the first
    that many positions of each row (a planted fault, never the
    reference)."""
    nll = (jax.nn.logsumexp(logits, axis=-1)
           - jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0])
    if positions is not None:
        nll = nll[..., :positions]
    return jnp.mean(nll)


def leaf_key(key, path: str):
    return jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)


def uniform(key, path, shape, fan_in, dtype):
    s = fan_in ** -0.5
    return jax.random.uniform(leaf_key(key, path), shape, jnp.float32,
                              -s, s).astype(dtype)


def normal(key, path, shape, std, dtype):
    return (jax.random.normal(leaf_key(key, path), shape, jnp.float32)
            * std).astype(dtype)


def padded_vocab(vocab: int) -> int:
    """Rows of the embedding table as the program lays it out."""
    return (vocab + 255) // 256 * 256


def layer_scan(body, x, stacked):
    """Run ``body(x, layer_params)`` over the stacked layers, keeping
    one layer's activations at a time (recomputed in the backward)."""
    def step(h, lp):
        return jax.checkpoint(body)(h, lp), None
    x, _ = jax.lax.scan(step, x, stacked)
    return x
