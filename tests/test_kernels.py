"""Pallas kernel validation: interpret=True kernels vs pure-jnp oracles,
swept over shapes and dtypes (hypothesis for the shape space)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels.ops import flash_decode, ssd_scan, weighted_mix
from repro.kernels.ref import (flash_decode_ref, ssd_scan_ref,
                               weighted_mix_ref)

RNG = np.random.default_rng(0)
TOLS = {jnp.float32: dict(rtol=2e-5, atol=2e-5),
        jnp.bfloat16: dict(rtol=2e-2, atol=2e-2)}


# --------------------------------------------------------------------------
# weighted_mix
# --------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("K,N,bn", [(1, 128, 128), (3, 1000, 256),
                                    (7, 4096, 1024), (13, 65536, 65536),
                                    (5, 131, 128),
                                    # 128 < N < block_n with N % 128 != 0:
                                    # the lane-alignment regression (the
                                    # old min(block_n, N) block was
                                    # TPU-invalid here)
                                    (3, 200, 65536), (5, 300, 512)])
def test_weighted_mix_sweep(K, N, bn, dtype):
    m = jnp.asarray(RNG.normal(size=(K, N)), dtype)
    w = jnp.asarray(RNG.random(K).astype(np.float32))
    w = w / w.sum()
    out = weighted_mix(m, w, block_n=bn, interpret=True)
    ref = weighted_mix_ref(m, w)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **TOLS[dtype])


@settings(max_examples=15, deadline=None)
@given(st.integers(1, 10), st.integers(1, 3000), st.integers(0, 4))
def test_weighted_mix_property(K, N, seed):
    rng = np.random.default_rng(seed)
    m = jnp.asarray(rng.normal(size=(K, N)).astype(np.float32))
    w = jnp.asarray(rng.random(K).astype(np.float32) + 0.01)
    out = weighted_mix(m, w, block_n=512, interpret=True)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(weighted_mix_ref(m, w)),
                               rtol=3e-5, atol=3e-5)


def test_weighted_mix_block_is_always_lane_aligned():
    """Regression: with 128 < N < block_n and N % 128 != 0 the old
    ``min(block_n, N)`` tile was not a lane multiple — TPU-invalid, and
    only passed in interpret mode.  The chosen block must always be a
    multiple of 128 and still tile the padded vector exactly."""
    from repro.kernels.weighted_mix import LANE, aligned_block_n
    for n, block_n in [(200, 65536), (131, 128), (129, 4096), (300, 512),
                       (1000, 300), (65536, 65536), (1, 128), (127, 64)]:
        bn = aligned_block_n(n, block_n)
        assert bn % LANE == 0, (n, block_n, bn)
        assert bn >= LANE
        padded = n + ((-n) % bn)
        assert padded % bn == 0
    # the exact regression shape: N=200 used to pick bn=200
    assert aligned_block_n(200, 65536) == 256


def test_weighted_mix_identity():
    """Self-weight 1, neighbors 0 ⇒ output == own model exactly."""
    m = jnp.asarray(RNG.normal(size=(4, 300)).astype(np.float32))
    w = jnp.asarray([1.0, 0.0, 0.0, 0.0], jnp.float32)
    out = weighted_mix(m, w, block_n=128, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(m[0]), atol=1e-6)


def test_weighted_mix_masked_renormalizes():
    """The masked variant drops masked-out models and renormalizes the
    surviving weights (≡ masked_mixing_matrix row semantics); an
    all-masked stack yields zeros."""
    m = jnp.asarray(RNG.normal(size=(5, 300)).astype(np.float32))
    w = jnp.asarray(RNG.random(5).astype(np.float32) + 0.1)
    mask = jnp.asarray([1, 0, 1, 1, 0], jnp.float32)
    out = weighted_mix(m, w, mask=mask, block_n=128, interpret=True)
    ref = weighted_mix_ref(m, w, mask=mask)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    # surviving effective weights sum to 1: a constant stack is fixed
    const = jnp.ones((5, 256), jnp.float32) * 3.25
    out_c = weighted_mix(const, w, mask=mask, block_n=128, interpret=True)
    np.testing.assert_allclose(np.asarray(out_c), 3.25, rtol=1e-6)
    out0 = weighted_mix(m, w, mask=jnp.zeros(5), block_n=128,
                        interpret=True)
    np.testing.assert_array_equal(np.asarray(out0), 0.0)


def test_mix_accumulate_incremental_equals_stacked():
    """Folding K models one at a time through the incremental entry ==
    the stacked weighted_mix == the jnp oracle."""
    from repro.kernels.ref import mix_accumulate_ref
    from repro.kernels.weighted_mix import mix_accumulate
    K, B, N = 5, 3, 515
    models = jnp.asarray(RNG.normal(size=(K, B, N)).astype(np.float32))
    w = jnp.asarray(RNG.random((K, B)).astype(np.float32))
    acc = mix_accumulate(None, models[0], w[0], block_n=256, interpret=True)
    ref = mix_accumulate_ref(None, models[0], w[0])
    for k in range(1, K):
        acc = mix_accumulate(acc, models[k], w[k], block_n=256,
                             interpret=True)
        ref = mix_accumulate_ref(ref, models[k], w[k])
    np.testing.assert_allclose(np.asarray(acc), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    # per-row parity with the stacked kernel (row 0 of each model)
    stacked = weighted_mix(models[:, 0, :], w[:, 0], block_n=256,
                           interpret=True)
    np.testing.assert_allclose(np.asarray(acc[0]), np.asarray(stacked),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("N,bn", [(1000, 256), (1280, 512)])
def test_gather_mix_equals_dense_product(dtype, N, bn):
    """The whole-round kernel: static source rows + runtime weights ≡
    the dense W·X it encodes — also when the last tile overhangs a
    lane-aligned width (1280 = 2.5 tiles of 512, no padded copy)."""
    from repro.kernels.ref import gather_mix_ref
    from repro.kernels.weighted_mix import gather_mix
    C, K1 = 8, 5
    rng = np.random.default_rng(3)
    buf = jnp.asarray(rng.normal(size=(C, N)), dtype)
    srcs = rng.integers(0, C, size=(C, K1))
    srcs[:, 0] = np.arange(C)                   # self column
    w = jnp.asarray(rng.random((C, K1)).astype(np.float32))
    out = gather_mix(buf, srcs, w, block_n=bn, interpret=True)
    assert out.shape == (C, N)
    assert out.dtype == buf.dtype
    ref = gather_mix_ref(buf, srcs, w)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **TOLS[dtype])
    # dense-matrix cross-check: scatter the (srcs, w) table into (C, C)
    W = np.zeros((C, C))
    for i in range(C):
        for k in range(K1):
            W[i, srcs[i, k]] += float(w[i, k])
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               W @ np.asarray(buf, np.float32),
                               **TOLS[dtype])


LEAF_SHAPES = [(), (7,), (300,), (3, 5), (6, 4, 130), (2, 32, 200),
               (5, 40, 33)]


def _storage_orders(ndim):
    """Row-major, and a TPU-like default layout that stores the client
    dimension (dim 0) inside the array: the minor two dimensions
    swapped, or the client dimension second-minor."""
    orders = [tuple(range(ndim))]
    if ndim >= 2:
        orders.append(tuple(range(ndim - 2)) + (ndim - 1, ndim - 2))
    if ndim >= 3:
        orders.append(tuple(range(1, ndim - 1)) + (0, ndim - 1))
    return orders


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("trailing", LEAF_SHAPES)
@pytest.mark.parametrize("block_bytes", [None, 4096])
def test_gather_mix_leaf_equals_dense_product(monkeypatch, dtype, trailing,
                                              block_bytes):
    """The leaf-native round kernel ≡ the dense W·X, for every storage
    order a default layout may pick and with blocks small enough to tile
    every dimension (4 KiB); dtype and shape preserved."""
    import sys
    from repro.kernels.weighted_mix import gather_mix_leaf
    wm = sys.modules["repro.kernels.weighted_mix"]
    C = 3
    rng = np.random.default_rng(len(trailing))
    x = jnp.asarray(rng.normal(size=(C,) + trailing), dtype)
    W = jnp.asarray(rng.random((C, C)).astype(np.float32))
    ref = np.einsum("ik,k...->i...", np.asarray(W, np.float64),
                    np.asarray(x, np.float64))
    for order in _storage_orders(x.ndim):
        monkeypatch.setattr(wm, "_default_order", lambda s, d, o=order: o)
        out = gather_mix_leaf(x, W, block_bytes=block_bytes,
                              interpret=True)
        assert out.shape == x.shape and out.dtype == x.dtype
        np.testing.assert_allclose(np.asarray(out, np.float32), ref,
                                   **TOLS[dtype])


def test_gather_mix_leaf_matches_gather_mix():
    """Scattered through round_matrix, one (srcs, weights) table mixes a
    2-D leaf alike through both kernels."""
    from repro.kernels.weighted_mix import (gather_mix, gather_mix_leaf,
                                            round_matrix)
    C, K1 = 6, 3
    rng = np.random.default_rng(9)
    buf = jnp.asarray(rng.normal(size=(C, 1280)), jnp.float32)
    srcs = rng.integers(0, C, size=(C, K1))
    w = jnp.asarray(rng.random((C, K1)).astype(np.float32))
    np.testing.assert_allclose(
        np.asarray(gather_mix_leaf(buf, round_matrix(C, srcs, w))),
        np.asarray(gather_mix(buf, srcs, w)), rtol=0, atol=1e-6)
    with pytest.raises(ValueError, match="clients"):
        gather_mix_leaf(buf, jnp.ones((C - 1, C - 1)))


def test_gather_mix_rejects_bad_tables():
    from repro.kernels.weighted_mix import gather_mix
    buf = jnp.ones((4, 256), jnp.float32)
    with pytest.raises(ValueError, match="match"):
        gather_mix(buf, np.zeros((3, 2), np.int64),
                   jnp.ones((3, 2)), interpret=True)
    with pytest.raises(ValueError, match="out of range"):
        gather_mix(buf, np.full((4, 2), 9), jnp.ones((4, 2)),
                   interpret=True)


def test_kernels_auto_interpret_on_cpu():
    """Regression (ISSUE 5): the raw kernel entries must run on CPU
    without callers passing interpret= — the old interpret=False
    default died with 'Only interpret mode is supported on CPU
    backend', so the fused mixing hot path could never reach them."""
    from repro.kernels.interpret import resolve_interpret
    from repro.kernels.weighted_mix import (gather_mix, mix_accumulate,
                                            weighted_mix as raw_mix)
    if jax.default_backend() == "tpu":
        pytest.skip("auto-interpret regression is about non-TPU backends")
    assert resolve_interpret(None) is True
    assert resolve_interpret(False) is False
    m = jnp.asarray(RNG.normal(size=(3, 256)).astype(np.float32))
    w = jnp.asarray([0.5, 0.25, 0.25], jnp.float32)
    # none of these pass interpret= — all must auto-interpret
    np.testing.assert_allclose(
        np.asarray(raw_mix(m, w)), np.asarray(weighted_mix_ref(m, w)),
        rtol=2e-5, atol=2e-5)
    mix_accumulate(None, m, w)
    gather_mix(m, np.zeros((3, 1), np.int64), jnp.ones((3, 1)))
    # and the jit front door still accepts the explicit override
    weighted_mix(m, w, interpret=True)


# --------------------------------------------------------------------------
# flash_decode
# --------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,Hq,Hkv,hd,L,bl,pos", [
    (1, 4, 1, 64, 256, 128, 255),
    (2, 8, 2, 64, 700, 128, 450),      # unaligned L → padding path
    (2, 16, 2, 128, 1024, 512, 100),   # pos masks most of the cache
    (1, 8, 8, 64, 512, 256, 511),      # MHA (G=1)
    (3, 8, 4, 32, 384, 128, 0),        # single valid slot
])
def test_flash_decode_sweep(B, Hq, Hkv, hd, L, bl, pos, dtype):
    q = jnp.asarray(RNG.normal(size=(B, Hq, hd)), dtype)
    kc = jnp.asarray(RNG.normal(size=(B, L, Hkv, hd)), dtype)
    vc = jnp.asarray(RNG.normal(size=(B, L, Hkv, hd)), dtype)
    out = flash_decode(q, kc, vc, pos, block_l=bl, interpret=True)
    ref = flash_decode_ref(q, kc, vc, pos)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **TOLS[dtype])


@settings(max_examples=10, deadline=None)
@given(st.integers(1, 3), st.sampled_from([(4, 2), (8, 2), (4, 4)]),
       st.integers(10, 500), st.integers(0, 5))
def test_flash_decode_property(B, heads, L, seed):
    Hq, Hkv = heads
    hd = 32
    rng = np.random.default_rng(seed)
    pos = int(rng.integers(0, L))
    q = jnp.asarray(rng.normal(size=(B, Hq, hd)).astype(np.float32))
    kc = jnp.asarray(rng.normal(size=(B, L, Hkv, hd)).astype(np.float32))
    vc = jnp.asarray(rng.normal(size=(B, L, Hkv, hd)).astype(np.float32))
    out = flash_decode(q, kc, vc, pos, block_l=128, interpret=True)
    ref = flash_decode_ref(q, kc, vc, pos)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=3e-5, atol=3e-5)


def test_flash_decode_matches_model_cache_attention():
    """Kernel ≡ the model's cache_attention (the serving integration)."""
    from repro.models.attention import cache_attention
    B, Hq, Hkv, hd, L = 2, 8, 2, 64, 333
    q = jnp.asarray(RNG.normal(size=(B, 1, Hq, hd)).astype(np.float32))
    kc = jnp.asarray(RNG.normal(size=(B, L, Hkv, hd)).astype(np.float32))
    vc = jnp.asarray(RNG.normal(size=(B, L, Hkv, hd)).astype(np.float32))
    pos = 200
    ref = cache_attention(q, kc, vc, pos)
    out = flash_decode(q[:, 0], kc, vc, pos, block_l=128, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref[:, 0]),
                               rtol=3e-5, atol=3e-5)


# --------------------------------------------------------------------------
# ssd_scan
# --------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,S,H,P,N,chunk", [
    (1, 64, 2, 16, 16, 16),
    (2, 128, 3, 16, 32, 32),
    (2, 96, 2, 32, 16, 32),            # S not divisible by chunk → halves
    (1, 256, 4, 64, 128, 64),          # production-ish tile
])
def test_ssd_scan_sweep(B, S, H, P, N, chunk, dtype):
    x = jnp.asarray(RNG.normal(size=(B, S, H, P)), dtype)
    dt = jnp.asarray(np.abs(RNG.normal(size=(B, S, H))) * 0.2, jnp.float32)
    A = -jnp.asarray(np.abs(RNG.normal(size=(H,))).astype(np.float32))
    Bm = jnp.asarray(RNG.normal(size=(B, S, N)), dtype)
    Cm = jnp.asarray(RNG.normal(size=(B, S, N)), dtype)
    out = ssd_scan(x, dt, A, Bm, Cm, chunk=chunk, interpret=True)
    ref = ssd_scan_ref(x.astype(jnp.float32), dt, A,
                       Bm.astype(jnp.float32), Cm.astype(jnp.float32))
    tol = dict(rtol=5e-2, atol=5e-2) if dtype == jnp.bfloat16 else \
        dict(rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **tol)


@settings(max_examples=10, deadline=None)
@given(st.integers(1, 2), st.sampled_from([32, 64, 96]),
       st.integers(1, 3), st.integers(0, 5))
def test_ssd_scan_property(B, S, H, seed):
    rng = np.random.default_rng(seed)
    P, N = 8, 16
    x = jnp.asarray(rng.normal(size=(B, S, H, P)).astype(np.float32))
    dt = jnp.asarray(np.abs(rng.normal(size=(B, S, H))).astype(np.float32) * 0.3)
    A = -jnp.asarray(np.abs(rng.normal(size=(H,))).astype(np.float32))
    Bm = jnp.asarray(rng.normal(size=(B, S, N)).astype(np.float32))
    Cm = jnp.asarray(rng.normal(size=(B, S, N)).astype(np.float32))
    out = ssd_scan(x, dt, A, Bm, Cm, chunk=32, interpret=True)
    ref = ssd_scan_ref(x, dt, A, Bm, Cm)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=3e-4, atol=3e-4)


def test_ssd_scan_chunk_invariance():
    """Different chunk sizes must give identical results."""
    B, S, H, P, N = 1, 128, 2, 16, 16
    x = jnp.asarray(RNG.normal(size=(B, S, H, P)).astype(np.float32))
    dt = jnp.asarray(np.abs(RNG.normal(size=(B, S, H))).astype(np.float32) * 0.2)
    A = -jnp.asarray(np.abs(RNG.normal(size=(H,))).astype(np.float32))
    Bm = jnp.asarray(RNG.normal(size=(B, S, N)).astype(np.float32))
    Cm = jnp.asarray(RNG.normal(size=(B, S, N)).astype(np.float32))
    o16 = ssd_scan(x, dt, A, Bm, Cm, chunk=16, interpret=True)
    o64 = ssd_scan(x, dt, A, Bm, Cm, chunk=64, interpret=True)
    np.testing.assert_allclose(np.asarray(o16), np.asarray(o64),
                               rtol=1e-4, atol=1e-4)
