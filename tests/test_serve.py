"""Serving-plane tests (ISSUE 9): the per-slot position vector through
the whole decode stack, batched prefill parity with the stepped decode
path, the three flash_decode/gqa_decode bugfixes, and the continuous-
batching ServeLoop's zero-retrace / isolation guarantees."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_decode import flash_decode, pick_block_l
from repro.kernels.ref import flash_decode_ref
from repro.launch.train import tiny_lm
from repro.models import decode_step, init_cache, init_params, prefill
from repro.models.attention import cache_attention, gqa_decode, gqa_init
from repro.obs.events import telemetry
from repro.obs.rounds import round_ledger
from repro.runtime.serving import ServeLoop


def _rand(rng, *shape):
    return jnp.asarray(rng.standard_normal(shape), jnp.float32)


# --------------------------------------------------------------------------
# Per-slot position vector through the kernel and its oracle
# --------------------------------------------------------------------------

@pytest.mark.parametrize("L", [64, 130, 160, 512, 700])
def test_flash_decode_pos_vector_parity(L):
    """flash_decode with a per-slot (B,) pos vector (mixed live, empty,
    boundary rows) equals both the pure-jnp cache_attention oracle and
    flash_decode_ref within 1e-5 — including odd/small L that exercise
    the lane-aligned block fix."""
    rng = np.random.default_rng(L)
    B, Hq, Hkv, hd = 5, 8, 2, 32
    q, k, v = (_rand(rng, B, Hq, hd), _rand(rng, B, L, Hkv, hd),
               _rand(rng, B, L, Hkv, hd))
    pos = jnp.asarray([0, L // 2, L - 1, -1, 3], jnp.int32)
    out = flash_decode(q, k, v, pos, interpret=True)
    ref = flash_decode_ref(q, k, v, pos)
    oracle = cache_attention(q[:, None], k, v, pos)[:, 0]
    assert float(jnp.abs(out - ref).max()) <= 1e-5
    assert float(jnp.abs(out - oracle).max()) <= 1e-5


def test_flash_decode_scalar_pos_still_works():
    rng = np.random.default_rng(0)
    B, Hq, Hkv, hd, L = 2, 4, 2, 16, 96
    q, k, v = (_rand(rng, B, Hq, hd), _rand(rng, B, L, Hkv, hd),
               _rand(rng, B, L, Hkv, hd))
    out = flash_decode(q, k, v, 7, interpret=True)
    ref = flash_decode_ref(q, k, v, 7)
    assert float(jnp.abs(out - ref).max()) <= 1e-5


def test_flash_decode_empty_slot_exactly_zero():
    """pos < 0 marks an empty serving slot: the output row must be
    EXACTLY zero (masked online softmax), not small-but-garbage — a
    bare exp(s - m) on an all-masked row would yield uniform weights."""
    rng = np.random.default_rng(1)
    B, Hq, Hkv, hd, L = 3, 4, 2, 16, 160
    q, k, v = (_rand(rng, B, Hq, hd), _rand(rng, B, L, Hkv, hd),
               _rand(rng, B, L, Hkv, hd))
    pos = jnp.asarray([-1, 5, -1], jnp.int32)
    out = flash_decode(q, k, v, pos, interpret=True)
    oracle = cache_attention(q[:, None], k, v, pos)[:, 0]
    assert float(jnp.abs(out[0]).max()) == 0.0
    assert float(jnp.abs(out[2]).max()) == 0.0
    assert float(jnp.abs(oracle[0]).max()) == 0.0
    assert float(jnp.abs(out[1]).max()) > 0.0


def test_pick_block_l_lane_aligned():
    """Regression for the bl = min(block_l, L) bug: every chosen block
    is a lane (128) multiple (a bare min() handed Pallas a lane-invalid
    block whenever 128 < L < block_l with L % 128 != 0)."""
    expected = {1: 128, 100: 128, 129: 256, 160: 256, 300: 384,
                511: 512, 512: 512, 513: 512, 4096: 512}
    for L, want in expected.items():
        bl = pick_block_l(L, 512)
        assert bl == want, (L, bl)
        assert bl % 128 == 0


def test_flash_decode_rejects_ragged_gqa():
    rng = np.random.default_rng(2)
    q, k, v = (_rand(rng, 2, 7, 16), _rand(rng, 2, 64, 2, 16),
               _rand(rng, 2, 64, 2, 16))
    with pytest.raises(ValueError, match="integer multiple"):
        flash_decode(q, k, v, 3, interpret=True)
    q8 = _rand(rng, 2, 8, 16)
    with pytest.raises(ValueError, match="per-slot vector"):
        flash_decode(q8, k, v, jnp.zeros((3,), jnp.int32), interpret=True)


# --------------------------------------------------------------------------
# gqa_decode overflow + per-slot writes
# --------------------------------------------------------------------------

def _gqa_setup(rng, B=2, L=8):
    p = gqa_init(jax.random.PRNGKey(0), 32, 4, 2, 8)
    x = _rand(rng, B, 1, 32)
    cache = {"k": jnp.zeros((B, L, 2, 8)), "v": jnp.zeros((B, L, 2, 8))}
    kw = dict(num_heads=4, num_kv_heads=2, head_dim=8, rope_theta=1e4)
    return p, x, cache, kw


def test_gqa_decode_overflow_raises():
    """Concrete pos >= cache_len with no window must raise instead of
    silently clamping onto the last slot (the old wrong-answer bug)."""
    rng = np.random.default_rng(3)
    p, x, cache, kw = _gqa_setup(rng, L=8)
    with pytest.raises(ValueError, match="overflows"):
        gqa_decode(p, x, cache, 8, **kw)
    with pytest.raises(ValueError, match="overflows"):
        gqa_decode(p, x, cache, jnp.asarray([3, 8]), **kw)
    # the windowed path is the ring buffer: same pos must NOT raise
    out, _ = gqa_decode(p, x, cache, 8, window=8, **kw)
    assert out.shape == (2, 1, 32)
    # in-range per-slot vector is fine; the empty row's output is zero
    out, new = gqa_decode(p, x, cache, jnp.asarray([3, -1]), **kw)
    assert float(jnp.abs(out[1]).max()) == 0.0
    assert new["k"].shape == cache["k"].shape


def test_gqa_decode_vector_matches_scalar():
    """A uniform (B,) pos vector must reproduce the scalar-pos path
    bit-for-bit (same writes, same validity)."""
    rng = np.random.default_rng(4)
    p, x, cache, kw = _gqa_setup(rng, L=8)
    o1, c1 = gqa_decode(p, x, cache, 2, **kw)
    o2, c2 = gqa_decode(p, x, cache, jnp.asarray([2, 2]), **kw)
    assert float(jnp.abs(o1 - o2).max()) <= 1e-6
    assert float(jnp.abs(c1["k"] - c2["k"]).max()) == 0.0


# --------------------------------------------------------------------------
# Batched prefill ≡ stepped decode
# --------------------------------------------------------------------------

def _stepped(cfg, params, cache, toks):
    logits = None
    for t in range(toks.shape[1]):
        logits, cache = decode_step(cfg, params, cache, toks[:, t:t + 1])
    return logits, cache


def _parity(cfg, B=2, P=8, cache_len=24, seed=0):
    rng = np.random.default_rng(seed)
    params = init_params(cfg, jax.random.PRNGKey(0))
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, P)), jnp.int32)
    l1, c1 = _stepped(cfg, params, init_cache(cfg, params, B, cache_len), toks)
    l2, c2 = prefill(cfg, params, init_cache(cfg, params, B, cache_len), toks)
    scale = max(1.0, float(jnp.abs(l1).max()))
    assert float(jnp.abs(l1 - l2).max()) / scale < 2e-4
    nxt = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, 1)), jnp.int32)
    g1, _ = decode_step(cfg, params, c1, nxt)
    g2, _ = decode_step(cfg, params, c2, nxt)
    assert float(jnp.abs(g1 - g2).max()) / scale < 2e-4
    assert int(c2["pos"]) == P if jnp.ndim(c2["pos"]) == 0 else True


def test_prefill_parity_dense():
    _parity(tiny_lm(layers=2))


def test_prefill_parity_sliding_window():
    cfg = dataclasses.replace(tiny_lm(layers=2), sliding_window=4)
    _parity(cfg, P=8)       # prompt longer than the window → ring prefill


def test_prefill_parity_ssm():
    from repro.configs import REGISTRY, reduce_for_smoke
    _parity(reduce_for_smoke(REGISTRY["mamba2-370m"]), P=8)


def test_prefill_ragged_lengths():
    """Padded ragged prefill: each row's last-valid-token logits and
    primed cache must equal a tight (unpadded) prefill of that row."""
    cfg = tiny_lm(layers=2)
    rng = np.random.default_rng(5)
    params = init_params(cfg, jax.random.PRNGKey(0))
    P, cache_len = 8, 24
    lens = [3, 8, 5]
    toks = np.zeros((3, P), np.int32)
    for b, ln in enumerate(lens):
        toks[b, :ln] = rng.integers(0, cfg.vocab_size, ln)
    cache = init_cache(cfg, params, 3, cache_len, per_slot_pos=True)
    logits, cache = prefill(cfg, params, cache, jnp.asarray(toks),
                            lengths=jnp.asarray(lens))
    assert list(np.asarray(cache["pos"])) == lens
    for b, ln in enumerate(lens):
        solo_cache = init_cache(cfg, params, 1, cache_len)
        solo, _ = prefill(cfg, params, solo_cache,
                          jnp.asarray(toks[b:b + 1, :ln]))
        scale = max(1.0, float(jnp.abs(solo).max()))
        assert float(jnp.abs(solo[0] - logits[b]).max()) / scale < 2e-4


def test_prefill_ragged_rejects_ssm_and_scalar_cache():
    from repro.configs import REGISTRY, reduce_for_smoke
    cfg = tiny_lm(layers=2)
    params = init_params(cfg, jax.random.PRNGKey(0))
    toks = jnp.zeros((2, 4), jnp.int32)
    with pytest.raises(ValueError, match="per-slot pos"):
        prefill(cfg, params, init_cache(cfg, params, 2, 16), toks,
                lengths=jnp.asarray([2, 4]))
    ssm_cfg = reduce_for_smoke(REGISTRY["mamba2-370m"])
    ssm_params = init_params(ssm_cfg, jax.random.PRNGKey(0))
    ssm_cache = init_cache(ssm_cfg, ssm_params, 2, 16, per_slot_pos=True)
    with pytest.raises(ValueError, match="SSM"):
        prefill(ssm_cfg, ssm_params, ssm_cache, toks,
                lengths=jnp.asarray([2, 4]))


def test_prefill_overflow_raises():
    cfg = tiny_lm(layers=2)
    params = init_params(cfg, jax.random.PRNGKey(0))
    cache = init_cache(cfg, params, 1, 4)
    with pytest.raises(ValueError, match="overflows"):
        prefill(cfg, params, cache, jnp.zeros((1, 8), jnp.int32))


def test_decode_step_empty_slots_frozen():
    """Vector-pos decode: empty slots (pos = -1) never advance."""
    cfg = tiny_lm(layers=2)
    params = init_params(cfg, jax.random.PRNGKey(0))
    cache = init_cache(cfg, params, 3, 16, per_slot_pos=True)
    cache["pos"] = jnp.asarray([2, -1, 5], jnp.int32)
    _, new = decode_step(cfg, params, cache, jnp.zeros((3, 1), jnp.int32))
    assert list(np.asarray(new["pos"])) == [3, -1, 6]


# --------------------------------------------------------------------------
# The continuous-batching serving loop
# --------------------------------------------------------------------------

CFG = tiny_lm(layers=2)
PARAMS = init_params(CFG, jax.random.PRNGKey(0))


def _loop(policy="continuous", capacity=3):
    return ServeLoop(CFG, PARAMS, capacity=capacity, cache_len=24,
                     prompt_len=8, policy=policy)


def test_serve_cache_takes_the_params_dtype():
    """A bf16 model keeps a bf16 KV cache (half the HBM of f32), and
    serves tokens inside the vocabulary from it."""
    params = init_params(CFG, jax.random.PRNGKey(0), dtype=jnp.bfloat16)
    loop = ServeLoop(CFG, params, capacity=2, cache_len=24, prompt_len=8)
    kv = [l for l in jax.tree.leaves(loop.cache)
          if jnp.issubdtype(l.dtype, jnp.floating)]
    assert kv and all(l.dtype == jnp.bfloat16 for l in kv)
    req = loop.submit(np.arange(1, 6), max_new=3)
    loop.run()
    assert len(req.tokens) == 3
    assert all(0 <= t < CFG.vocab_size for t in req.tokens)


def test_serve_rewrites_the_cache_in_place():
    """Admission, decode and retirement donate the KV cache: each step's
    input cache is consumed, so one copy of it is ever live."""
    loop = _loop(capacity=2)
    req = loop.submit(np.arange(1, 6), max_new=3)
    ticks = 0
    while not req.done:
        before = jax.tree.leaves(loop.cache)
        loop.tick()
        ticks += 1
        assert all(l.is_deleted() for l in before)
    assert ticks == 2 and len(req.tokens) == 3


def test_serve_churn_zero_retraces():
    """Request churn across >= 3 distinct occupancy counts compiles
    exactly one trace per step function — 0 retraces after warmup."""
    rng = np.random.default_rng(6)
    with telemetry() as bus, round_ledger() as ledger:
        loop = _loop()
        loop.submit(rng.integers(0, CFG.vocab_size, 4), max_new=2)
        loop.run()                      # warmup: all four steps traced
        warm = loop.traces
        occup = set()
        for _ in range(8):
            loop.submit(rng.integers(0, CFG.vocab_size,
                                     int(rng.integers(1, 9))),
                        max_new=int(rng.integers(2, 7)))
        while loop.pending or loop.active:
            loop.tick()
            occup.add(len(loop.slots))
        assert len(occup & {1, 2, 3}) >= 3 or len(occup) >= 3
        assert loop.traces == warm      # ZERO retraces across churn
        assert loop.retraces == 0
        assert bus.counters["serve.completed"] == 9
        assert "serve.tick.ms" in bus.histograms
        assert len(ledger.rows) > 0     # one RoundRecord per tick
        assert all(r.loop == "serve" for r in ledger.rows)
        assert all(r.retraces == 0 for r in ledger.rows)


def test_serve_continuous_matches_solo():
    """Batching must not change anyone's tokens: every request served
    in a churning continuous batch produces exactly the greedy tokens
    it gets when served alone — the per-slot pos correctness pin."""
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, CFG.vocab_size, int(rng.integers(2, 9)))
               for _ in range(6)]
    gens = [int(rng.integers(2, 7)) for _ in range(6)]

    loop = _loop()
    for p, g in zip(prompts, gens):
        loop.submit(p, max_new=g)
    loop.run()
    batched = {r.rid: r.tokens for r in loop.completed}

    solo_loop = _loop(capacity=1)
    for i, (p, g) in enumerate(zip(prompts, gens)):
        solo_loop.submit(p, max_new=g)
    solo_loop.run()
    solo = {r.rid: r.tokens for r in solo_loop.completed}
    assert batched == solo
    assert all(len(batched[i]) == gens[i] for i in range(6))


def test_serve_static_policy_never_mixes_batches():
    """Static policy: admissions only ever happen into an empty batch
    (the baseline semantics serve_load measures against), and outputs
    still match the solo run."""
    rng = np.random.default_rng(8)
    with round_ledger() as ledger:
        loop = _loop(policy="static")
        for _ in range(5):
            loop.submit(rng.integers(0, CFG.vocab_size, 4),
                        max_new=int(rng.integers(2, 6)))
        loop.run()
    for row in ledger.rows:
        admitted = row.extra.get("admitted", 0)
        # an admission tick started from an empty batch: alive after the
        # tick can only be what was admitted (minus same-tick retires)
        if admitted:
            assert row.num_alive <= admitted
    assert len(loop.completed) == 5


def test_serve_forced_retirement_on_cache_overflow():
    """A generation that would overflow cache_len is force-retired by
    the host-side guard instead of silently wrapping the prefix cache."""
    loop = ServeLoop(CFG, PARAMS, capacity=1, cache_len=10, prompt_len=8)
    req = loop.submit(np.arange(8) % CFG.vocab_size, max_new=50)
    loop.run()
    # prompt fills pos 0..7; decode may write pos 8 and 9 only
    assert req.done and len(req.tokens) <= 3


def test_serve_hot_reload_from_flat_buffer():
    """Model hot-swap straight from the training loop's FlatSpec flat
    buffer: same treedef in, zero retraces, and the identical row
    reproduces the exact pre-reload tokens."""
    from repro.dist.flat import FlatSpec
    prompt = np.arange(6) % CFG.vocab_size
    tree = jax.tree.map(lambda l: jnp.stack([l, l * 2.0]), PARAMS)
    spec = FlatSpec.for_tree(tree)
    buf = spec.ravel(tree)

    loop = _loop(capacity=2)
    loop.submit(prompt, max_new=4)
    loop.run()
    base = loop.completed[-1].tokens
    t0 = loop.traces
    loop.reload_from_flat(buf, spec, row=0)
    swapped = loop.params
    same_leaf = jax.tree.leaves(swapped)[0]
    assert float(jnp.abs(same_leaf - jax.tree.leaves(PARAMS)[0]).max()) == 0.0
    loop.submit(prompt, max_new=4)
    loop.run()
    assert loop.completed[-1].tokens == base
    loop.reload_from_flat(buf, spec, row=1)
    doubled_leaf = jax.tree.leaves(loop.params)[0]
    assert float(jnp.abs(doubled_leaf - 2.0 *
                         jax.tree.leaves(PARAMS)[0]).max()) == 0.0
    loop.submit(prompt, max_new=4)
    loop.run()
    assert loop.traces == t0            # reloads never retrace


def test_serve_rejects_bad_configs():
    with pytest.raises(ValueError, match="policy"):
        ServeLoop(CFG, PARAMS, capacity=2, cache_len=16, prompt_len=8,
                  policy="adaptive")
    with pytest.raises(ValueError, match="prompt_len"):
        ServeLoop(CFG, PARAMS, capacity=2, cache_len=8, prompt_len=16)
    from repro.configs import REGISTRY, reduce_for_smoke
    ssm_cfg = reduce_for_smoke(REGISTRY["mamba2-370m"])
    ssm_params = init_params(ssm_cfg, jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="SSM"):
        ServeLoop(ssm_cfg, ssm_params, capacity=2, cache_len=16,
                  prompt_len=8)
    loop = _loop()
    with pytest.raises(ValueError, match="prompt length"):
        loop.submit(np.zeros(9, np.int32))


def test_serve_deadline_eviction_frees_slot():
    """ISSUE 10 satellite: a request past its per-slot tick budget is
    force-retired with ``evicted=True`` and a ``serve.evictions``
    counter, and its slot frees the same tick — a stuck generation can
    never wedge the batch."""
    with telemetry() as bus, round_ledger() as ledger:
        loop = _loop(capacity=1)
        doomed = loop.submit(np.arange(4) % CFG.vocab_size, max_new=50,
                             max_ticks=2)
        ok = loop.submit(np.arange(4) % CFG.vocab_size, max_new=3)
        loop.run()
    assert doomed.evicted
    assert len(doomed.tokens) <= 3          # admit + 2 decode ticks max
    # the evicted slot was reclaimed: the queued request still completes
    assert not ok.evicted and len(ok.tokens) == 3
    assert bus.counters["serve.evictions"] == 1
    assert sum(r.extra.get("evicted", 0) for r in ledger.rows) == 1


def test_serve_wall_deadline_eviction():
    loop = _loop(capacity=2)
    req = loop.submit(np.arange(4) % CFG.vocab_size, max_new=50,
                      deadline_s=0.0)       # already expired on arrival
    loop.run()
    assert req.evicted and len(req.tokens) <= 2


def test_serve_rejects_bad_max_ticks():
    loop = _loop()
    with pytest.raises(ValueError, match="max_ticks"):
        loop.submit(np.arange(4) % CFG.vocab_size, max_ticks=0)
