"""The flat-buffer fused mixing hot path (ISSUE 5).

Three layers pinned here:

* :class:`repro.dist.flat.FlatSpec` — ``unravel ∘ ravel`` is the exact
  identity over mixed-dtype / mixed-shape trees (fixed cases plus
  hypothesis fuzz), offsets are lane-aligned, and lossy layouts are
  rejected loudly;
* the fused mixers — ``fedlay_mix(fuse="flat")`` under ``shard_map`` on
  the real 8-device tier-1 mesh and ``global_mixer(fuse="flat")`` both
  ≡ the tree walk ≡ the dense ``schedule_mixing_matrix`` /
  ``masked_mixing_matrix`` oracles for G ∈ {1, 2, 4}, masked and
  unmasked;
* the control plane — :class:`repro.overlay.OverlayController` with
  ``fuse="flat"``: the MixerCache keys on the fuse mode, and a grouped
  capacity-mode churn loop over the fused mixers holds **zero
  retraces** across ≥ 3 distinct alive counts (the ISSUE 4 pin, now on
  the fused path).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.mixing import (build_permute_schedule, masked_mixing_matrix,
                               schedule_mixing_matrix)
from repro.dist.compat import make_client_mesh, shard_map
from repro.dist.flat import FlatSpec
from repro.dist.sync import check_fuse, fedlay_mix, global_mixer, make_mixer
from repro.kernels.weighted_mix import LANE

GROUPS = (1, 2, 4)
EIGHT_DEVICES = jax.device_count() >= 8


# --------------------------------------------------------------------------
# FlatSpec: the flat-buffer contract
# --------------------------------------------------------------------------

def _mixed_tree(batch=4, seed=0):
    rng = np.random.default_rng(seed)
    return {
        "w": jnp.asarray(rng.normal(size=(batch, 3, 5)).astype(np.float32)),
        "b": jnp.asarray(rng.normal(size=(batch, 7)).astype(np.float32)
                         ).astype(jnp.bfloat16),
        "nest": {"s": jnp.asarray(
            rng.normal(size=(batch,)).astype(np.float16))},
    }


def test_flat_spec_round_trip_exact_mixed_dtypes():
    tree = _mixed_tree()
    spec = FlatSpec.for_tree(tree)
    back = spec.unravel(spec.ravel(tree))
    for got, want in zip(jax.tree.leaves(back), jax.tree.leaves(tree)):
        assert got.dtype == want.dtype
        assert jnp.array_equal(got, want)        # bitwise, not allclose


def test_flat_spec_offsets_lane_aligned():
    tree = _mixed_tree()
    spec = FlatSpec.for_tree(tree)
    assert all(off % LANE == 0 for off in spec.offsets)
    assert spec.size % LANE == 0
    # segments don't overlap and cover in declaration order
    for off, size, nxt in zip(spec.offsets, spec.sizes,
                              spec.offsets[1:] + (spec.size,)):
        assert off + size <= nxt


def test_flat_spec_ravel_shape_and_padding_zeros():
    tree = {"a": jnp.ones((2, 3), jnp.float32)}
    spec = FlatSpec.for_tree(tree)
    buf = spec.ravel(tree)
    assert buf.shape == (2, LANE) and buf.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(buf[:, 3:]), 0.0)


def test_flat_spec_rejects_lossy_or_ragged_layouts():
    with pytest.raises(ValueError, match="losslessly"):
        FlatSpec.for_tree({"x": jnp.zeros((2, 3), jnp.int32)})
    with pytest.raises(ValueError, match="losslessly"):
        FlatSpec.for_tree({"x": jnp.zeros((2, 3), jnp.float32)},
                          dtype=jnp.float16)
    with pytest.raises(ValueError, match="leading batch"):
        FlatSpec.for_tree({"x": jnp.zeros((2, 3)), "y": jnp.zeros((4, 3))})
    with pytest.raises(ValueError, match="empty"):
        FlatSpec.for_tree({})
    spec = FlatSpec.for_tree({"x": jnp.zeros((2, 3))})
    with pytest.raises(ValueError, match="buffer shape"):
        spec.unravel(jnp.zeros((2, 2 * LANE)))


def test_check_fuse_validates():
    assert check_fuse(None) is None
    assert check_fuse("tree") is None
    assert check_fuse("flat") == "flat"
    with pytest.raises(ValueError, match="fuse"):
        check_fuse("nope")
    with pytest.raises(ValueError, match="fuse"):
        global_mixer("fedlay", build_permute_schedule(4, 1), fuse="bogus")


@settings(max_examples=25, deadline=None)
@given(data=st.data(),
       batch=st.integers(min_value=1, max_value=6),
       num_leaves=st.integers(min_value=1, max_value=5),
       seed=st.integers(min_value=0, max_value=10**6))
def test_property_flat_round_trip_identity(data, batch, num_leaves, seed):
    """The tentpole fuzz: ravel ∘ unravel is the exact identity over
    random mixed-dtype / mixed-shape trees."""
    rng = np.random.default_rng(seed)
    tree = {}
    for i in range(num_leaves):
        ndim = data.draw(st.integers(min_value=0, max_value=3),
                         label=f"ndim{i}")
        trailing = tuple(data.draw(st.integers(min_value=1, max_value=7),
                                   label=f"dim{i}_{d}") for d in range(ndim))
        dt = data.draw(st.sampled_from(
            [jnp.float32, jnp.bfloat16, jnp.float16]), label=f"dtype{i}")
        arr = rng.normal(size=(batch,) + trailing).astype(np.float32)
        tree[f"leaf{i}"] = jnp.asarray(arr).astype(dt)
    spec = FlatSpec.for_tree(tree)
    assert spec.size % LANE == 0
    back = spec.unravel(spec.ravel(tree))
    for got, want in zip(jax.tree.leaves(back), jax.tree.leaves(tree)):
        assert got.dtype == want.dtype and jnp.array_equal(got, want)


# --------------------------------------------------------------------------
# Fused mixing ≡ tree walk ≡ dense oracle
# --------------------------------------------------------------------------

def _tree_of(X, n):
    """Split (n, 17) rows into a two-leaf mixed-shape tree."""
    return {"a": jnp.asarray(X[:, :12]).reshape(n, 3, 4),
            "b": jnp.asarray(X[:, 12:])}


def _tree_rows(tree, n):
    return np.concatenate(
        [np.asarray(l, np.float32).reshape(n, -1)
         for l in jax.tree.leaves(tree)], axis=1)


def _mix_on_mesh(sched, X, mask=None, fuse=None, num_devices=8):
    n = sched.num_clients
    mesh = make_client_mesh(num_devices, "data")
    shard = NamedSharding(mesh, P("data"))
    W = jnp.asarray(sched.weights)
    S = jnp.asarray(sched.self_weight)
    tree = _tree_of(X, n)
    if mask is None:
        def body(t, w, s):
            return fedlay_mix(t, sched, w, s, "data", fuse=fuse)
        in_specs = (jax.tree.map(lambda _: P("data"), tree),
                    P("data"), P("data"))
        args = (tree, W, S)
    else:
        def body(t, w, s, m):
            return fedlay_mix(t, sched, w, s, "data", mask=m, fuse=fuse)
        in_specs = (jax.tree.map(lambda _: P("data"), tree),
                    P("data"), P("data"), P("data"))
        args = (tree, W, S, jnp.asarray(mask, jnp.float32))
    f = jax.jit(shard_map(body, mesh=mesh, in_specs=in_specs,
                          out_specs=jax.tree.map(lambda _: P("data"), tree),
                          check_vma=False))
    out = f(*jax.tree.map(lambda a: jax.device_put(a, shard), args))
    return _tree_rows(out, n)


@pytest.mark.multi_device
@pytest.mark.parametrize("G", GROUPS)
@pytest.mark.parametrize("masked", (False, True))
def test_fused_fedlay_mix_equals_tree_and_dense_oracle(G, masked,
                                                       multi_device):
    """The acceptance pin: shard_map fuse="flat" ≡ the tree walk ≡ W·X
    on the real 8-device mesh, G ∈ {1, 2, 4}, masked and unmasked."""
    n = 8 * G
    sched = build_permute_schedule(n, 2, salt=f"fused{G}")
    rng = np.random.default_rng(G)
    X = rng.normal(size=(n, 17)).astype(np.float32)
    mask = None
    Wd = schedule_mixing_matrix(sched)
    if masked:
        mask = (rng.random(n) > 0.4).astype(np.float32)
        mask[0] = 0.0
        Wd = masked_mixing_matrix(sched, mask)
    fused = _mix_on_mesh(sched, X, mask=mask, fuse="flat")
    tree = _mix_on_mesh(sched, X, mask=mask, fuse=None)
    ref = Wd @ X
    np.testing.assert_allclose(fused, ref, atol=1e-6)
    np.testing.assert_allclose(fused, tree, atol=1e-6)


@pytest.mark.parametrize("G", GROUPS)
@pytest.mark.parametrize("masked", (False, True))
def test_fused_global_mixer_equals_dense_oracle(G, masked):
    """Global-view fuse="flat" (one gather_mix kernel per round) ≡ the
    dense oracle, on a mixed-shape tree, G ∈ {1, 2, 4}."""
    n = 8 * G
    sched = build_permute_schedule(n, 2, salt=f"gflat{G}")
    rng = np.random.default_rng(G + 3)
    X = rng.normal(size=(n, 17)).astype(np.float32)
    tree = _tree_of(X, n)
    Wd = schedule_mixing_matrix(sched)
    if masked:
        mask = (rng.random(n) > 0.4).astype(np.float32)
        mask[0] = 0.0
        Wd = masked_mixing_matrix(sched, mask)
        mix = jax.jit(global_mixer("fedlay", sched, masked=True,
                                   fuse="flat", clients_per_device=G))
        out = mix(tree, jnp.asarray(mask))
    else:
        mix = jax.jit(global_mixer("fedlay", sched, fuse="flat",
                                   clients_per_device=G))
        out = mix(tree)
    np.testing.assert_allclose(_tree_rows(out, n), Wd @ X, atol=1e-6)
    # dtypes survive the flat round trip
    assert jax.tree.map(lambda l: l.dtype, out) == \
        jax.tree.map(lambda l: l.dtype, tree)


def test_fused_global_mixer_preserves_bf16_leaves():
    sched = build_permute_schedule(4, 1, salt="bf16")
    rng = np.random.default_rng(0)
    tree = {"w": jnp.asarray(rng.normal(size=(4, 9)).astype(np.float32)
                             ).astype(jnp.bfloat16)}
    out = jax.jit(global_mixer("fedlay", sched, fuse="flat"))(tree)
    assert out["w"].dtype == jnp.bfloat16
    ref = schedule_mixing_matrix(sched) @ np.asarray(
        tree["w"], np.float32)
    np.testing.assert_allclose(np.asarray(out["w"], np.float32), ref,
                               atol=2e-2)


def test_fused_global_mixer_mixed_dtypes_match_one_f32_buffer():
    """Codec-free flat rounds mix each leaf dtype in its own buffer; the
    kernel accumulates in f32 and rounds once, so the result is exactly
    that of one f32 buffer over the whole tree."""
    C = 4
    sched = build_permute_schedule(C, 2, salt="mixed")
    rng = np.random.default_rng(1)
    tree = {"a": jnp.asarray(rng.normal(size=(C, 3, 50)), jnp.bfloat16),
            "b": jnp.asarray(rng.normal(size=(C, 7)), jnp.float32),
            "c": jnp.asarray(rng.normal(size=(C, 130)), jnp.bfloat16)}
    mask = jnp.asarray([1.0, 1.0, 0.0, 1.0])
    out = jax.jit(global_mixer("fedlay", sched, masked=True,
                               fuse="flat"))(tree, mask)
    assert jax.tree.map(lambda l: l.dtype, out) == \
        jax.tree.map(lambda l: l.dtype, tree)
    spec = FlatSpec.for_tree(tree)              # one f32 buffer
    one_buffer = global_mixer("fedlay", sched, masked=True, fuse="flat",
                              flat_io=True)
    ref = spec.unravel(jax.jit(one_buffer)(spec.ravel(tree), mask))
    for k in tree:
        np.testing.assert_array_equal(np.asarray(out[k], np.float32),
                                      np.asarray(ref[k], np.float32))


@pytest.mark.multi_device
@pytest.mark.parametrize("strategy", ("fedlay", "ring"))
def test_fused_make_mixer_equals_unfused(strategy, multi_device):
    """make_mixer(fuse="flat") ≡ make_mixer(fuse=None) for both
    schedule-driven strategies on the real mesh (G = 2)."""
    G, n = 2, 16
    sched = build_permute_schedule(n, 2, salt="mm")
    mesh = make_client_mesh(8, "data")
    shard = NamedSharding(mesh, P("data"))
    rng = np.random.default_rng(7)
    X = jnp.asarray(rng.normal(size=(n, 11)).astype(np.float32))
    W = jnp.asarray(sched.weights)
    S = jnp.asarray(sched.self_weight)
    outs = []
    for fuse in (None, "flat"):
        mixer = make_mixer(strategy, sched, "data", n,
                           clients_per_device=G, fuse=fuse)

        def body(x, w, s):
            return mixer({"m": x}, w, s)["m"]

        f = jax.jit(shard_map(body, mesh=mesh, in_specs=(P("data"),) * 3,
                              out_specs=P("data"), check_vma=False))
        outs.append(np.asarray(f(*[jax.device_put(a, shard)
                                   for a in (X, W, S)])))
    np.testing.assert_allclose(outs[0], outs[1], atol=1e-6)


@pytest.mark.multi_device
@pytest.mark.skipif(not EIGHT_DEVICES, reason="needs 8 host devices")
@settings(max_examples=8, deadline=None)
@given(G=st.sampled_from(GROUPS),
       salt=st.integers(min_value=0, max_value=10**6))
def test_property_fused_fedlay_mix_vs_dense(G, salt):
    """Fuzzed sibling of the fixed-seed fused parity pin."""
    n = 8 * G
    sched = build_permute_schedule(n, 2, salt=f"pf{salt}")
    rng = np.random.default_rng(salt)
    X = rng.normal(size=(n, 17)).astype(np.float32)
    mask = (rng.random(n) > 0.35).astype(np.float32)
    out = _mix_on_mesh(sched, X, mask=mask, fuse="flat")
    ref = masked_mixing_matrix(sched, mask) @ X
    np.testing.assert_allclose(out, ref, atol=1e-6)


# --------------------------------------------------------------------------
# Control plane: fuse-keyed cache + zero-retrace churn on the fused path
# --------------------------------------------------------------------------

def _make_sim(n=6, L=2, seed=0):
    from repro.core.ndmp import Simulator
    sim = Simulator(num_spaces=L, latency=0.05, heartbeat_period=0.5,
                    probe_period=1.0, seed=seed)
    sim.seed_network(list(range(n)))
    return sim


def test_mixer_cache_keys_on_fuse_mode():
    from repro.overlay.controller import MixerCache
    built = []

    def factory(sched):
        built.append(sched)
        return lambda p: p

    cache = MixerCache(factory)
    sched = build_permute_schedule(4, 1)
    _, hit0 = cache.get(sched, None)
    _, hit1 = cache.get(sched, "flat")      # same schedule, other mode
    _, hit2 = cache.get(sched, "flat")
    assert (hit0, hit1, hit2) == (False, False, True)
    assert len(built) == 2 and len(cache) == 2


def test_controller_fuse_flat_mixers_match_unfused():
    """Two controllers over the same seed network, fused vs unfused
    global mixers: identical mixed params."""
    from repro.overlay import OverlayController
    rng = np.random.default_rng(0)
    X = jnp.asarray(rng.normal(size=(5, 2, 3)).astype(np.float32))
    outs = [np.asarray(OverlayController(_make_sim(n=5, seed=3),
                                         fuse=fuse).mixer({"w": X})["w"])
            for fuse in (None, "flat")]
    np.testing.assert_allclose(outs[0], outs[1], atol=1e-6)


def test_controller_rejects_bad_fuse():
    from repro.overlay import OverlayController
    with pytest.raises(ValueError, match="fuse"):
        OverlayController(_make_sim(), fuse="bogus")


@pytest.mark.multi_device
def test_grouped_fused_slot_loop_zero_retrace(multi_device):
    """The ISSUE 5 churn pin: the ISSUE 4 zero-retrace loop (capacity =
    2 × devices, G = 2, rows sharded over the real 8-device mesh), now
    with fuse="flat" — the fused mask-aware gather_mix mixers hold 0
    retraces across ≥ 3 distinct alive counts."""
    from repro.optim.optimizers import sgd
    from repro.overlay import ChurnTrace, OverlayController
    from repro.runtime import SlotTrainLoop, counting_jit, masked_local_step

    dim = 24

    def make_params(u):
        w = np.random.default_rng(u).normal(size=dim).astype(np.float32)
        return {"w": jnp.asarray(w)}

    def make_batch(node_ids, step):
        rows = [np.random.default_rng(abs(hash((u, step))) % 2**32)
                .normal(size=dim).astype(np.float32) for u in node_ids]
        return {"x": jnp.asarray(np.stack(rows))}

    def base_step(params, opt_state, batch):
        w, x = params["w"], batch["x"]
        loss = jnp.mean((w - x) ** 2, axis=-1)
        return {"w": w - 0.05 * 2.0 * (w - x) / dim}, opt_state, \
            {"loss": loss}

    mesh = make_client_mesh(8, "data")
    ctl = OverlayController(_make_sim(n=12), capacity=16,
                            clients_per_device=2, fuse="flat")
    sjit, scount = counting_jit(masked_local_step(base_step))
    loop = SlotTrainLoop(
        ctl, local_step=sjit, make_params=make_params, optimizer=sgd(0.0),
        make_batch=make_batch, jit_local_step=False, mesh=mesh)
    recs = loop.run(12, trace=ChurnTrace.scripted([
        (2.5, "fail", 1), (4.5, "fail", 3),
        (6.5, "join", 100, 0), (8.5, "join", 101, 0),
    ]))
    assert len({r.num_alive for r in recs}) >= 3
    assert all(np.isfinite(r.loss) for r in recs)
    assert scount.traces == 1 and scount.retraces == 0
    # fail -> rejoin restored a previously-seen padded schedule: the
    # fused mixer came straight out of the fuse-keyed compile cache
    assert ctl.cache.hits > 0
