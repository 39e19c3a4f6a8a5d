"""repro.runtime — the fixed-capacity slot runtime.

Acceptance pins (ISSUE 3): the slot loop's jitted local step traces
exactly once (zero retraces) over a churn trace with >= 3 distinct
alive counts, while the re-stack loop traces once per distinct count;
and SlotTrainLoop losses match ChurnTrainLoop on the same scripted
trace to fp tolerance.  Plus coverage for SlotMap planning, schedule
padding, mask-aware mixing (vs the dense oracle, including the
shard_map path on 8 host devices), masked local steps, on-device
multirate participation, capacity-mode + double-buffered controllers,
and the Fig.-18 donor-copy / fresh-init joiner paths.

ISSUE 4 additions: the grouped (clients_per_device = G > 1) churn path —
OverlayController capacity mode at capacity C = G × devices driving a
SlotTrainLoop whose capacity axis is sharded over the real 8-device
mesh — pins 0 retraces across ≥ 3 distinct alive counts.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.mixing import (build_permute_schedule, masked_mixing_matrix,
                               multirate_participation, pad_schedule,
                               participation_mults, schedule_mixing_matrix)
from repro.core.ndmp import Simulator
from repro.overlay import (ChurnTrace, ChurnTrainLoop, OverlayController,
                           joiner_donors)
from repro.runtime import (SlotCapacityError, SlotMap, SlotTrainLoop,
                           counting_jit, masked_local_step, masked_mean,
                           pad_to_capacity, participation_mask)


def make_sim(n=6, L=2, seed=0):
    sim = Simulator(num_spaces=L, latency=0.05, heartbeat_period=0.5,
                    probe_period=1.0, seed=seed)
    sim.seed_network(list(range(n)))
    return sim


# --------------------------------------------------------------------------
# SlotMap
# --------------------------------------------------------------------------

def test_slot_map_allocates_lowest_free_slot():
    sm = SlotMap(4, initial=(10, 11))
    assert sm.slot_of == {10: 0, 11: 1}
    sm.free(10)
    assert 10 not in sm and len(sm) == 1
    assert sm.alloc(12) == 0            # freed slot reused, lowest first
    assert sm.alloc(13) == 2
    assert sm.nodes() == (12, 11, 13)   # slot order
    with pytest.raises(ValueError, match="already holds"):
        sm.alloc(13)
    sm.alloc(14)
    with pytest.raises(SlotCapacityError):
        sm.alloc(15)
    with pytest.raises(KeyError):
        sm.free(99)


def test_slot_map_plan_is_pure_and_identity_preserving():
    sm = SlotMap(6, initial=(1, 2, 3, 4))
    plan = sm.plan((2, 3, 5, 6))        # 1,4 leave; 5,6 join
    assert dict(plan.survivors) == {2: 1, 3: 2}
    assert dict(plan.leavers) == {1: 0, 4: 3}
    assert dict(plan.joiners) == {5: 0, 6: 3}   # lowest freed slots
    assert plan.changed
    # pure: nothing moved yet
    assert sm.slot_of == {1: 0, 2: 1, 3: 2, 4: 3}
    sm.apply(plan)
    assert sm.slot_of == {2: 1, 3: 2, 5: 0, 6: 3}
    np.testing.assert_array_equal(sm.alive_mask(),
                                  [1, 1, 1, 1, 0, 0])
    # no-op plan
    plan2 = sm.plan((2, 3, 5, 6))
    assert not plan2.changed and plan2.slot_of == sm.slot_of


def test_slot_map_plan_overflow_raises():
    sm = SlotMap(3, initial=(0, 1, 2))
    with pytest.raises(SlotCapacityError):
        sm.plan((0, 1, 2, 3))
    with pytest.raises(ValueError, match="duplicate"):
        sm.plan((0, 0, 1))


# --------------------------------------------------------------------------
# Capacity padding + mask-aware mixing
# --------------------------------------------------------------------------

def test_pad_schedule_dense_equivalence_and_dead_self_loops():
    sched = build_permute_schedule(5, 2)
    slots = (0, 2, 3, 5, 6)
    padded = pad_schedule(sched, slots, 8)
    assert padded.num_clients == 8
    Wp = schedule_mixing_matrix(padded)
    W = schedule_mixing_matrix(sched)
    idx = np.asarray(slots)
    np.testing.assert_allclose(Wp[np.ix_(idx, idx)], W, atol=1e-7)
    np.testing.assert_allclose(Wp.sum(axis=1), 1.0, atol=1e-6)
    for dead in (1, 4, 7):
        expect = np.zeros(8)
        expect[dead] = 1.0                  # self-loop with weight 1
        np.testing.assert_allclose(Wp[dead], expect)
        assert all(p[dead] == dead for p in padded.perms)


def test_pad_schedule_rejects_bad_assignments():
    sched = build_permute_schedule(4, 2)
    with pytest.raises(ValueError, match="one slot per"):
        pad_schedule(sched, (0, 1, 2), 8)
    with pytest.raises(ValueError, match="duplicate"):
        pad_schedule(sched, (0, 1, 1, 2), 8)
    with pytest.raises(ValueError, match="out of range"):
        pad_schedule(sched, (0, 1, 2, 8), 8)


def test_pad_to_capacity_uses_sorted_alive_order():
    sm = SlotMap(6, initial=(7, 3, 9))      # slots: 7->0, 3->1, 9->2
    sched = build_permute_schedule(3, 2)    # alive order sorted: 3,7,9
    padded = pad_to_capacity(sched, sm)
    W = schedule_mixing_matrix(sched)
    Wp = schedule_mixing_matrix(padded)
    idx = np.asarray([sm.slot_of[u] for u in (3, 7, 9)])
    np.testing.assert_allclose(Wp[np.ix_(idx, idx)], W, atol=1e-7)


def test_masked_global_mixer_matches_dense_oracle():
    from repro.dist.sync import global_mixer
    sched = build_permute_schedule(8, 2)
    mix = jax.jit(global_mixer("fedlay", sched, masked=True))
    rng = np.random.default_rng(0)
    X = jnp.asarray(rng.normal(size=(8, 17)).astype(np.float32))
    mask = np.asarray([1, 1, 0, 1, 0, 1, 1, 1], np.float32)
    out = np.asarray(mix(X, jnp.asarray(mask)))
    ref = masked_mixing_matrix(sched, mask) @ np.asarray(X)
    np.testing.assert_allclose(out, ref, atol=1e-5)
    # masked-out rows pass through untouched
    np.testing.assert_array_equal(out[2], np.asarray(X)[2])
    # all-ones mask degenerates to the unmasked mixer
    ones = jnp.ones((8,), jnp.float32)
    ref_plain = np.asarray(global_mixer("fedlay", sched)(X))
    np.testing.assert_allclose(np.asarray(mix(X, ones)), ref_plain,
                               atol=1e-6)


def test_masked_allreduce_mixer_means_live_rows_only():
    from repro.dist.sync import global_mixer
    mix = global_mixer("allreduce", masked=True)
    X = jnp.asarray(np.arange(12, dtype=np.float32).reshape(4, 3))
    mask = jnp.asarray([1.0, 0.0, 1.0, 0.0])
    out = np.asarray(mix({"w": X}, mask)["w"])
    live_mean = np.asarray(X)[[0, 2]].mean(axis=0)
    np.testing.assert_allclose(out[0], live_mean, atol=1e-6)
    np.testing.assert_allclose(out[2], live_mean, atol=1e-6)
    np.testing.assert_array_equal(out[1], np.asarray(X)[1])  # untouched
    np.testing.assert_array_equal(out[3], np.asarray(X)[3])


@pytest.mark.multi_device
def test_masked_fedlay_mix_shard_map_matches_dense_oracle(multi_device):
    """Mask-aware ppermute mixing on 8 host devices ≡ the dense oracle —
    inline on the tier-1 forced host mesh (used to be a subprocess)."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.dist.compat import make_client_mesh, shard_map
    from repro.dist.sync import fedlay_mix

    n, dim = 8, 24
    mesh = make_client_mesh(n, "data")
    sched = build_permute_schedule(n, 2)
    rng = np.random.default_rng(0)
    X = jnp.asarray(rng.normal(size=(n, dim)).astype(np.float32))
    mask = np.asarray([1, 0, 1, 1, 1, 0, 1, 1], np.float32)
    W = jnp.asarray(sched.weights)
    S = jnp.asarray(sched.self_weight)

    def body(x, w, s, m):
        return fedlay_mix({"m": x}, sched, w, s, "data", mask=m)["m"]

    f = jax.jit(shard_map(body, mesh=mesh,
                          in_specs=(P("data"), P("data"), P("data"),
                                    P("data")),
                          out_specs=P("data"), check_vma=False))
    shard = NamedSharding(mesh, P("data"))
    out = f(jax.device_put(X, shard), jax.device_put(W, shard),
            jax.device_put(S, shard),
            jax.device_put(jnp.asarray(mask), shard))
    ref = masked_mixing_matrix(sched, mask) @ np.asarray(X)
    assert float(np.abs(np.asarray(out) - ref).max()) < 1e-5


# --------------------------------------------------------------------------
# Masked local step + participation
# --------------------------------------------------------------------------

def test_masked_local_step_freezes_dead_rows_and_contains_nan():
    def step(params, opt_state, batch):
        w = params["w"] + batch["x"]
        loss = jnp.mean(w, axis=-1)
        return {"w": w}, opt_state, {"loss": loss}

    params = {"w": jnp.ones((4, 3))}
    batch = {"x": jnp.asarray(
        np.stack([np.full(3, 1.0), np.full(3, np.nan),
                  np.full(3, 2.0), np.full(3, np.nan)]),
        jnp.float32)}
    mask = jnp.asarray([1.0, 0.0, 1.0, 0.0])
    mstep = jax.jit(masked_local_step(step))
    new_p, _, metrics = mstep(params, (), batch, mask)
    out = np.asarray(new_p["w"])
    np.testing.assert_allclose(out[0], 2.0)      # live: updated
    np.testing.assert_allclose(out[2], 3.0)
    np.testing.assert_allclose(out[1], 1.0)      # dead: frozen, NaN blocked
    np.testing.assert_allclose(out[3], 1.0)
    loss = float(np.asarray(metrics["loss"]))
    assert np.isfinite(loss)
    assert loss == pytest.approx((2.0 + 3.0) / 2)


def test_masked_mean_matches_numpy_oracle():
    v = jnp.asarray([1.0, 2.0, 3.0, 4.0])
    m = jnp.asarray([1.0, 0.0, 1.0, 1.0])
    assert float(masked_mean(v, m)) == pytest.approx((1 + 3 + 4) / 3)
    assert float(masked_mean(v, jnp.zeros(4))) == 0.0   # guarded denom
    # 2-D metrics leaf: mean over live elements
    v2 = jnp.asarray(np.arange(8, dtype=np.float32).reshape(4, 2))
    want = np.asarray(v2)[[0, 2, 3]].mean()
    assert float(masked_mean(v2, m)) == pytest.approx(want)


def test_participation_mask_on_device_matches_host():
    periods = (1.0, 2.0, 4.0)
    mults = participation_mults(periods)
    np.testing.assert_array_equal(mults, [1, 2, 4])
    masker = jax.jit(lambda t: participation_mask(mults, t))
    for step in range(8):
        np.testing.assert_array_equal(
            np.asarray(masker(step)),
            multirate_participation(periods, step))


# --------------------------------------------------------------------------
# Capacity-mode + double-buffered controller
# --------------------------------------------------------------------------

def test_controller_capacity_mode_pads_and_masks():
    ctl = OverlayController(make_sim(n=6), capacity=8)
    assert ctl.schedule.num_clients == 8
    assert ctl.alive_schedule.num_clients == 6
    assert ctl.alive_mask().sum() == 6
    rng = np.random.default_rng(0)
    X = jnp.asarray(rng.normal(size=(8, 9)).astype(np.float32))
    out = np.asarray(ctl.mixer(X, jnp.asarray(ctl.alive_mask())))
    ref = masked_mixing_matrix(ctl.schedule, ctl.alive_mask()) @ np.asarray(X)
    np.testing.assert_allclose(out, ref, atol=1e-5)


def test_controller_capacity_fail_rejoin_is_cache_hit():
    """Same alive set + identity-preserving slots ⇒ same padded schedule
    digest ⇒ the swap back is a pure cache hit (zero retrace)."""
    sim = make_sim(n=6)
    ctl = OverlayController(sim, capacity=8)
    original = ctl.schedule
    misses0 = ctl.cache.misses
    for _ in range(20):
        ctl.step(1.0, trace=ChurnTrace.scripted(
            [(sim.now + 0.1, "fail", 2)]))
        if len(ctl.alive) == 5:
            break
    assert ctl.schedule != original
    assert ctl.alive_mask().sum() == 5
    trace = ChurnTrace.scripted([(sim.now + 0.1, "join", 2, 0)])
    for _ in range(20):
        ctl.step(1.0, trace=trace)
        trace = None
        if len(ctl.alive) == 6:
            break
    assert ctl.schedule == original     # node 2 reclaimed its old slot
    assert ctl.cache.misses == misses0 + 1


def test_controller_double_buffered_swaps_only_at_commit():
    sim = make_sim(n=6)
    ctl = OverlayController(sim, capacity=8, double_buffered=True)
    mixer0, sched0 = ctl.mixer, ctl.schedule
    trace = ChurnTrace.scripted([(sim.now + 0.1, "fail", 4)])
    swapped = False
    for _ in range(20):
        r = ctl.step(1.0, trace=trace)
        trace = None
        if r.swapped:
            swapped = True
            break
    assert swapped
    # staged, not live: the data plane still sees the old program
    assert ctl.mixer is mixer0 and ctl.schedule == sched0
    assert 4 in ctl.slots
    plan = ctl.commit()
    assert plan is not None and dict(plan.leavers)
    assert ctl.mixer is not mixer0 and ctl.schedule != sched0
    assert 4 not in ctl.slots
    # idempotent at quiescence
    ctl.step(1.0)
    assert ctl.commit() is None


def test_controller_capacity_requires_global_mixer_kind():
    with pytest.raises(ValueError, match="capacity mode"):
        OverlayController(make_sim(n=4), capacity=8,
                          mixer_kind="shard_map")


# --------------------------------------------------------------------------
# Joiner donors: the Fig.-18 catch-up selection (satellite coverage)
# --------------------------------------------------------------------------

def test_joiner_donors_all_joiner_cohort_falls_back_to_fresh_init():
    """A mass-join cohort with no surviving neighbors gets None for
    every joiner (fresh-init fallback)."""
    sched = build_permute_schedule(6, 2)
    alive = tuple(range(6))
    donors = joiner_donors(sched, alive, joiners=alive, survivors=())
    assert donors == {u: None for u in alive}


def test_joiner_donors_picks_highest_weight_survivor():
    sched = build_permute_schedule(6, 2)
    alive = tuple(range(6))
    donors = joiner_donors(sched, alive, joiners=(3,),
                           survivors=(0, 1, 2, 4, 5))
    donor = donors[3]
    weights = {}
    for k in range(sched.num_slots):
        src = alive[sched.perms[k][3]]
        if src != 3:
            weights[src] = max(weights.get(src, 0.0),
                               float(sched.weights[3, k]))
    assert donor is not None and weights[donor] == max(weights.values())


# --------------------------------------------------------------------------
# SlotTrainLoop: the ISSUE acceptance pins
# --------------------------------------------------------------------------

DIM = 32


def _make_params(u):
    w = np.random.default_rng(u).normal(size=DIM).astype(np.float32)
    return {"w": jnp.asarray(w)}


def _make_batch(node_ids, step):
    rows = [np.random.default_rng(abs(hash((u, step))) % 2**32)
            .normal(size=DIM).astype(np.float32) for u in node_ids]
    return {"x": jnp.asarray(np.stack(rows))}


def _base_step(lr=0.05):
    def step(params, opt_state, batch):
        w, x = params["w"], batch["x"]
        loss = jnp.mean((w - x) ** 2, axis=-1)
        grad = 2.0 * (w - x) / DIM
        return {"w": w - lr * grad}, opt_state, {"loss": loss}
    return step


def _restack_step(lr=0.05):
    base = _base_step(lr)

    def step(params, opt_state, batch):
        p, o, m = base(params, opt_state, batch)
        return p, o, {"loss": jnp.mean(m["loss"])}
    return step


def _churn():
    return ChurnTrace.scripted([
        (2.5, "fail", 1), (4.5, "fail", 3),
        (6.5, "join", 100, 0), (8.5, "join", 101, 0),
    ])


def test_slot_loop_matches_restack_loop_and_never_retraces():
    from repro.optim.optimizers import sgd
    opt = sgd(0.0)
    rjit, rcount = counting_jit(_restack_step())
    restack = ChurnTrainLoop(
        OverlayController(make_sim(n=6)), local_step=rjit,
        make_params=_make_params, optimizer=opt, make_batch=_make_batch,
        jit_local_step=False)
    recs_r = restack.run(12, trace=_churn())

    sjit, scount = counting_jit(masked_local_step(_base_step()))
    slot = SlotTrainLoop(
        OverlayController(make_sim(n=6), capacity=8), local_step=sjit,
        make_params=_make_params, optimizer=opt, make_batch=_make_batch,
        jit_local_step=False)
    recs_s = slot.run(12, trace=_churn())

    # identical churn observation
    assert [r.num_alive for r in recs_r] == [r.num_alive for r in recs_s]
    assert [r.joined for r in recs_r] == [r.joined for r in recs_s]
    assert [r.left for r in recs_r] == [r.left for r in recs_s]
    alive_counts = {r.num_alive for r in recs_s}
    assert len(alive_counts) >= 3
    # loss parity to fp tolerance
    np.testing.assert_allclose([r.loss for r in recs_r],
                               [r.loss for r in recs_s],
                               rtol=1e-5, atol=1e-5)
    # the acceptance pin: static shapes never retrace, re-stack pays one
    # trace per distinct alive count
    assert scount.traces == 1 and scount.retraces == 0
    assert rcount.traces == len(alive_counts)


def test_slot_loop_joiner_donor_copy_and_fresh_optimizer():
    """lr=0 + identity mixer ⇒ params are pure lineage markers: the
    joiner's row must equal its donor's init exactly (Fig.-18 catch-up),
    not its own fresh init."""
    from repro.optim.optimizers import sgd
    ctl = OverlayController(
        make_sim(n=4), capacity=6,
        mixer_factory=lambda sched: (lambda params, mask: params))
    loop = SlotTrainLoop(
        ctl, local_step=masked_local_step(_base_step(lr=0.0)),
        make_params=_make_params, optimizer=sgd(0.0),
        make_batch=_make_batch)
    loop.run(8, trace=ChurnTrace.scripted([(2.5, "join", 50, 0)]))
    assert 50 in ctl.slots
    joined = np.asarray(loop.client_params(50)["w"])
    donors = {u: np.asarray(_make_params(u)["w"]) for u in range(4)}
    fresh = np.asarray(_make_params(50)["w"])
    assert any(np.array_equal(joined, d) for d in donors.values())
    assert not np.array_equal(joined, fresh)


def test_slot_loop_multirate_participation_skips_mixing():
    """A slow client (period 4) trains locally every step but only mixes
    when step % 4 == 0; with lr=0 its params change exactly on
    participating steps."""
    from repro.optim.optimizers import sgd
    slow = 2
    ctl = OverlayController(make_sim(n=4), capacity=4)
    loop = SlotTrainLoop(
        ctl, local_step=masked_local_step(_base_step(lr=0.0)),
        make_params=_make_params, optimizer=sgd(0.0),
        make_batch=_make_batch,
        periods={u: (4.0 if u == slow else 1.0) for u in range(4)})
    snaps = []
    for _ in range(6):
        loop.run(1)
        snaps.append({u: np.asarray(loop.client_params(u)["w"])
                      for u in (0, slow)})
    assert [r.participating for r in loop.records] == [4, 3, 3, 3, 4, 3]
    for t in range(1, 6):
        fast_moved = not np.array_equal(snaps[t][0], snaps[t - 1][0])
        slow_moved = not np.array_equal(snaps[t][slow],
                                        snaps[t - 1][slow])
        assert fast_moved              # period-1 clients mix every step
        assert slow_moved == (t % 4 == 0)


def test_restack_loop_commits_double_buffered_controller():
    """Regression: ChurnTrainLoop must land staged swaps before using
    report.alive — otherwise it re-stacks to the staged membership but
    mixes with the stale uncommitted program.  With commit() in the
    loop, a double_buffered controller matches the immediate one."""
    from repro.optim.optimizers import sgd
    opt = sgd(0.0)
    runs = []
    for db in (False, True):
        loop = ChurnTrainLoop(
            OverlayController(make_sim(n=5), double_buffered=db),
            local_step=_restack_step(), make_params=_make_params,
            optimizer=opt, make_batch=_make_batch)
        runs.append(loop.run(10, trace=ChurnTrace.scripted(
            [(2.5, "fail", 1), (4.5, "join", 77, 0)])))
    immediate, buffered = runs
    assert [r.num_alive for r in immediate] == \
        [r.num_alive for r in buffered]
    np.testing.assert_allclose([r.loss for r in immediate],
                               [r.loss for r in buffered], rtol=1e-6)


def test_slot_loop_over_double_buffered_controller():
    """With double_buffered staging, the loop's commit() at the step
    boundary still lands every membership change exactly once."""
    from repro.optim.optimizers import sgd
    ctl = OverlayController(make_sim(n=5), capacity=8,
                            double_buffered=True)
    loop = SlotTrainLoop(
        ctl, local_step=masked_local_step(_base_step()),
        make_params=_make_params, optimizer=sgd(0.0),
        make_batch=_make_batch)
    recs = loop.run(10, trace=ChurnTrace.scripted(
        [(2.5, "fail", 1), (4.5, "join", 77, 0)]))
    assert [r.left for r in recs if r.left] == [(1,)]
    assert [r.joined for r in recs if r.joined] == [(77,)]
    assert recs[-1].num_alive == 5 and 77 in ctl.slots
    assert all(np.isfinite(r.loss) for r in recs)


@pytest.mark.multi_device
def test_grouped_slot_loop_capacity_2x_devices_zero_retrace(multi_device):
    """The ISSUE 4 acceptance pin: capacity C = 2 × devices (G = 2) on
    the real 8-device mesh — the slot loop's jitted local step and the
    controller's mask-aware mixers hold 0 retraces across a churn trace
    with ≥ 3 distinct alive counts, with every capacity-stacked row
    tree genuinely sharded over the mesh."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.dist.compat import make_client_mesh
    from repro.optim.optimizers import sgd

    mesh = make_client_mesh(8, "data")
    ctl = OverlayController(make_sim(n=12), capacity=16,
                            clients_per_device=2)
    sjit, scount = counting_jit(masked_local_step(_base_step()))
    loop = SlotTrainLoop(
        ctl, local_step=sjit, make_params=_make_params, optimizer=sgd(0.0),
        make_batch=_make_batch, jit_local_step=False, mesh=mesh)
    # the capacity axis is genuinely distributed: 2 rows per device
    assert loop.params["w"].sharding == NamedSharding(mesh, P("data", None))
    recs = loop.run(12, trace=ChurnTrace.scripted([
        (2.5, "fail", 1), (4.5, "fail", 3),
        (6.5, "join", 100, 0), (8.5, "join", 101, 0),
    ]))
    assert len({r.num_alive for r in recs}) >= 3
    assert all(np.isfinite(r.loss) for r in recs)
    # zero retraces: one trace ever for the local step, and every
    # post-churn mixer program came out of the schedule-keyed cache on
    # revisit (fail -> rejoin restores the padded-schedule digest)
    assert scount.traces == 1 and scount.retraces == 0
    assert loop.params["w"].sharding == NamedSharding(mesh, P("data", None))


@pytest.mark.multi_device
def test_slot_loop_makes_each_row_on_its_own_device(multi_device):
    """With a mesh, each client's row is made on the device that holds
    it and stacked there: no device ever holds the whole population."""
    from repro.dist.compat import make_client_mesh
    from repro.optim.optimizers import sgd

    mesh = make_client_mesh(8, "data")
    ctl = OverlayController(make_sim(n=12), capacity=16,
                            clients_per_device=2)
    made_on = {}

    def make_params(u):
        row = _make_params(u)
        made_on[u] = row["w"].devices()
        return row
    loop = SlotTrainLoop(
        ctl, local_step=masked_local_step(_base_step()),
        make_params=make_params, optimizer=sgd(0.0),
        make_batch=_make_batch, mesh=mesh)
    w = loop.params["w"]
    holder = {i: shard.device for shard in w.addressable_shards
              for i in range(16)[shard.index[0]]}
    for slot in range(16):
        u = ctl.slots.node_at(slot)
        if u is None:                            # dead slot: zeros
            np.testing.assert_array_equal(np.asarray(w[slot]), 0.0)
        else:
            assert made_on[u] == {holder[slot]}
            np.testing.assert_array_equal(np.asarray(w[slot]),
                                          _make_params(u)["w"])


def test_grouped_slot_loop_rejects_mismatched_mesh():
    from repro.dist.compat import make_client_mesh
    from repro.optim.optimizers import sgd
    mesh = make_client_mesh(8, "data")
    ctl = OverlayController(make_sim(n=4), capacity=8)   # G=1, 8 = 1×8 ok
    SlotTrainLoop(ctl, local_step=masked_local_step(_base_step()),
                  make_params=_make_params, optimizer=sgd(0.0),
                  make_batch=_make_batch, mesh=mesh)
    ctl2 = OverlayController(make_sim(n=4), capacity=16)  # 16 != 1×8
    with pytest.raises(ValueError, match="capacity 16"):
        SlotTrainLoop(ctl2, local_step=masked_local_step(_base_step()),
                      make_params=_make_params, optimizer=sgd(0.0),
                      make_batch=_make_batch, mesh=mesh)


def test_controller_capacity_must_divide_into_groups():
    with pytest.raises(ValueError, match="multiple"):
        OverlayController(make_sim(n=4), capacity=9, clients_per_device=2)


def test_slot_loop_capacity_overflow_raises():
    from repro.optim.optimizers import sgd
    from repro.runtime import SlotCapacityError
    ctl = OverlayController(make_sim(n=4), capacity=4)
    loop = SlotTrainLoop(
        ctl, local_step=masked_local_step(_base_step()),
        make_params=_make_params, optimizer=sgd(0.0),
        make_batch=_make_batch)
    with pytest.raises(SlotCapacityError):
        loop.run(6, trace=ChurnTrace.scripted([(1.5, "join", 70, 0)]))


def test_slot_loop_requires_capacity_controller():
    from repro.optim.optimizers import sgd
    with pytest.raises(ValueError, match="capacity"):
        SlotTrainLoop(OverlayController(make_sim(n=4)),
                      local_step=masked_local_step(_base_step()),
                      make_params=_make_params, optimizer=sgd(0.0),
                      make_batch=_make_batch)


def test_slot_loop_drives_masked_dfl_train_bundle():
    """The real integration: dfl_train_bundle(masked=True) local step
    under the slot runtime (smoke-scale model, one join)."""
    import dataclasses
    from repro.configs import REGISTRY, reduce_for_smoke
    from repro.launch.mesh import make_local_mesh
    from repro.launch.steps import dfl_train_bundle
    from repro.models import init_params
    from repro.models.config import INPUT_SHAPES
    from repro.optim.optimizers import adamw
    cfg = reduce_for_smoke(REGISTRY["qwen3-4b"])
    capacity = 3
    shape = dataclasses.replace(INPUT_SHAPES["train_4k"],
                                global_batch=capacity, seq_len=32)
    mesh = make_local_mesh(1, 1)
    opt = adamw(1e-3)
    bundle = dfl_train_bundle(cfg, shape, mesh, opt, dtype=jnp.float32,
                              sync="none", masked=True)
    assert len(bundle.arg_shapes) == 4
    stacked = jax.tree.leaves(bundle.arg_shapes[0])[0]
    assert bundle.arg_shapes[3].shape == (stacked.shape[0],)
    per_client = {k: v.shape[1:] for k, v in bundle.arg_shapes[2].items()}

    def make_params(node_id):
        return init_params(cfg, jax.random.PRNGKey(node_id),
                           dtype=jnp.float32)

    def make_batch(node_ids, step):
        out = {}
        for k, shp in per_client.items():
            rows = [np.random.default_rng(
                abs(hash((u, step, k))) % 2**32).integers(
                    0, cfg.vocab_size, shp) for u in node_ids]
            out[k] = jnp.asarray(np.stack(rows), jnp.int32)
        return out

    ctl = OverlayController(make_sim(n=2), capacity=capacity)
    loop = SlotTrainLoop(ctl, local_step=bundle.step,
                         make_params=make_params, optimizer=opt,
                         make_batch=make_batch)
    recs = loop.run(4, trace=ChurnTrace.scripted([(1.5, "join", 50, 0)]))
    assert all(np.isfinite(r.loss) for r in recs)
    assert recs[-1].num_alive == 3
    assert loop.controller.alive == (0, 1, 50)


def test_slot_loop_resident_flat_matches_tree_loop():
    """ISSUE 7 satellite: with OverlayController(flat_io=True) the loop
    keeps the population as the resident (capacity, N) flat buffer —
    ravel/unravel leaves the hot loop — yet observes identical churn,
    loss parity, zero retraces, and identity-preserving client_params."""
    from repro.optim.optimizers import sgd
    opt = sgd(0.0)
    tjit, _ = counting_jit(masked_local_step(_base_step()))
    tree_loop = SlotTrainLoop(
        OverlayController(make_sim(n=6), capacity=8, fuse="flat"),
        local_step=tjit, make_params=_make_params, optimizer=opt,
        make_batch=_make_batch, jit_local_step=False)
    recs_t = tree_loop.run(12, trace=_churn())

    fjit, fcount = counting_jit(masked_local_step(_base_step()))
    flat_loop = SlotTrainLoop(
        OverlayController(make_sim(n=6), capacity=8, fuse="flat",
                          flat_io=True),
        local_step=fjit, make_params=_make_params, optimizer=opt,
        make_batch=_make_batch, jit_local_step=False)
    assert flat_loop.flat_io
    recs_f = flat_loop.run(12, trace=_churn())

    assert [r.num_alive for r in recs_t] == [r.num_alive for r in recs_f]
    np.testing.assert_allclose([r.loss for r in recs_t],
                               [r.loss for r in recs_f],
                               rtol=1e-5, atol=1e-5)
    assert fcount.traces == 1 and fcount.retraces == 0
    # client_params unravels one row back to the tree contract
    for u in (0, 100):
        pt = np.asarray(tree_loop.client_params(u)["w"])
        pf = np.asarray(flat_loop.client_params(u)["w"])
        np.testing.assert_allclose(pf, pt, rtol=1e-6, atol=1e-6)


def test_slot_loop_checkpoint_roundtrip_bit_exact(tmp_path):
    """ISSUE 10 satellite: save/restore of the full slot-runtime state
    — resident flat rows, optimizer state, EF residual, step counter —
    is bit-exact, with slot occupancy validated against the checkpoint
    and wire-config mismatches rejected."""
    from repro.optim.optimizers import sgd
    opt = sgd(0.0)

    def build(n=6):
        ctl = OverlayController(make_sim(n=n), capacity=8, fuse="flat",
                                codec="int8-block", flat_io=True)
        return SlotTrainLoop(ctl, local_step=masked_local_step(_base_step()),
                             make_params=_make_params, optimizer=opt,
                             make_batch=_make_batch)

    loop = build()
    assert loop.ef and loop.flat_io
    loop.run(5)
    assert float(np.abs(np.asarray(loop.residual)).max()) > 0  # EF active
    path = str(tmp_path / "slot.npz")
    loop.save(path)

    # a brand-new stack: control plane replayed, then state restored
    fresh = build()
    for _ in range(5):
        fresh.controller.step(1.0)
        fresh.controller.commit()
    meta = fresh.restore(path)
    assert meta["step"] == 5 and fresh._step == 5
    np.testing.assert_array_equal(np.asarray(loop.params),
                                  np.asarray(fresh.params))
    np.testing.assert_array_equal(np.asarray(loop.residual),
                                  np.asarray(fresh.residual))
    for a, b in zip(jax.tree.leaves(loop.opt_state),
                    jax.tree.leaves(fresh.opt_state)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # occupancy metadata survived: -1 for the two empty slots
    assert meta["slots"].count(-1) == 2
    # resumed run == uninterrupted run, bit for bit
    recs_a = loop.run(3)
    recs_b = fresh.run(3)
    np.testing.assert_array_equal(np.asarray(loop.params),
                                  np.asarray(fresh.params))
    assert [r.loss for r in recs_a[-3:]] == [r.loss for r in recs_b[-3:]]

    # a loop with a different wire config must refuse the checkpoint
    plain = SlotTrainLoop(
        OverlayController(make_sim(n=6), capacity=8),
        local_step=masked_local_step(_base_step()),
        make_params=_make_params, optimizer=opt, make_batch=_make_batch)
    with pytest.raises(ValueError, match="wire configuration"):
        plain.restore(path)
