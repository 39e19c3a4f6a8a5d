"""Vectorized NDMP engine (repro.scale.ndmp_vec) vs the object
simulator: both are NDMP engines behind the same
:class:`repro.core.ndmp.SimulatorProtocol` seam, and on any churn trace
their **converged** states must be identical — neighbor tables,
exported flat arrays, Definition-1 correctness, and the schedules (and
hence confidence-weighted mixing weights) built from their alive sets.
Includes a hypothesis fuzz over batched event orderings (shimmed to
skip when hypothesis is not installed)."""

import numpy as np
import pytest
from hypothesis import example, given, settings, strategies as st

from repro.core.coords import coordinates, coordinates_batch
from repro.core.mep import ClientProfile
from repro.core.mixing import schedule_from_addresses
from repro.core.ndmp import Simulator, SimulatorProtocol
from repro.scale import VectorSimulator

KW = dict(num_spaces=3, latency=0.05, heartbeat_period=0.5,
          probe_period=1.0)


def make_pair(n, seed=0):
    obj = Simulator(seed=seed, **KW)
    obj.seed_network(list(range(n)))
    vec = VectorSimulator(**KW)
    vec.seed_network(range(n))
    return obj, vec


# --------------------------------------------------------------------------
# Protocol seam
# --------------------------------------------------------------------------

def test_both_engines_satisfy_protocol():
    obj, vec = make_pair(10)
    assert isinstance(obj, SimulatorProtocol)
    assert isinstance(vec, SimulatorProtocol)


def test_tables_version_is_a_change_detector():
    _, vec = make_pair(20)
    v0 = vec.tables_version()
    vec.advance(5.0)
    assert vec.tables_version() == v0          # idle: no change
    vec.fail(3)
    vec.run_for(10.0)
    assert vec.tables_version() != v0


# --------------------------------------------------------------------------
# Batch coordinate hashing
# --------------------------------------------------------------------------

def test_coordinates_batch_bit_exact():
    ids = [0, 1, 7, 123, 10**12, 2**40 + 17]
    got = coordinates_batch(ids, 4, salt="s")
    for i, u in enumerate(ids):
        assert tuple(got[i]) == coordinates(u, 4, salt="s")


# --------------------------------------------------------------------------
# Converged-state parity on seeded traces (n <= 200)
# --------------------------------------------------------------------------

def assert_converged_equal(obj, vec):
    assert obj.correctness() == 1.0
    assert vec.correctness() == 1.0
    assert obj.alive_ids() == vec.alive_ids()
    assert obj.neighbor_tables() == vec.neighbor_tables()


@pytest.mark.parametrize("n", [30, 200])
def test_parity_join_leave_fail_trace(n):
    obj, vec = make_pair(n)
    assert_converged_equal(obj, vec)
    # interleaved churn: joins, abrupt failures, graceful leaves
    for j in range(n + 100, n + 100 + 5):
        obj.join(j, bootstrap=n // 2)
        vec.join(j)
    obj.run_for(8.0)
    vec.run_for(8.0)
    for f in (1, 4, 9):
        obj.fail(f)
        vec.fail(f)
    for v in (2, 6):
        obj.leave(v)
        vec.leave(v)
    obj.run_for(40.0)
    vec.run_for(40.0)
    assert_converged_equal(obj, vec)


def test_parity_export_state():
    obj, vec = make_pair(40)
    for f in (3, 8):
        obj.fail(f)
        vec.fail(f)
    obj.run_for(30.0)
    vec.run_for(30.0)
    a, b = obj.export_state(), vec.export_state()
    np.testing.assert_array_equal(a["ids"], b["ids"])
    np.testing.assert_array_equal(a["coords"], b["coords"])  # bit-exact
    np.testing.assert_array_equal(a["succ"], b["succ"])
    np.testing.assert_array_equal(a["pred"], b["pred"])


def test_parity_schedule_weights():
    """Identical alive sets + identical MEP profiles → bit-identical
    confidence-weighted mixing schedules from either engine."""
    obj, vec = make_pair(24)
    obj.fail(5)
    vec.fail(5)
    obj.run_for(30.0)
    vec.run_for(30.0)
    hist = np.ones(4)
    profiles = {u: ClientProfile(client_id=u, period=1.0 + (u % 3),
                                 label_histogram=hist * (1 + u % 5))
                for u in obj.alive_ids()}
    sa = schedule_from_addresses(obj.alive_addresses(), profiles=profiles)
    sb = schedule_from_addresses(vec.alive_addresses(), profiles=profiles)
    np.testing.assert_array_equal(sa.perms, sb.perms)
    np.testing.assert_array_equal(sa.weights, sb.weights)
    np.testing.assert_array_equal(sa.self_weight, sb.self_weight)


def test_from_simulator_adopts_membership():
    obj, _ = make_pair(25)
    obj.fail(7)
    obj.run_for(30.0)
    vec = VectorSimulator.from_simulator(obj)
    assert vec.alive_ids() == obj.alive_ids()
    assert vec.neighbor_tables() == obj.neighbor_tables()


# --------------------------------------------------------------------------
# Vectorized engine semantics
# --------------------------------------------------------------------------

def test_mid_repair_correctness_dips_then_recovers():
    """The engine models protocol *timing*, not just the fixed point:
    a failure is invisible until detection + repair completes."""
    _, vec = make_pair(50)
    vec.fail_batch([1, 2, 3])
    assert vec.correctness() < 1.0     # stale pointers during repair
    vec.run_for(30.0)
    assert vec.correctness() == 1.0


def test_batch_churn_rejects_bad_ops():
    _, vec = make_pair(10)
    with pytest.raises(ValueError):
        vec.join_batch([3])            # already alive
    with pytest.raises(KeyError):
        vec.fail_batch([99])           # not alive


def test_rejoin_after_failure():
    _, vec = make_pair(12)
    vec.fail(4)
    vec.run_for(30.0)
    vec.join(4)
    vec.run_for(30.0)
    assert 4 in vec.alive_ids()
    assert vec.correctness() == 1.0


def test_large_population_batch_churn_converges():
    """10^4 nodes: seed + 1% batched churn, exact repair — the fig20
    scale path in miniature (the full 10^5/10^6 budget is the
    benchmark's claim, not tier-1's)."""
    vec = VectorSimulator(**KW)
    vec.seed_network(range(10_000))
    vec.fail_batch(range(100))
    vec.join_batch(range(20_000, 20_100))
    vec.run_for(30.0)
    assert len(vec.alive_ids()) == 10_000
    assert vec.correctness() == 1.0


# --------------------------------------------------------------------------
# Property: any batched event ordering converges to the object fixpoint
# --------------------------------------------------------------------------

@settings(max_examples=10, deadline=None)
@given(st.lists(st.tuples(st.sampled_from(["join", "fail", "leave"]),
                          st.integers(0, 10_000)),
                min_size=1, max_size=10),
       st.integers(0, 3))
# a joiner whose only contact fails in the same instant
@example([("join", 0), ("fail", 0)], 0)
def test_fuzz_batched_churn_parity(events, seed):
    """Property: the object engine applies events one by one, the
    vectorized engine in per-kind batches — same converged network."""
    n = 40
    obj, vec = make_pair(n, seed=seed)
    alive = set(range(n))
    next_id = 1000
    batch = {"join": [], "fail": [], "leave": []}
    for kind, jitter in events:
        if kind == "join":
            order = sorted(alive)
            obj.join(next_id, bootstrap=int(order[jitter % len(order)]))
            batch["join"].append(next_id)
            alive.add(next_id)
            next_id += 1
        elif len(alive) > 25:
            victim = sorted(alive)[jitter % len(alive)]
            if victim in batch["join"]:
                continue               # same-instant join+depart: skip
            getattr(obj, kind)(victim)
            batch[kind].append(victim)
            alive.discard(victim)
    if batch["fail"]:
        vec.fail_batch(batch["fail"])
    if batch["leave"]:
        vec.leave_batch(batch["leave"])
    if batch["join"]:
        vec.join_batch(batch["join"])
    obj.run_for(60.0)
    vec.run_for(60.0)
    assert_converged_equal(obj, vec)
