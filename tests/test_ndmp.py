"""NDMP protocols: join correctness (Thm 1), leave, failure repair
(Thm 2), and concurrent-churn convergence — including hypothesis-driven
random churn schedules."""

import numpy as np
import pytest
from hypothesis import example, given, settings, strategies as st

from repro.core.coords import NodeAddress, circular_distance, coordinates
from repro.core.ndmp import Simulator
from repro.core.topology import correct_neighbor_sets


def make_sim(n=30, L=3, seed=0, **kw):
    sim = Simulator(num_spaces=L, latency=0.05, heartbeat_period=0.5,
                    probe_period=1.0, seed=seed, **kw)
    sim.seed_network(list(range(n)))
    return sim


def test_seeded_network_is_correct():
    assert make_sim().correctness() == 1.0


def test_single_join_converges_to_correct():
    sim = make_sim(n=20)
    sim.join(100, bootstrap=3)
    sim.run_for(5.0)
    assert sim.correctness() == 1.0
    # Definition-1 check: the joiner's table is exactly its ring adjacency
    want = correct_neighbor_sets(sim.alive_addresses())
    assert sim.nodes[100].neighbor_set == want[100]


def test_join_is_recursive_from_two_nodes():
    """Paper: recursive construction from a 2-node network."""
    sim = Simulator(num_spaces=2, latency=0.05, heartbeat_period=0.5,
                    probe_period=1.0)
    sim.seed_network([0, 1])
    for j in range(2, 12):
        sim.join(j, bootstrap=int(j % 2))
        sim.run_for(4.0)
    assert sim.correctness() == 1.0


def test_leave_protocol():
    sim = make_sim(n=25)
    sim.leave(7)
    sim.run_for(3.0)
    assert sim.correctness() == 1.0
    assert 7 not in {a.node_id for a in sim.alive_addresses()}


def test_failure_repair_theorem2():
    """After one abrupt failure the two ring-adjacent nodes reconnect."""
    sim = make_sim(n=25)
    sim.fail(11)
    sim.run_for(10.0)   # detect (3T) + repair
    assert sim.correctness() == 1.0


def test_mass_concurrent_join():
    """Paper Fig 8a: 25 clients join a 100-client network at once."""
    sim = make_sim(n=100)
    for j in range(200, 225):
        sim.join(j, bootstrap=int(j % 100))
    sim.run_for(30.0)
    assert sim.correctness() == 1.0


def test_mass_concurrent_failure():
    """Paper Fig 8b: 25% of clients fail at the same instant."""
    sim = make_sim(n=80)
    for f in range(0, 20):
        sim.fail(f)
    sim.run_for(40.0)
    assert sim.correctness() == 1.0


@settings(max_examples=10, deadline=None)
@given(st.lists(st.tuples(st.sampled_from(["join", "fail", "leave"]),
                          st.integers(0, 10_000)),
                min_size=1, max_size=12),
       st.integers(0, 5))
# a joiner bootstrapping through a node that has not joined yet
@example([("join", 0), ("join", 0), ("fail", 0), ("fail", 0),
          ("join", 7078)], 0)
def test_random_churn_schedule_converges(events, seed):
    """Property: any interleaving of joins/leaves/failures converges back
    to a correct FedLay (the paper's core resilience claim)."""
    sim = make_sim(n=40, seed=seed)
    alive = set(range(40))
    next_id = 1000
    for kind, jitter in events:
        sim.run_for(0.01 * (jitter % 7))
        if kind == "join":
            order = sorted(alive)
            boot = int(order[jitter % len(alive)])
            # realistic deployment: joiner ships a 3-entry seed list, so
            # a bootstrap that dies mid-join doesn't strand it
            seeds = tuple(int(order[(jitter + k) % len(alive)])
                          for k in range(1, 4))
            sim.join(next_id, bootstrap=boot, seeds=seeds)
            alive.add(next_id)
            next_id += 1
        elif len(alive) > 25:
            victim = sorted(alive)[jitter % len(alive)]
            (sim.fail if kind == "fail" else sim.leave)(victim)
            alive.discard(victim)
    sim.run_for(60.0)
    assert sim.correctness() == 1.0


def test_construction_message_cost_scales():
    """Paper Fig 8c: ~30 join messages per client at n=500 — we assert the
    per-client join cost grows sub-linearly (greedy routing shortcuts)."""
    costs = {}
    for n in (50, 200):
        sim = Simulator(num_spaces=3, latency=0.01, heartbeat_period=50.0,
                        probe_period=100.0, seed=1)
        sim.seed_network(list(range(10)))
        for j in range(10, n):
            sim.join(j, bootstrap=int(j % 10))
            sim.run_for(1.0)
        sim.run_for(5.0)
        joins = [st_.join_messages for id_, st_ in sim.nodes.items() if id_ >= 10]
        costs[n] = float(np.mean(joins))
    assert costs[200] < costs[50] * 4.0   # ≈O(log n) growth, not O(n)
    assert costs[200] < 80.0
