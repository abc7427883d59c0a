"""Fault events: capacity degradation, movement-bounded rebalance, and the
injector's schedule in the simulator's event representation."""
import numpy as np
import pytest

from repro.core import generate_cluster
from repro.distributed.fault import (CapacityEvent, FaultInjector,
                                     degrade, rebalance)


# ---------------------------------------------------------------------------
# fault tolerance -> SPTLB rebalance
# ---------------------------------------------------------------------------

def test_degrade_shrinks_capacity():
    cluster = generate_cluster(num_apps=100, seed=0)
    before = np.asarray(cluster.problem.capacity).copy()
    ev = CapacityEvent("host_failure", tier=2, fraction=0.25)
    after = degrade(cluster, ev.to_timed())
    np.testing.assert_allclose(np.asarray(after.problem.capacity)[2],
                               before[2] * 0.75, rtol=1e-6)
    assert after.hosts_per_tier[2] < cluster.hosts_per_tier[2]


def test_rebalance_after_failure_feasible_and_bounded():
    cluster = generate_cluster(num_apps=200, seed=1)
    ev = CapacityEvent("host_failure", tier=2, fraction=0.3)
    rebalanced, decision = rebalance(cluster, ev)
    assert decision.violations.ok
    # movement bounded: the paper's constraint 3 holds through recovery
    assert (decision.projected.num_moved
            <= int(cluster.problem.move_budget))


def test_fault_injector_deterministic():
    a = FaultInjector(5, seed=42, failure_rate=0.5)
    b = FaultInjector(5, seed=42, failure_rate=0.5)
    ev_a = [a.sample(s) for s in range(20)]
    ev_b = [b.sample(s) for s in range(20)]
    assert [(e.kind, e.tier) for evs in ev_a for e in evs] == \
           [(e.kind, e.tier) for evs in ev_b for e in evs]


def test_injector_schedule_unifies_with_sim_events():
    inj = FaultInjector(5, seed=3, failure_rate=0.3, straggler_rate=0.3)
    timed, advisories = inj.schedule(30)
    assert timed, "seed should produce at least one event in 30 steps"
    # Timed events are sim CapacityScale records with composed scales.
    from repro.sim.events import CapacityScale
    assert all(isinstance(t, CapacityScale) for t in timed)
    assert all(0.0 < t.scale for t in timed)
    # Stacked events on one tier compose multiplicatively against as-built.
    per_tier = {}
    for t in timed:
        per_tier.setdefault(t.tier, []).append(t.scale)
    for scales in per_tier.values():
        assert all(b != a for a, b in zip(scales, scales[1:])) or len(scales) == 1
    # Announced events (stragglers here) ride the PR-4 advisory channel;
    # hard failures stay surprises.
    announced = [t for t in timed if t.announced]
    assert len(advisories) == len(announced)
    for adv, t in zip(advisories, announced):
        assert (adv.at, adv.tier) == (t.at, t.tier)
        assert adv.scale == pytest.approx(t.scale)
