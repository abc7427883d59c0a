"""Stream frontend: build_cluster's field mapping, routing onto pod slices,
and re-routing after a capacity event."""
import dataclasses

import numpy as np
import pytest

from repro.distributed.fault import CapacityEvent, rebalance
from repro.streams import (StreamRouter, build_cluster, default_slices,
                           demo_apps)


def test_router_routes_apps_to_slices():
    apps = demo_apps(48, seed=0)
    cluster = build_cluster(apps, default_slices(), seed=0)
    router = StreamRouter(cluster)
    decision = router.route()
    assert decision.violations.ok
    # every app is assigned to exactly one tier; partitions follow it
    total = sum(len(router.partitions_for_tier(t, apps))
                for t in range(5))
    assert total == len(apps)


@pytest.mark.parametrize("kind,fraction", [
    ("host_failure", 0.2), ("straggler", 0.1), ("scale_up", 0.25)])
def test_router_rides_a_capacity_event(kind, fraction):
    """Route, then re-route after a capacity event on the routed cluster:
    the result is feasible, moves stay within the movement budget, and
    every app's partitions land on exactly one slice."""
    apps = demo_apps(48, seed=0)
    slices = default_slices()
    router = StreamRouter(build_cluster(apps, slices, seed=0))
    routed = router.route(engine="local", variant="manual_cnst")
    assert routed.violations.ok
    cluster = dataclasses.replace(
        router.cluster,
        problem=router.cluster.problem.with_assignment0(routed.assignment))

    event = CapacityEvent(kind, tier=2, fraction=fraction)
    rebalanced, decision = rebalance(cluster, event)
    before = np.asarray(cluster.problem.capacity)[2]
    after = np.asarray(rebalanced.problem.capacity)[2]
    np.testing.assert_allclose(after, before * event.factor, rtol=1e-6)
    assert decision.violations.ok
    assert (decision.projected.num_moved
            <= int(cluster.problem.move_budget))

    router.cluster = rebalanced
    router.assignment = np.asarray(decision.assignment)
    placed = [router.partitions_for_tier(t, apps) for t in range(len(slices))]
    names = [name for tier in placed for name in tier]
    assert sorted(names) == sorted(a.name for a in apps)
    parts = {name: n for tier in placed for name, n in tier.items()}
    assert parts == {a.name: a.num_partitions for a in apps}


# field -> (what carries it, its column in the assembled ClusterState)
_FIELDS = {
    "flops_demand": ("app", lambda c: c.problem.demand[:, 0]),
    "hbm_demand": ("app", lambda c: c.problem.demand[:, 1]),
    "num_partitions": ("app", lambda c: c.problem.tasks),
    "slo": ("app", lambda c: c.problem.slo),
    "criticality": ("app", lambda c: c.problem.criticality),
    "data_region": ("app", lambda c: c.app_region),
    "flops_capacity": ("slice", lambda c: c.problem.capacity[:, 0]),
    "hbm_capacity": ("slice", lambda c: c.problem.capacity[:, 1]),
    "task_slots": ("slice", lambda c: c.problem.task_limit),
    "regions": ("slice", lambda c: c.tier_regions),
}


@pytest.mark.parametrize("field", sorted(_FIELDS))
def test_build_cluster_carries_field(field):
    """Each StreamApp / PodSlice field lands, row for row, in the
    ClusterState column the scheduler reads it from."""
    num_regions = 6
    apps = demo_apps(24, num_regions=num_regions, seed=3)
    slices = default_slices()
    cluster = build_cluster(apps, slices, num_regions=num_regions, seed=3)
    owner, column = _FIELDS[field]
    got = np.asarray(column(cluster))
    if field == "regions":
        want = np.zeros((len(slices), num_regions), bool)
        for t, s in enumerate(slices):
            want[t, list(s.regions)] = True
    else:
        rows = apps if owner == "app" else slices
        want = np.array([getattr(r, field) for r in rows], np.float32)
    assert got.shape == want.shape
    np.testing.assert_array_equal(got.astype(want.dtype), want)
