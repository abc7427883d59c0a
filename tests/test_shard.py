"""Sharded fleet solver (PR 8): partition/merge bijection, single-shard
golden parity with the global solver, decomposable multi-shard parity,
coordinator vetting + priced boundary migrations, and the controller's
``shards`` routing."""

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    LocalSearchConfig,
    Sptlb,
    generate_cluster,
    pad_problem,
    solve_local,
)
from repro.core.controller import (BalanceController, ControllerConfig,
                                   TickInput)
from repro.core.goals import objective
from repro.core.levels import CoopConfig, Proposal, level_factory
from repro.core.problem import tier_loads
from repro.shard import (
    FleetConfig,
    FleetCoordinator,
    balance_fleet,
    merge_assignment,
    partition_problem,
    plan_shards,
    shard_utilization,
    solve_fleet,
    solve_shards,
    stranded_apps,
    synthetic_fleet,
    tier_anchors,
)
from _hypothesis_compat import hypothesis, st


@pytest.fixture(scope="module")
def cluster():
    return generate_cluster(num_apps=120, seed=3)


# -- partitioning ------------------------------------------------------------


def test_plan_shards_covers_every_tier_exactly_once(cluster):
    plan = plan_shards(cluster, 3)
    T = cluster.problem.num_tiers
    all_tiers = np.sort(np.concatenate(plan.shard_tiers))
    np.testing.assert_array_equal(all_tiers, np.arange(T))
    x0 = np.asarray(cluster.problem.assignment0)
    np.testing.assert_array_equal(plan.app_shard, plan.tier_shard[x0])
    # every shard owns >= 1 tier; S clamps to [1, T]
    assert all(len(ts) >= 1 for ts in plan.shard_tiers)
    assert plan_shards(cluster, 10 * T).num_shards == T
    assert plan_shards(cluster, 0).num_shards == 1


def test_tier_anchors_follow_region_arcs():
    tr = np.zeros((4, 8), bool)
    tr[0, 2:5] = True  # arc starting at region 2
    tr[1, 6:] = True
    tr[1, 0] = True  # wrap-around arc starting at 6
    tr[2, :] = True  # degenerate: everywhere -> 0
    np.testing.assert_array_equal(tier_anchors(tr), [2, 6, 0, 0])


@hypothesis.given(
    st.integers(40, 160), st.integers(1, 6), st.integers(0, 5)
)
@hypothesis.settings(max_examples=12, deadline=None, derandomize=True)
def test_partition_merge_is_a_bijection(num_apps, num_shards, seed):
    """Every app lands in exactly one shard slot, and merging the stacked
    local incumbents returns the global assignment0 bit-for-bit."""
    cl = generate_cluster(num_apps=num_apps, seed=seed)
    plan = plan_shards(cl, num_shards)
    sharded = partition_problem(cl.problem, plan)
    ids = sharded.app_ids[sharded.app_ids >= 0]
    np.testing.assert_array_equal(np.sort(ids), np.arange(num_apps))
    local_x0 = np.asarray(sharded.problems.assignment0)
    merged = merge_assignment(cl.problem, sharded, local_x0)
    np.testing.assert_array_equal(merged, np.asarray(cl.problem.assignment0))
    assert stranded_apps(cl.problem, merged) == 0


def test_partition_pads_inert_tiers(cluster):
    plan = plan_shards(cluster, 4)
    sharded = partition_problem(cluster.problem, plan)
    widths = [len(ts) for ts in plan.shard_tiers]
    assert sharded.tier_bucket == max(widths)
    slo_allowed = np.asarray(sharded.problems.slo_allowed)
    avoid = np.asarray(sharded.problems.avoid)
    for s, w in enumerate(widths):
        assert (sharded.tier_ids[s, w:] == -1).all()
        # inert tiers: no SLO class allowed, avoided by every real app
        # (pad_problem's inert app rows are neutralized by valid=False)
        assert not slo_allowed[s, w:].any()
        real = sharded.app_ids[s] >= 0
        assert avoid[s, real, w:].all()


# -- solve parity ------------------------------------------------------------


def test_single_shard_solve_matches_global_golden():
    """S=1 partitioning is the identity (tiers sorted ascending, defaults
    matching ``LocalSearchConfig``), so the sharded pass must reproduce the
    global solver's assignment exactly — the golden parity pin."""
    cl = generate_cluster(num_apps=100, seed=0)
    plan = plan_shards(cl, 1)
    sharded = partition_problem(cl.problem, plan)
    res = solve_shards(sharded)
    merged = merge_assignment(cl.problem, sharded, res.x)

    ref = solve_local(
        pad_problem(cl.problem),
        LocalSearchConfig(max_iters=256, batch_moves=16),
    )
    n = cl.problem.num_apps
    np.testing.assert_array_equal(merged, np.asarray(ref.assignment)[:n])
    assert float(objective(cl.problem, jnp.asarray(merged))) == pytest.approx(
        float(ref.objective), rel=1e-6
    )


def test_multi_shard_parity_when_problem_decomposes(cluster):
    """With feasibility confined to each app's home shard the global and
    sharded searches range over the same space — objectives must agree
    within a small tolerance and both must improve on the incumbent."""
    p = cluster.problem
    plan = plan_shards(cluster, 2)
    cross = plan.tier_shard[None, :] != plan.app_shard[:, None]
    p2 = dataclasses.replace(p, avoid=jnp.asarray(np.asarray(p.avoid) | cross))

    sharded = partition_problem(p2, plan)
    res = solve_shards(sharded)
    merged = merge_assignment(p2, sharded, res.x)
    obj_sharded = float(objective(p2, jnp.asarray(merged)))

    ref = solve_local(
        pad_problem(p2), LocalSearchConfig(max_iters=256, batch_moves=16)
    )
    obj_global = float(ref.objective)
    obj_start = float(objective(p2, p2.assignment0))

    assert stranded_apps(p2, merged) == 0
    assert obj_sharded < obj_start
    # Per-shard solves balance against shard-local tier sets, so the merged
    # objective tracks (not equals) the global optimum on the same space.
    assert obj_sharded == pytest.approx(obj_global, rel=0.15, abs=1e-3)
    # no-cross-shard-demand invariant: the merged mapping never crosses
    x0 = np.asarray(p2.assignment0)
    moved = merged != x0
    assert (plan.tier_shard[merged[moved]] == plan.app_shard[moved]).all()


def test_solve_fleet_end_to_end(cluster):
    fd = solve_fleet(cluster, FleetConfig(num_shards=3, timeout_s=30))
    p = cluster.problem
    assert fd.stranded == 0
    assert fd.objective <= float(objective(p, p.assignment0)) + 1e-6
    assert fd.apps_per_s > 0
    assert 0.0 <= fd.coordinator_overhead_frac <= 1.0
    assert set(fd.timings) == {
        "partition_s",
        "solve_s",
        "merge_s",
        "coordinator_s",
        "total_s",
        "solved_shards",
        "reverted",
        "delta_reverted",
    }
    assert fd.timings["solved_shards"] == 3
    assert fd.timings["delta_reverted"] is False


# -- coordinator -------------------------------------------------------------


def test_premask_blocks_cross_shard_but_never_home(cluster):
    coord = FleetCoordinator(cluster, num_shards=3)
    mask = coord.premask(cluster.problem)
    n = cluster.problem.num_apps
    x0 = np.asarray(cluster.problem.assignment0)
    assert mask.shape == (n, cluster.problem.num_tiers)
    assert not mask[np.arange(n), x0].any()  # home tier always open
    cross = coord.plan.tier_shard[None, :] != coord.plan.app_shard[:, None]
    np.testing.assert_array_equal(mask, cross)


def test_vet_rejects_ungranted_cross_shard_moves(cluster):
    coord = FleetCoordinator(cluster, num_shards=2)
    plan = coord.plan
    x0 = np.asarray(cluster.problem.assignment0).astype(np.int64)
    # one same-shard move, one cross-shard move
    same = int(np.where(plan.app_shard == 0)[0][0])
    cross = int(np.where(plan.app_shard == 0)[0][1])
    x = x0.copy()
    x[same] = int(plan.shard_tiers[0][-1])
    x[cross] = int(plan.shard_tiers[1][0])
    prop = Proposal(
        x=x, x0=x0, candidates=np.asarray([same, cross], np.int64)
    )
    rejected = coord.vet(prop)
    np.testing.assert_array_equal(rejected, [cross])
    assert coord.counters()["rejected_cross_shard"] == 1
    # a standing grant flips the verdict
    coord._granted[cross, x[cross]] = True
    assert coord.vet(prop).size == 0


def test_plan_migrations_prices_and_grants(cluster):
    p = cluster.problem
    x0 = np.asarray(p.assignment0)
    plan = plan_shards(cluster, 2)
    util = shard_utilization(plan, p, x0)
    threshold = float(util.max()) - 1e-6  # exactly one shard saturated
    coord = FleetCoordinator(cluster, plan=plan, saturation=threshold)
    moves = coord.plan_migrations(p, x0)
    assert moves, "saturated shard must shed at least one donor"
    hot = int(np.argmax(util))
    feas = np.asarray(p.feasible_mask())
    for a, t in moves:
        assert plan.app_shard[a] == hot
        assert plan.tier_shard[t] != hot
        assert feas[a, t]
    assert coord.counters()["granted"] == len(moves)
    # granted moves now pass the bus vet
    x = x0.astype(np.int64).copy()
    apps = np.asarray([a for a, _ in moves], np.int64)
    x[apps] = [t for _, t in moves]
    assert coord.vet(Proposal(x=x, x0=x0.astype(np.int64), candidates=apps)).size == 0
    # a zero budget buys zero moves
    coord0 = FleetCoordinator(cluster, plan=plan, saturation=threshold)
    assert coord0.plan_migrations(p, x0, cost_budget=0.0) == []
    # max_moves caps the grant count
    coord1 = FleetCoordinator(cluster, plan=plan, saturation=threshold)
    assert len(coord1.plan_migrations(p, x0, max_moves=1)) <= 1


def test_fleet_level_registered_on_the_bus(cluster):
    assert level_factory("fleet") is FleetCoordinator
    balancer = Sptlb(cluster)
    decision = balancer.balance(
        "local",
        timeout_s=30,
        config=CoopConfig(levels=("region", "host", "fleet")),
    )
    assert decision.cooperation is not None
    assert "fleet" in decision.cooperation.timings.levels
    assert stranded_apps(cluster.problem, np.asarray(decision.assignment)) == 0


# -- controller + BalanceDecision contract -----------------------------------


def test_balance_fleet_decision_contract(cluster):
    decision = balance_fleet(
        cluster, fleet=FleetConfig(num_shards=2, timeout_s=30)
    )
    assert decision.cooperation is None
    sharded = decision.solve.extra["sharded"]
    assert sharded["num_shards"] == 2
    assert sharded["stranded"] == 0
    assert decision.solve.iterations >= 1  # never reads as a dead solver
    assert "balance_timings" in decision.solve.extra
    assert decision.movement_cost >= 0.0


def test_balance_fleet_respects_zero_movement_budget(cluster):
    n = cluster.problem.num_apps
    decision = balance_fleet(
        cluster,
        fleet=FleetConfig(num_shards=2, timeout_s=30),
        coop=CoopConfig(cost_budget=0.0, move_cost=np.ones(n, np.float32)),
    )
    np.testing.assert_array_equal(
        np.asarray(decision.assignment),
        np.asarray(cluster.problem.assignment0),
    )
    assert decision.movement_cost == pytest.approx(0.0)


def test_controller_routes_through_sharded_path(cluster):
    ctl = BalanceController(
        cluster,
        ControllerConfig(
            shards=2,
            timeout_s=30,
            cooldown_rounds=1,
            trigger_d2b=0.0,
            trigger_over_ideal=0.0,
        ),
    )
    ev = ctl.step(TickInput()).event
    assert ev.triggered and ev.applied
    assert ctl.audit()["rebalances"] == 1
    assert (
        stranded_apps(
            ctl.cluster.problem, np.asarray(ctl.cluster.problem.assignment0)
        )
        == 0
    )


# -- synthetic fleet generator ----------------------------------------------


def test_synthetic_fleet_is_well_formed():
    cl = synthetic_fleet(5_000, num_tiers=12, num_regions=8, seed=1)
    p = cl.problem
    assert p.num_apps == 5_000 and p.num_tiers == 12
    assert bool(np.asarray(p.valid).all())
    assert stranded_apps(p, np.asarray(p.assignment0)) == 0
    assert (np.asarray(p.capacity) > 0).all()
    util, _ = tier_loads(p, np.asarray(p.assignment0))
    frac = np.asarray(util) / np.asarray(p.capacity)
    assert 0.2 < float(frac.mean()) < 0.9  # near the util_target calibration
