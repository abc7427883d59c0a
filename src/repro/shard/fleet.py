"""End-to-end sharded fleet pass: partition -> batched solve -> merge ->
coordinate.

``solve_fleet`` is the scale path the global solver cannot reach: S
subproblems solve as one vmapped executable (``shard.solve``), the merged
assignment is globally feasible by construction (``shard.partition``), and
the ``FleetCoordinator`` then vets saturation and grants priced boundary
migrations.  ``balance_fleet`` wraps the same pass in the controller's
``BalanceDecision`` contract — shed caps scale the served problem, a
``PlanOutlook`` steers only the solver, the PR-4 movement budget trims the
merged mapping (``enforce_cost_budget``), and the decision is evaluated
against the real collected problem exactly like ``Sptlb.balance``.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax.numpy as jnp
import numpy as np

from repro.core import constraints, metrics
from repro.core.goals import objective as global_objective
from repro.core.hierarchy import enforce_cost_budget
from repro.core.levels import CoopConfig
from repro.core.planner import movement_cost_of
from repro.core.solver_local import SolveResult
from repro.core.sptlb import TIMEOUT_BUDGETS, BalanceDecision
from repro.shard.coordinator import SATURATION_FRAC, FleetCoordinator
from repro.shard.partition import (
    ShardedProblem,
    merge_assignment,
    partition_problem,
    plan_shards,
    stranded_apps,
)
from repro.shard.solve import ShardSolveConfig, ShardSolveResult, solve_shards
from repro.spans import count, span


@dataclasses.dataclass(frozen=True)
class FleetConfig:
    """Knobs for one sharded rebalance pass."""

    num_shards: int = 8
    # Deterministic iteration budget via the paper's timeout knobs (same
    # TIMEOUT_BUDGETS table as the global engines).
    timeout_s: int = 30
    batch_moves: int = 16
    batch_quality: float = 0.9
    tol: float = 1e-7
    seed: int = 0
    # Coordinator: detect saturated shards and grant boundary migrations.
    rebalance: bool = True
    saturation: float = SATURATION_FRAC
    migration_frac: float = 0.05

    @property
    def max_iters(self) -> int:
        return TIMEOUT_BUDGETS.get(self.timeout_s, max(64, int(self.timeout_s * 8)))


@dataclasses.dataclass
class FleetDecision:
    """Outputs of one partition -> solve -> merge -> coordinate pass."""

    assignment: np.ndarray  # i32[N] merged global mapping
    objective: float  # global objective of the merged mapping
    shard_objectives: np.ndarray  # f32[S] per-shard (padded-problem) objectives
    stranded: int  # valid apps on infeasible tiers (must be 0)
    migrations: int  # coordinator-granted boundary moves
    saturated: int  # shards over the saturation threshold
    apps_per_s: float  # valid apps / end-to-end wall-clock
    coordinator_overhead_frac: float  # coordinator share of the pass
    timings: dict
    sharded: ShardedProblem
    solve: ShardSolveResult
    coordinator: FleetCoordinator


def never_worse(problem, merged: np.ndarray) -> tuple[np.ndarray, bool]:
    """Keep a merged sharded mapping only if it does not worsen the global
    objective against the incumbent ``assignment0``.

    Each shard minimizes its own objective, but the balance terms couple
    every shard through the fleet mean, so the merge of locally better
    mappings can be globally worse.  Returns ``(mapping, reverted)``.
    """
    x0 = np.asarray(problem.assignment0)
    obj0 = float(global_objective(problem, jnp.asarray(x0)))
    obj1 = float(global_objective(problem, jnp.asarray(merged)))
    if obj1 > obj0 + 1e-9:
        return x0.copy(), True
    return merged, False


def solve_fleet(
    cluster,
    config: FleetConfig | None = None,
    *,
    move_cost: Optional[np.ndarray] = None,
    migration_budget: float = float("inf"),
    dirty_shards=None,
) -> FleetDecision:
    """One sharded rebalance pass over the cluster's current problem.

    ``dirty_shards`` (optional bool[S] mask or shard-index iterable) is the
    delta-solve path: only the named shards re-solve, the rest keep their
    incumbent mapping (``shard.solve``).  An all-dirty mask is bit-identical
    to the full pass.  Every merged mapping, full or delta, carries a
    never-worse guard (``never_worse``): the global objective is not
    shard-separable (the balance terms couple through the fleet mean), so
    shard-local improvements that worsen the global objective revert to
    the incumbent — observable as ``timings["reverted"]`` (and
    ``timings["delta_reverted"]`` for a strict-subset delta), never silent.
    """
    cfg = config if config is not None else FleetConfig()
    problem = cluster.problem
    timings = dict.fromkeys(
        ("partition_s", "solve_s", "merge_s", "coordinator_s", "total_s"), 0.0)
    with span("shard.pass", into=timings, key="total_s"):
        with span("shard.partition", into=timings, key="partition_s"):
            plan = plan_shards(cluster, cfg.num_shards)
            sharded = partition_problem(problem, plan)

        dirty = None
        if dirty_shards is not None:
            mask = np.zeros(plan.num_shards, bool)
            arr = np.asarray(dirty_shards)
            if arr.dtype == bool:
                mask[: arr.size] = arr[: plan.num_shards]
            else:
                ids = arr.astype(np.int64)
                mask[ids[(ids >= 0) & (ids < plan.num_shards)]] = True
            dirty = mask

        with span("shard.solve", into=timings, key="solve_s"):
            res = solve_shards(
                sharded,
                ShardSolveConfig(
                    max_iters=cfg.max_iters,
                    tol=cfg.tol,
                    batch_moves=cfg.batch_moves,
                    batch_quality=cfg.batch_quality,
                    seed=cfg.seed,
                ),
                dirty=dirty,
            )
        # Every lane of the batched solve iterates until the slowest solved
        # shard converges: the spread of the solved shards' iteration
        # counts is the lanes' idle share.
        lanes = res.iterations[res.solved]
        count("shard.lanes", lanes=int(lanes.size),
              iters_max=int(lanes.max(initial=0)), iters_sum=int(lanes.sum()))

        with span("shard.merge", into=timings, key="merge_s"):
            merged, reverted = never_worse(
                problem, merge_assignment(problem, sharded, res.x)
            )
            delta_reverted = reverted and dirty is not None and not dirty.all()

        with span("shard.coordinator", into=timings, key="coordinator_s"):
            coordinator = FleetCoordinator(
                cluster,
                num_shards=plan.num_shards,
                saturation=cfg.saturation,
                migration_frac=cfg.migration_frac,
                plan=plan,
            )
            moves: list = []
            if cfg.rebalance:
                moves = coordinator.plan_migrations(
                    problem, merged, move_cost=move_cost, cost_budget=migration_budget
                )
                for a, t in moves:
                    merged[a] = t

    total_s = timings["total_s"] = max(timings["total_s"], 1e-9)
    counters = coordinator.counters()
    timings.update(
        solved_shards=int(res.solved.sum()) if res.solved.size else plan.num_shards,
        reverted=reverted,
        delta_reverted=delta_reverted,
    )
    return FleetDecision(
        assignment=merged,
        objective=float(global_objective(problem, jnp.asarray(merged))),
        shard_objectives=res.objective,
        stranded=stranded_apps(problem, merged),
        migrations=len(moves),
        saturated=int(counters["saturated_shards"]),
        apps_per_s=float(int(np.asarray(problem.valid).sum()) / total_s),
        coordinator_overhead_frac=timings["coordinator_s"] / total_s,
        timings=timings,
        sharded=sharded,
        solve=res,
        coordinator=coordinator,
    )


def balance_fleet(
    cluster,
    *,
    fleet: FleetConfig | None = None,
    coop: CoopConfig | None = None,
    dirty_shards=None,
) -> BalanceDecision:
    """The sharded pass under the controller's ``BalanceDecision`` contract.

    Mirrors ``Sptlb.balance``'s served/steered split: an active shed plan
    scales what the fleet really serves (solve AND evaluation), a plan
    outlook only steers the solver, and the movement budget prices + trims
    the merged mapping via the same ``enforce_cost_budget`` the engines
    share.  ``cooperation`` is None — the coordinator, not the bus, vetted
    this pass (its counters ride ``solve.extra``).
    """
    cfg = fleet if fleet is not None else FleetConfig()
    knobs = coop if coop is not None else CoopConfig()
    base_cluster = cluster
    shed = knobs.shed
    if shed is not None and shed.active:
        base_cluster = dataclasses.replace(
            cluster, problem=shed.apply(cluster.problem)
        )
    solve_cluster = base_cluster
    plan = knobs.plan
    if plan is not None and plan.active:
        solve_cluster = dataclasses.replace(
            base_cluster, problem=plan.apply(base_cluster.problem)
        )

    budget = knobs.cost_budget if knobs.cost_budget is not None else float("inf")
    balance_timings: dict = {}
    with span("controller.solve", into=balance_timings, key="solve_s"):
        fd = solve_fleet(
            solve_cluster,
            cfg,
            move_cost=knobs.move_cost,
            migration_budget=budget,
            dirty_shards=dirty_shards,
        )
        problem = base_cluster.problem
        res = SolveResult(
            assignment=jnp.asarray(fd.assignment),
            iterations=int(max(int(fd.solve.iterations.max()), 1)),
            converged=bool(fd.solve.converged.all()),
            objective=float(global_objective(problem, jnp.asarray(fd.assignment))),
            num_moved=int(
                np.sum(fd.assignment != np.asarray(problem.assignment0))
            ),
            solve_time_s=fd.timings["total_s"],
            extra={
                "sharded": {
                    "num_shards": fd.sharded.num_shards,
                    "app_bucket": fd.sharded.app_bucket,
                    "tier_bucket": fd.sharded.tier_bucket,
                    "stranded": fd.stranded,
                    "migrations": fd.migrations,
                    "saturated": fd.saturated,
                    "apps_per_s": fd.apps_per_s,
                    "coordinator_overhead_frac": fd.coordinator_overhead_frac,
                    **fd.timings,
                }
            },
        )
        timings: dict = {}
        res = enforce_cost_budget(
            base_cluster,
            res,
            np.asarray(base_cluster.problem.assignment0),
            knobs.move_cost,
            budget,
            (),
            timings,
        )
    with span("controller.evaluate", into=balance_timings, key="evaluate_s"):
        movement = timings.get(
            "movement_cost",
            movement_cost_of(res.assignment, problem.assignment0, knobs.move_cost),
        )
        decision = BalanceDecision(
            assignment=res.assignment,
            projected=metrics.projected_metrics(problem, res.assignment),
            violations=constraints.validate(problem, res.assignment),
            difference_to_balance=metrics.difference_to_balance(problem, res.assignment),
            network_p99_ms=metrics.network_p99_ms(cluster, res.assignment),
            solve=res,
            cooperation=None,
            movement_cost=movement,
            budget_trimmed=int(timings.get("budget_trimmed", 0)),
        )
    res.extra["balance_timings"] = balance_timings
    return decision
