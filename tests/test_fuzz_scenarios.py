"""Invariant fuzzing over random trajectories (ISSUE 7 satellite).

Six fuzz surfaces, >= 200 random trajectories total, each asserting the
control plane's hard invariants — the properties the regression gate pins
on two curated scenarios, checked here across a randomized family:

  * full overload sim trajectories (random surge/flash/churn mixes through
    ``run_scenario(utility=True)``): the movement budget is never overrun
    (shed churn included), admission never admits an app that does not fit
    its priced tier, and the live population never escapes the pool;
  * admission-gate decision trajectories (random arrival streams priced
    against randomly loaded fleets): every ADMIT fits the named tier at
    the admitted cap under hard capacity, degraded caps respect the
    config floor, and DEFER backoff is monotone per app;
  * cooperation passes over randomly perturbed clusters with the premask
    on: zero region rejections and zero resident-set overflows, whatever
    the demand skew;
  * sharded fleet passes (PR 8): partition -> merge stays a bijection,
    the merged mapping strands nobody and never worsens the incumbent,
    whatever the shard count or demand skew;
  * measured-latency trajectories (PR 10): whatever random link weather a
    network scenario throws (degrades, detours, jitter storms), the
    measured netlat+host stack never commits a move whose destination
    tier has a pair over its live p99 budget.

``FUZZ_TRAJECTORIES`` scales every surface proportionally: unset (CI) it
keeps the per-surface defaults below (256 total); a nightly-style run sets
e.g. ``FUZZ_TRAJECTORIES=2000`` for ~9x the coverage.  Values at or below
the default total are ignored — the knob only ever adds examples.

Runs under the ``_hypothesis_compat`` fallback (deterministic seeded
examples) when hypothesis is not installed — tier-1 needs no optional
packages.
"""

import dataclasses
import os

import jax.numpy as jnp
import numpy as np

from _hypothesis_compat import hypothesis, st
from repro.core import CoopConfig, Sptlb, generate_cluster
from repro.core.constraints import FEAS_TOL
from repro.core.goals import objective
from repro.core.problem import tier_loads
from repro.shard import (
    merge_assignment,
    partition_problem,
    plan_shards,
    solve_shards,
    stranded_apps,
)
from repro.shard.fleet import never_worse
from repro.shard.solve import ShardSolveConfig
from repro.sim import Scenario, WorkloadConfig, run_scenario
from repro.sim.events import CapacityScale, ChurnRate, FlashCrowd, JitterStorm, LinkDegrade
from repro.streams.admission import AdmissionController, AdmissionState

# Per-surface example counts at the CI default, before the env knob.
_BASE_SIM, _BASE_ADMISSION, _BASE_PREMASK, _BASE_SHARD = 48, 120, 40, 24
_BASE_SERVICE, _BASE_NETLAT = 24, 8
_BASE_TOTAL = (
    _BASE_SIM + _BASE_ADMISSION + _BASE_PREMASK + _BASE_SHARD + _BASE_SERVICE + _BASE_NETLAT
)
_SCALE = max(1.0, int(os.environ.get("FUZZ_TRAJECTORIES", "0")) / _BASE_TOTAL)

# ---------------------------------------------------------------------------
# 1. full overload trajectories (48 examples x 5 ticks, one shape bucket)
# ---------------------------------------------------------------------------

N_SIM_TRAJECTORIES = int(round(_BASE_SIM * _SCALE))


def _random_overload_scenario(seed: int) -> Scenario:
    """A small random overload scenario: every draw keeps the same pool
    size (one jit bucket for all examples) but randomizes the pressure —
    surge rates, flash magnitude/targets, capacity loss, and the budget."""
    rng = np.random.default_rng(seed)
    events = []
    if rng.random() < 0.7:
        events.append(
            ChurnRate(
                at=int(rng.integers(0, 2)),
                arrival_rate=float(rng.uniform(1.0, 4.0)),
                retire_rate=float(rng.uniform(0.0, 0.01)),
            )
        )
    if rng.random() < 0.7:
        events.append(
            FlashCrowd(
                at=int(rng.integers(1, 4)),
                frac=float(rng.uniform(0.2, 0.5)),
                magnitude=float(rng.uniform(2.0, 8.0)),
                crit_below=float(rng.uniform(0.3, 1.0)) if rng.random() < 0.5 else None,
            )
        )
    if rng.random() < 0.4:
        events.append(
            CapacityScale(
                at=int(rng.integers(1, 4)),
                tier=int(rng.integers(0, 5)),
                scale=float(rng.uniform(0.4, 0.8)),
                announced=False,
            )
        )
    return Scenario(
        name=f"fuzz_overload_{seed}",
        description="",
        ticks=5,
        num_apps=20,
        seed=seed,
        overload=True,
        pool_frac=1.6,
        util_scale=float(rng.uniform(0.8, 1.1)),
        arrival_rate=float(rng.uniform(0.5, 2.5)),
        retire_rate=float(rng.uniform(0.0, 0.02)),
        workload=WorkloadConfig(
            period=8,
            diurnal_amp=float(rng.uniform(0.0, 0.3)),
            burst_sigma=float(rng.uniform(0.0, 0.2)),
        ),
        events=tuple(events),
        move_budget=float(rng.uniform(10.0, 60.0)),
    )


@hypothesis.settings(max_examples=N_SIM_TRAJECTORIES, deadline=None)
@hypothesis.given(st.integers(0, 10_000))
def test_fuzz_overload_trajectories_hold_invariants(seed):
    sc = _random_overload_scenario(seed)
    report = run_scenario(sc, utility=True)
    summary = report.summary()
    audit = summary["audit"]
    # Movement budget is a hard ceiling: applied moves + shed churn,
    # lifetime, never exceed it (budget_limited ticks are fine — the
    # budget binding is the design working, overrunning it is the bug).
    assert audit["movement_cost"] <= sc.move_budget + 1e-6, (seed, audit)
    assert summary["budget_overruns"] == 0, (seed, summary)
    # Admission never admitted an app that did not fit its priced tier.
    assert summary["infeasible_admissions"] == 0, (seed, summary)
    # The live population stays inside the pool (shapes are static; an
    # escape means the admission overlay corrupted the valid mask).
    assert all(t.live_apps <= sc.max_apps for t in report.ticks), seed
    # Deferred accounting never goes negative / beyond the pool.
    assert 0 <= summary.get("deferred_backlog", 0) <= sc.max_apps, seed


# ---------------------------------------------------------------------------
# 2. admission-gate decision trajectories (120 examples, pure numpy, fast)
# ---------------------------------------------------------------------------

N_ADMISSION_TRAJECTORIES = int(round(_BASE_ADMISSION * _SCALE))
_BASE_CLUSTER = None


def _base_problem():
    global _BASE_CLUSTER
    if _BASE_CLUSTER is None:
        _BASE_CLUSTER = generate_cluster(num_apps=64, seed=3)
    return _BASE_CLUSTER.problem


@hypothesis.settings(max_examples=N_ADMISSION_TRAJECTORIES, deadline=None)
@hypothesis.given(st.integers(0, 10_000))
def test_fuzz_admission_never_admits_infeasible(seed):
    rng = np.random.default_rng(seed ^ 0xAD317)
    base = _base_problem()
    # Random fleet pressure: scale demand so some trajectories start with
    # headroom and some start saturated.
    scale = float(rng.uniform(0.6, 1.6))
    problem = dataclasses.replace(base, demand=base.demand * jnp.float32(scale))
    gate = AdmissionController()
    mode = str(rng.choice(["normal", "conservative", "safe"]))
    last_retry: dict[str, int] = {}
    for step in range(rng.integers(4, 10)):
        demand = rng.uniform(0.0, 0.08, size=problem.num_resources)
        key = f"fuzz{seed}_{step % 3}"  # repeats exercise the backoff
        d = gate.decide(
            problem,
            demand=demand,
            tasks=float(rng.integers(1, 12)),
            slo=int(rng.integers(0, 3)),
            criticality=float(rng.uniform(0.0, 1.0)),
            key=key,
            mode=mode,
            now=step,
        )
        if d.admitted:
            util, tier_tasks = tier_loads(problem, problem.assignment0)
            util = np.asarray(util, np.float64)
            cap = np.asarray(problem.capacity, np.float64)
            klim = np.asarray(problem.task_limit, np.float64)
            # The priced tier holds the app at the admitted cap under hard
            # capacity — the invariant the sim recount also pins.
            assert d.tier >= 0, d
            assert 0.0 < d.cap <= 1.0, d
            fits = util[d.tier] + d.cap * demand <= cap[d.tier] + FEAS_TOL
            # Marginal contract: fit is required on every resource the
            # app consumes (a pre-existing overflow on a resource it
            # demands none of is not this admission's doing).
            assert fits[demand > 0.0].all(), (seed, step, d)
            if d.state is AdmissionState.ADMIT_DEGRADED:
                assert mode == "normal", d
                assert d.cap >= gate.config.min_degraded_cap - FEAS_TOL, d
                assert d.declared_utility > 0.0, d
            last_retry.pop(key, None)
        elif d.state is AdmissionState.DEFER:
            assert 1 <= d.retry_after <= gate.config.backoff_cap, d
            # Exponential backoff is monotone per app key until admission
            # or the cap.
            prev = last_retry.get(key, 0)
            assert d.retry_after >= prev or d.retry_after == gate.config.backoff_cap, d
            last_retry[key] = d.retry_after
        else:
            assert d.state is AdmissionState.REJECT
            assert mode == "safe", d
            assert d.reason.startswith("safe-mode"), d
    audit = gate.audit()
    assert audit["decisions"] == len(gate.log)
    total = audit["admit"] + audit["admit_degraded"] + audit["defer"] + audit["reject"]
    assert total == audit["decisions"]


# ---------------------------------------------------------------------------
# 3. premask cooperation passes (40 examples, shared cluster/bucket)
# ---------------------------------------------------------------------------

N_PREMASK_TRAJECTORIES = int(round(_BASE_PREMASK * _SCALE))
_PREMASK_CLUSTER = None


def _premask_cluster():
    global _PREMASK_CLUSTER
    if _PREMASK_CLUSTER is None:
        _PREMASK_CLUSTER = generate_cluster(num_apps=96, seed=11)
    return _PREMASK_CLUSTER


def _unpackable_residents(cluster) -> int:
    """Residents whose tier's *initial* membership fails host FFD packing.

    The no-overflow contract is conditioned on a packable start: a seed
    state whose residents already fail host packing is pre-existing
    overload the machinery tolerates (their placement is the fallback),
    not a returner gap — overflow beyond this count is the bug."""
    from repro.core.hierarchy import HostScheduler

    host = HostScheduler(cluster)
    x0 = np.asarray(cluster.problem.assignment0)
    return sum(
        len(host.check_tier(t, np.where(x0 == t)[0])) for t in range(cluster.problem.num_tiers)
    )


@hypothesis.settings(max_examples=N_PREMASK_TRAJECTORIES, deadline=None)
@hypothesis.given(st.integers(0, 10_000))
def test_fuzz_premask_no_rejections_no_resident_overflow(seed):
    rng = np.random.default_rng(seed ^ 0x93A5)
    cluster = _premask_cluster()
    # Random per-app demand skew (same shapes, same bucket, new pressure).
    skew = rng.uniform(0.5, 1.8, size=(cluster.problem.num_apps, 1))
    problem = dataclasses.replace(
        cluster.problem, demand=cluster.problem.demand * jnp.asarray(skew, jnp.float32)
    )
    skewed = dataclasses.replace(cluster, problem=problem)
    pre_existing = _unpackable_residents(skewed)
    decision = Sptlb(skewed).balance("local", timeout_s=4, config=CoopConfig(premask=True))
    tm = decision.cooperation.timings
    # The premask contract, fuzzed: no region-infeasible proposal ever
    # reaches the region level, whatever the skew.
    assert tm["region_rejections"] == 0, (seed, dict(tm))
    # The host packer never strands more residents than the skew made
    # unpackable before cooperation even ran; on a packable start
    # (pre_existing == 0, most draws) this is the strict zero contract.
    assert tm["resident_overflows"] <= pre_existing, (seed, dict(tm))
    assert decision.violations.ok, seed


# ---------------------------------------------------------------------------
# 4. sharded fleet passes (24 examples, shared cluster, <= 5 shape buckets)
# ---------------------------------------------------------------------------

N_SHARD_TRAJECTORIES = int(round(_BASE_SHARD * _SCALE))
_SHARD_CLUSTER = None


def _shard_cluster():
    global _SHARD_CLUSTER
    if _SHARD_CLUSTER is None:
        _SHARD_CLUSTER = generate_cluster(num_apps=96, seed=7)
    return _SHARD_CLUSTER


@hypothesis.settings(max_examples=N_SHARD_TRAJECTORIES, deadline=None)
@hypothesis.given(st.integers(0, 10_000))
# Seed 1379 merges shard-local improvements into a globally worse mapping
# (5717.29 against an incumbent of 5712.32): the never-worse guard of a
# full pass must revert it.
@hypothesis.example(1379)
def test_fuzz_sharded_passes_hold_invariants(seed):
    rng = np.random.default_rng(seed ^ 0x54A2D)
    cluster = _shard_cluster()
    # Random per-app demand skew; shapes stay fixed so at most one compile
    # per shard count (S in 1..5 -> <= 5 (S, Nb, Tb) buckets).
    skew = rng.uniform(0.5, 1.8, size=(cluster.problem.num_apps, 1))
    problem = dataclasses.replace(
        cluster.problem, demand=cluster.problem.demand * jnp.asarray(skew, jnp.float32)
    )
    skewed = dataclasses.replace(cluster, problem=problem)
    num_shards = int(rng.integers(1, 6))

    plan = plan_shards(skewed, num_shards)
    sharded = partition_problem(problem, plan)
    # Bijection: every app in exactly one slot; merged incumbents are the
    # global incumbents bit-for-bit.
    ids = sharded.app_ids[sharded.app_ids >= 0]
    assert np.array_equal(np.sort(ids), np.arange(problem.num_apps)), seed
    identity = merge_assignment(problem, sharded, np.asarray(sharded.problems.assignment0))
    assert np.array_equal(identity, np.asarray(problem.assignment0)), seed

    res = solve_shards(sharded, ShardSolveConfig(max_iters=32))
    raw = merge_assignment(problem, sharded, res.x)
    # Hard invariants of the merge: nobody stranded and no app left its
    # home shard (cross-shard is coordinator-only).
    assert stranded_apps(problem, raw) == 0, (seed, num_shards)
    assert (plan.tier_shard[raw] == plan.app_shard).all(), (seed, num_shards)
    # The pass (solve_fleet's guard over the merge) never worsens the
    # incumbent; a revert is exactly the incumbent.
    merged, reverted = never_worse(problem, raw)
    obj0 = float(objective(problem, problem.assignment0))
    assert float(objective(problem, jnp.asarray(merged))) <= obj0 + 1e-4, seed
    if reverted:
        assert np.array_equal(merged, np.asarray(problem.assignment0)), seed
    else:
        assert merged is raw, seed


# ---------------------------------------------------------------------------
# 5. service event streams (PR 9): ingestion integrity under random bursts
# ---------------------------------------------------------------------------

N_SERVICE_TRAJECTORIES = int(round(_BASE_SERVICE * _SCALE))
_SERVICE_CLUSTER = None


def _service_cluster():
    global _SERVICE_CLUSTER
    if _SERVICE_CLUSTER is None:
        _SERVICE_CLUSTER = generate_cluster(num_apps=48, seed=11)
    return _SERVICE_CLUSTER


@hypothesis.settings(max_examples=N_SERVICE_TRAJECTORIES, deadline=None)
@hypothesis.given(st.integers(0, 10_000))
def test_fuzz_service_event_streams_hold_integrity(seed):
    """Random event bursts through the ServiceLoop: whatever mix of
    telemetry, churn, capacity, advisory, and fault events arrives between
    ticks, no event is dropped and every app's applied-sequence log is
    exactly the submission order of the events that touched it."""
    from repro.core.planner import CAPACITY, Advisory
    from repro.service import (AdvisoryBatch, AppArrival, AppDeparture,
                               CapacityUpdate, FaultSignal, ServiceLoop,
                               TelemetryDelta)

    rng = np.random.default_rng(seed ^ 0x5E21CE)
    cluster = _service_cluster()
    loop = ServiceLoop(cluster)
    demand = np.asarray(cluster.problem.demand, np.float64)
    tasks = np.asarray(cluster.problem.tasks, np.float64)
    slo = np.asarray(cluster.problem.slo)
    num_apps = demand.shape[0]
    num_tiers = np.asarray(cluster.problem.capacity).shape[0]
    live = set(range(num_apps))
    expected: dict[int, list[int]] = {}

    def submit(event, touched):
        seq = loop.submit(event)
        for n in touched:
            expected.setdefault(int(n), []).append(seq)

    for tick in range(4):
        for _ in range(int(rng.integers(0, 4))):
            roll = rng.random()
            if roll < 0.5 and live:
                ids = rng.choice(sorted(live), size=min(len(live), int(rng.integers(1, 8))), replace=False)
                scale = rng.uniform(0.6, 1.6, size=(ids.size, 1))
                submit(
                    TelemetryDelta(
                        app_ids=tuple(int(n) for n in ids),
                        demand=demand[ids] * scale,
                        tasks=tasks[ids] * rng.uniform(0.8, 1.2),
                        collected_at=tick,
                    ),
                    ids,
                )
            elif roll < 0.65 and len(live) > 4:
                gone = int(rng.choice(sorted(live)))
                live.discard(gone)
                submit(AppDeparture(app_id=gone), [gone])
            elif roll < 0.8 and len(live) < num_apps:
                back = int(rng.choice(sorted(set(range(num_apps)) - live)))
                live.add(back)
                submit(
                    AppArrival(
                        app_id=back, demand=demand[back] * rng.uniform(0.5, 1.5),
                        tasks=float(tasks[back]), slo=int(slo[back]),
                        tier=int(rng.integers(0, num_tiers)),
                    ),
                    [back],
                )
            elif roll < 0.9:
                submit(
                    AdvisoryBatch(advisories=(
                        Advisory(at=tick + int(rng.integers(2, 9)),
                                 kind=CAPACITY,
                                 scale=float(rng.uniform(0.7, 1.0))),)),
                    [],
                )
            else:
                submit(FaultSignal(source="fuzz", until=tick + 1), [])
        loop.step(tick)

    assert loop.dropped_events == 0, seed
    assert loop.applied_events == loop.submitted, seed
    # Per-app integrity: the log is the submission order, verbatim — no
    # drop, no duplicate, no reorder; strictly increasing by construction.
    assert loop.shadow.applied_seq == expected, seed
    for seqs in loop.shadow.applied_seq.values():
        assert all(a < b for a, b in zip(seqs, seqs[1:])), seed


# ---------------------------------------------------------------------------
# 6. measured-latency trajectories (PR 10): random link weather, one bucket
# ---------------------------------------------------------------------------

N_NETLAT_TRAJECTORIES = int(round(_BASE_NETLAT * _SCALE))


def _random_network_scenario(seed: int) -> Scenario:
    """A small random network_degraded scenario: the pool shape stays
    fixed (one jit bucket) while the link weather — which pairs degrade,
    how hard, whether a detour is one-directional, whether a jitter storm
    fattens every tail — is drawn fresh per example.  Degrade factors stay
    under the sketch bank's plausibility jump limit, as real detours do."""
    rng = np.random.default_rng(seed ^ 0x9E7147)
    t0 = int(rng.integers(1, 3))
    events = []
    for _ in range(int(rng.integers(1, 4))):
        src, dst = (int(r) for r in rng.choice(5, size=2, replace=False))
        events.append(
            LinkDegrade(
                at=t0,
                src=src,
                dst=dst,
                factor=float(rng.uniform(1.4, 2.4)),
                symmetric=bool(rng.random() < 0.7),
            )
        )
    if rng.random() < 0.5:
        events.append(
            JitterStorm(at=t0 + 1, ticks=3, sigma=float(rng.uniform(0.2, 0.5)), seed=seed)
        )
    return Scenario(
        name=f"fuzz_network_{seed}",
        description="",
        ticks=6,
        num_apps=24,
        seed=seed,
        netlat=True,
        workload=WorkloadConfig(period=8, diurnal_amp=0.2, burst_sigma=0.1),
        events=tuple(events),
    )


@hypothesis.settings(max_examples=N_NETLAT_TRAJECTORIES, deadline=None)
@hypothesis.given(st.integers(0, 10_000))
def test_fuzz_measured_stack_never_exceeds_live_budget(seed):
    from repro.sim.harness import SIM_CONTROLLER

    sc = _random_network_scenario(seed)
    cfg = dataclasses.replace(
        SIM_CONTROLLER,
        coop=dataclasses.replace(SIM_CONTROLLER.coop, levels=("netlat", "host")),
    )
    report = run_scenario(sc, config=cfg, netlat=True)
    summary = report.summary()
    # The measured-latency hard invariant: zero committed moves whose
    # destination tier holds a pair over its live p99 budget, whatever
    # the weather.  (The static stack leaks these by design — that contrast
    # is the regression gate's job; this surface pins the measured stack.)
    assert summary["budget_exceeding_moves"] == 0, (seed, summary)
    # The plane calibrated (budgets were real, not the inert fallback) and
    # the run kept its feasibility contract.
    assert report.extra["netlat"]["calibrated"], seed


def test_fuzz_counts_cover_the_contract():
    """The satellite's floor: at least 200 random trajectories total (and
    the env knob only ever scales the coverage up)."""
    total = (
        N_SIM_TRAJECTORIES
        + N_ADMISSION_TRAJECTORIES
        + N_PREMASK_TRAJECTORIES
        + N_SHARD_TRAJECTORIES
        + N_SERVICE_TRAJECTORIES
        + N_NETLAT_TRAJECTORIES
    )
    assert total >= 200
    assert total >= _BASE_TOTAL
