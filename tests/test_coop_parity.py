"""Parity suite for the PR-5 pluggable-hierarchy refactor.

The protocol-based default region+host stack must reproduce the
pre-refactor ``cooperate`` outputs bit-for-bit: ``tests/data/coop_golden.json``
was captured at the pre-refactor commit (seed 3, local engine, timeout 4,
8 feedback rounds) and pins the assignment hash, objective, rounds, and
rejection counts at N in {64, 1000} for all three variants plus the
premask-off / restart / cost-budget knob paths.

Also covered here: a no-op custom level appended to the stack never
changes results (property test over seeded clusters), the PR-6 fault
machinery is invisible when idle — a healthy ``BreakerBoard`` and a fresh
``TelemetryMonitor`` leave results bit-identical to the goldens — and the
PR-10 measured-latency level is inert without a sketch bank: swapping
``netlat`` in for ``region`` reproduces the goldens bit-for-bit.
"""

import dataclasses
import hashlib
import json
import os

import numpy as np
import pytest
from _hypothesis_compat import hypothesis, st

from repro.core import CoopConfig, Hierarchy, Sptlb, generate_cluster
from repro.core.levels import SchedulerLevel
from repro.core.planner import move_costs

with open(os.path.join(os.path.dirname(__file__), "data", "coop_golden.json")) as f:
    GOLDEN = json.load(f)

# name -> (num_apps, CoopConfig kwargs); mirrors the capture script.
CASES = {
    "N64/no_cnst": (64, {"variant": "no_cnst"}),
    "N64/w_cnst": (64, {"variant": "w_cnst"}),
    "N64/manual_cnst": (64, {}),
    "N1000/no_cnst": (1000, {"variant": "no_cnst"}),
    "N1000/w_cnst": (1000, {"variant": "w_cnst"}),
    "N1000/manual_cnst": (1000, {}),
    "N64/manual_cnst/unmasked": (64, {"premask": False}),
    "N64/manual_cnst/restarts": (64, {"restart_rounds": 2}),
    "N64/manual_cnst/budget": (64, {"cost_budget": 3.0, "move_cost": "derive"}),
    "N1000/manual_cnst/unmasked": (1000, {"premask": False}),
}


# The objective is an f32 reduction, and its last bits depend on the order
# in which the toolchain sums it: under JAX 0.9 the goldens' assignments,
# rounds and rejections still match exactly while the objective moved by
# one f32 ulp.  The pinned assignment SHA already fixes the decision, so
# the objective is held to a few ulps and everything else stays exact.
OBJECTIVE_MAX_ULP = 4


def _assert_matches_golden(got, want):
    exact = {k: v for k, v in want.items() if k != "objective"}
    got_exact = {k: v for k, v in got.items() if k != "objective"}
    diff = {k: (exact[k], got_exact.get(k)) for k in exact if got_exact.get(k) != exact[k]}
    assert got_exact == exact, diff
    np.testing.assert_array_max_ulp(
        np.float32(got["objective"]), np.float32(want["objective"]), maxulp=OBJECTIVE_MAX_ULP
    )


def _decide(cluster, config):
    return Sptlb(cluster).balance("local", timeout_s=4, config=config)


def _record(cluster, decision, region_level="region"):
    x = np.asarray(decision.assignment, np.int64)
    rec = {
        "assignment_sha": hashlib.sha256(x.tobytes()).hexdigest(),
        "objective": float(decision.solve.objective),
        "num_moved": int(np.sum(x != np.asarray(cluster.problem.assignment0))),
        "d2b": float(decision.difference_to_balance),
    }
    if decision.cooperation is not None:
        tm = decision.cooperation.timings
        rec.update(
            rounds=int(tm["rounds"]),
            feedback_rounds=int(decision.cooperation.feedback_rounds),
            num_rejections=int(decision.cooperation.num_rejections),
            region_rejections=int(tm[f"{region_level}_rejections"]),
            host_rejections=int(tm["host_rejections"]),
            accepted=bool(decision.cooperation.accepted),
            movement_cost=float(tm.get("movement_cost", 0.0)),
            budget_trimmed=int(tm.get("budget_trimmed", 0)),
        )
    return rec


@pytest.mark.parametrize("name", sorted(CASES))
def test_default_stack_matches_pre_refactor_golden(name):
    num_apps, kw = CASES[name]
    cluster = generate_cluster(num_apps=num_apps, seed=3)
    kw = dict(kw)
    if kw.get("move_cost") == "derive":
        kw["move_cost"] = move_costs(cluster.problem)
    got = _record(cluster, _decide(cluster, CoopConfig(max_rounds=8, **kw)))
    _assert_matches_golden(got, GOLDEN[name])


def test_explicit_hierarchy_matches_default():
    """Hierarchy.default() / from_names('region,host') are the same stack."""
    cluster = generate_cluster(num_apps=200, seed=3)
    base = _record(cluster, _decide(cluster, CoopConfig()))
    for hierarchy in (Hierarchy.default(), Hierarchy.from_names("region,host")):
        d = Sptlb(cluster).balance("local", timeout_s=4, config=CoopConfig(), hierarchy=hierarchy)
        assert _record(cluster, d) == base


def test_legacy_kwarg_shims_are_gone():
    """PR-5 said the shims last one release; PR-6 is that release."""
    cluster = generate_cluster(num_apps=64, seed=5)
    with pytest.raises(TypeError):
        Sptlb(cluster).balance("local", timeout_s=4, variant="no_cnst")
    with pytest.raises(TypeError):
        Sptlb(cluster).balance("local", timeout_s=4, max_feedback_rounds=4)


@pytest.mark.parametrize("name", ["N64/manual_cnst", "N64/manual_cnst/budget",
                                  "N1000/manual_cnst"])
def test_healthy_breaker_board_matches_golden(name):
    """A BreakerBoard with every breaker closed changes nothing: same
    assignment hash / objective / rounds / rejections as the PR-5 goldens,
    with the board's (all-closed) snapshot surfaced in the timings."""
    from repro.core.health import BreakerBoard

    num_apps, kw = CASES[name]
    cluster = generate_cluster(num_apps=num_apps, seed=3)
    kw = dict(kw)
    if kw.get("move_cost") == "derive":
        kw["move_cost"] = move_costs(cluster.problem)
    board = BreakerBoard()
    d = _decide(cluster, CoopConfig(max_rounds=8, breakers=board, **kw))
    got = _record(cluster, d)
    assert got == GOLDEN[name], {
        k: (GOLDEN[name][k], got[k]) for k in GOLDEN[name]
        if got[k] != GOLDEN[name][k]}
    snap = d.cooperation.timings.breakers
    assert snap["bypassed"] == [] and snap["trips"] == 0
    assert all(b["state"] == "closed" for b in snap["levels"].values())


def test_fresh_telemetry_monitor_is_identity():
    """Fresh, plausible telemetry passes through the monitor unchanged —
    the same ClusterState object, so downstream decisions are untouched."""
    from repro.core.health import TelemetryMonitor

    cluster = generate_cluster(num_apps=150, seed=5)
    monitor = TelemetryMonitor()
    for now in range(3):
        sanitized, health = monitor.ingest(cluster, now, collected_at=now)
        assert sanitized is cluster
        assert health.score == 1.0
        assert health.quarantined == 0


class NoopLevel(SchedulerLevel):
    """A level that accepts everything and constrains nothing."""

    name = "noop"

    def __init__(self, cluster):
        self.cluster = cluster


@hypothesis.settings(max_examples=5, deadline=None)
@hypothesis.given(
    st.sampled_from([64, 150, 300]),
    st.integers(0, 5),
    st.sampled_from([True, False]),
)
def test_noop_custom_level_never_changes_results(num_apps, seed, premask):
    """Appending a no-op level anywhere in the stack is invisible: same
    assignment, objective, rounds, and rejection counts as the default."""
    cluster = generate_cluster(num_apps=num_apps, seed=seed)
    cfg = CoopConfig(premask=premask)
    base = _record(cluster, _decide(cluster, cfg))
    stacked = Hierarchy(
        (
            lambda c: NoopLevel(c),
            *Hierarchy.default().factories,
            lambda c: NoopLevel(c),
        )
    )
    d = Sptlb(cluster).balance("local", timeout_s=4, config=cfg, hierarchy=stacked)
    got = _record(cluster, d)
    assert got == base
    # the no-op level is visible in the observability, invisible in results
    tm = d.cooperation.timings
    assert tm["noop_rejections"] == 0
    assert "noop" in tm.levels


@pytest.mark.parametrize(
    "name,premask",
    [
        ("N64/manual_cnst", {"region": True, "host": True}),
        ("N64/manual_cnst", {}),  # absent levels default to True
        ("N64/manual_cnst/unmasked", {"region": False, "host": False}),
    ],
)
def test_premask_mapping_matches_bool_golden(name, premask):
    """The PR-7 per-level premask mapping is a strict generalization of the
    historical bool: all-True (and empty, via the default) reproduces the
    masked golden bit-for-bit, all-False the unmasked one."""
    cluster = generate_cluster(num_apps=64, seed=3)
    got = _record(
        cluster, _decide(cluster, CoopConfig(max_rounds=8, premask=premask))
    )
    want = GOLDEN[name]
    assert got == want, {k: (want[k], got[k]) for k in want if got[k] != want[k]}


def test_inactive_shed_plan_is_bit_identical():
    """The overload throttle off is really off: ``shed=None`` and an
    inactive plan (caps all ones) both reproduce the golden exactly."""
    from repro.core.shedding import ShedPlan

    cluster = generate_cluster(num_apps=64, seed=3)
    inert = ShedPlan(caps=np.ones(cluster.problem.num_apps, np.float32))
    for shed in (None, inert):
        got = _record(
            cluster, _decide(cluster, CoopConfig(max_rounds=8, shed=shed))
        )
        assert got == GOLDEN["N64/manual_cnst"], shed


def test_inert_netlat_level_is_the_static_region_contract():
    """PR 10's measured-latency level with no bank installed degrades to
    exactly the static region contract: swapping region -> netlat in the
    stack reproduces the PR-5 goldens bit-for-bit (the level reports its
    rejections under its own name), and merely importing the package —
    which registers the level — perturbs nothing."""
    import repro.netlat as netlat

    netlat.install_bank(None)  # explicit: no measurement state bound
    for name in ("N64/manual_cnst", "N1000/manual_cnst"):
        num_apps, kw = CASES[name]
        cluster = generate_cluster(num_apps=num_apps, seed=3)
        cfg = CoopConfig(max_rounds=8, levels=("netlat", "host"), **kw)
        got = _record(cluster, _decide(cluster, cfg), region_level="netlat")
        want = GOLDEN[name]
        assert got == want, {k: (want[k], got[k]) for k in want if got[k] != want[k]}
    # Registration alone is side-effect free: the default region+host
    # stack still matches its golden with the netlat package imported.
    cluster = generate_cluster(num_apps=64, seed=3)
    got = _record(cluster, _decide(cluster, CoopConfig(max_rounds=8)))
    assert got == GOLDEN["N64/manual_cnst"]


def test_controller_config_legacy_fields_fold_into_coop():
    from repro.core.controller import ControllerConfig

    cfg = ControllerConfig(variant="no_cnst", restart_rounds=3)
    assert cfg.coop.variant == "no_cnst"
    assert cfg.coop.restart_rounds == 3
    explicit = ControllerConfig(coop=CoopConfig(levels=("region", "host", "shard")))
    assert explicit.coop.levels == ("region", "host", "shard")
    carried = dataclasses.replace(explicit, movement_cost_budget=5.0)
    assert carried.coop.levels == ("region", "host", "shard")


def test_plan_relax_path_unchanged_through_levels():
    """Maintenance placement mode now flows through the level relax hooks;
    the resulting per-app region budget must match the historical
    ``np.where(relax_home_tiers[x0], base * factor, base)`` array."""
    from repro.core.hierarchy import REGION_LATENCY_BUDGET_MS, RegionScheduler
    from repro.core.planner import PlanOutlook

    cluster = generate_cluster(num_apps=120, seed=2)
    T = cluster.problem.num_tiers
    relax = np.zeros(T, bool)
    relax[2] = True
    plan = PlanOutlook(
        now=0,
        horizon=8,
        tier_factor=np.ones(T, np.float32),
        avoid_tiers=np.zeros(T, bool),
        slo_off_tiers=np.zeros(T, bool),
        pending=1,
        relax_home_tiers=relax,
        relax_latency_factor=1.5,
    )
    level = RegionScheduler(cluster)
    level.relax(plan, cluster)
    x0 = np.asarray(cluster.problem.assignment0)
    want = np.where(
        relax[x0], REGION_LATENCY_BUDGET_MS * 1.5, REGION_LATENCY_BUDGET_MS
    ).astype(np.float32)
    assert level.budget is None
    np.testing.assert_array_equal(level._budget_per_app, want)
