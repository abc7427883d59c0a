"""Fleet-simulator scenario benchmark: controller vs no-rebalance baseline
over every registry scenario, scored by trajectory-level SLO accounting.

For each scenario the harness runs the same workload trajectory twice —
``static`` (the t=0 placement rides out the run) and ``balanced``
(``BalanceController`` ticks with hysteresis/cooldown, anticipating any
declared maintenance advisories and pricing movement against the
scenario's budget) — and records the violation integrals, priced movement
vs budget, d2b series, and solver wall-clock.  The per-scenario comparison
ratios are the PR 3/4 acceptance numbers (tier_drain must stay <= 0.15
with movement inside the budget; region_outage must not regress), and
``benchmarks/check_regression.py`` gates them in CI.

Chaos scenarios (``Scenario.chaos``) run the degraded/oracle/static triple
via ``run_chaos_pair`` instead: their records carry the ``chaos``
scorecard (unsafe moves, mode residency and transitions, recovery,
degraded-vs-oracle violation ratio) that the regression gate pins — see
docs/degraded_modes.md.

Overload scenarios (``Scenario.overload``) run the binary-baseline vs
utility-armed pair via ``run_overload_pair``: their records carry the
``overload`` scorecard (delivered-utility improvement, admission/shedding
counters, the zero-infeasible-admissions invariant) — see
docs/overload_and_admission.md.

Network scenarios (``Scenario.netlat``) run the static-36ms vs
measured-budget pair via ``run_netlat_pair``: their records carry the
``netlat`` scorecard (p99-aware placement-latency ratio < 1, zero
budget-exceeding committed moves under live measured budgets) — see
docs/latency_slo.md.  ``service_ingest`` additionally drives one
``ServiceLoop`` from N concurrent producer threads (the thread-safe
``submit`` path) and records sustained events/s and re-solve latency.

Emits CSV rows like every other benchmark AND writes ``BENCH_sim.json`` at
the repo root so the trajectory scorecard is tracked PR-over-PR
(regenerate with ``PYTHONPATH=src python -m benchmarks.sim_scenarios``;
``--smoke`` shrinks apps/ticks for CI and writes BENCH_sim_smoke.json).
"""
from __future__ import annotations

import argparse
import json
import os
import time

from benchmarks.common import comment, emit
from repro.sim import (get_scenario, list_scenarios, run_chaos_pair,
                       run_netlat_pair, run_overload_pair, run_pair)

RESULTS: dict = {}


def bench_netlat_scenario(sc, num_apps: int, ticks: int):
    """Network scenarios run the static-budget/measured-budget pair: the
    record keys the gate pins are the ``netlat`` scorecard (the measured
    stack's p99-aware placement-latency integral at ratio <= 1 vs the
    static 36 ms stack, zero committed moves whose destination exceeds a
    live measured p99 budget, calibration achieved)."""
    t0 = time.perf_counter()
    out = run_netlat_pair(sc)
    wall = time.perf_counter() - t0
    n = out["netlat"]
    rec = {
        "num_apps": num_apps,
        "pool": sc.max_apps,
        "ticks": ticks,
        "wall_s": wall,
        "static": out["static"].summary(),
        "measured": out["measured"].summary(),
        "netlat": n,
        "series": {"static": out["static"].series(),
                   "measured": out["measured"].series()},
    }
    p99 = n["network_p99_integral"]
    bex = n["budget_exceeding_moves"]
    emit(f"sim_scenarios/{sc.name}/N{num_apps}x{ticks}", wall * 1e6,
         f"p99_static={p99['static']:.1f};p99_measured={p99['measured']:.1f};"
         f"p99_ratio={p99['ratio']:.4f};"
         f"bex_static={bex['static']};bex_measured={bex['measured']};"
         f"moves_static={n['moves']['static']};"
         f"moves_measured={n['moves']['measured']};"
         f"calibrated={n['calibrated']};relax={n['relax_factor']:.3f};"
         f"quarantined={n['quarantined_samples']}")
    comment(f"{sc.name} (netlat): p99 integral {p99['static']:.0f} -> "
            f"{p99['measured']:.0f} ({p99['ratio']:.3f}x), budget-exceeding "
            f"moves {bex['static']} -> {bex['measured']}, moves "
            f"{n['moves']['static']} -> {n['moves']['measured']}")
    RESULTS[sc.name] = rec
    return rec


def bench_service_ingest(num_apps: int, ticks: int, producers: int = 4):
    """Multi-producer ingestion: ``producers`` concurrent threads submit
    telemetry deltas for disjoint app partitions while the main thread
    steps the loop — the thread-safe ``submit`` path under contention.
    The gate pins zero dropped events and per-app sequence monotonicity;
    the operational numbers are sustained events/s and re-solve p50/p99."""
    import threading

    import numpy as np

    from repro.core import generate_cluster
    from repro.core.controller import BalanceController, ControllerConfig
    from repro.service import ServiceLoop, TelemetryDelta

    cluster = generate_cluster(num_apps=num_apps, seed=7)
    ctl = BalanceController(cluster, ControllerConfig(timeout_s=30))
    loop = ServiceLoop(controller=ctl)
    dem0 = np.asarray(cluster.problem.demand, np.float32)
    tsk0 = np.asarray(cluster.problem.tasks, np.float32)
    live = np.where(np.asarray(cluster.problem.valid))[0]
    chunks = [c for c in np.array_split(live, producers) if c.size]

    def produce(pid: int, ids: np.ndarray) -> None:
        rng = np.random.default_rng(100 + pid)
        for r in range(ticks):
            skew = rng.uniform(0.9, 1.15, size=(ids.size, 1)).astype(
                np.float32)
            loop.submit(TelemetryDelta(
                app_ids=tuple(int(n) for n in ids),
                demand=dem0[ids] * skew, tasks=tsk0[ids].copy(),
                collected_at=r))

    t0 = time.perf_counter()
    threads = [threading.Thread(target=produce, args=(i, c))
               for i, c in enumerate(chunks)]
    for t in threads:
        t.start()
    step = 0
    while any(t.is_alive() for t in threads) or loop._queue:
        loop.step(step)
        step += 1
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    stats = loop.stats()
    ordered = all(seqs == sorted(seqs)
                  for seqs in loop.shadow.applied_seq.values())
    rec = {
        "num_apps": num_apps,
        "producers": len(chunks),
        "events_per_producer": ticks,
        "wall_s": wall,
        "events_submitted": stats["events_submitted"],
        "dropped_events": stats["dropped_events"],
        "per_app_ordered": ordered,
        "ingest_events_per_s": (stats["events_applied"] / wall
                                if wall > 0 else 0.0),
        "stats": stats,
    }
    emit(f"sim_scenarios/service_ingest/N{num_apps}x{ticks}", wall * 1e6,
         f"producers={len(chunks)};"
         f"events={stats['events_submitted']};"
         f"dropped={stats['dropped_events']};ordered={ordered};"
         f"ingest_events_per_s={rec['ingest_events_per_s']:.0f};"
         f"resolve_p50_ms={stats['resolve_p50_ms']:.1f};"
         f"resolve_p99_ms={stats['resolve_p99_ms']:.1f}")
    comment(f"service_ingest: {len(chunks)} producers x {ticks} deltas, "
            f"{rec['ingest_events_per_s']:.0f} events/s ingested, "
            f"{stats['dropped_events']} dropped, re-solve p50 "
            f"{stats['resolve_p50_ms']:.1f} ms / p99 "
            f"{stats['resolve_p99_ms']:.1f} ms")
    RESULTS["service_ingest"] = rec
    return rec


def bench_overload_scenario(sc, num_apps: int, ticks: int):
    """Overload scenarios run the binary-baseline/utility-armed pair: the
    record keys the gate pins are the ``overload`` scorecard (delivered-
    utility improvement > 1 on the same trajectory and the same curves,
    zero infeasible admissions, bounded shed churn, budgets held)."""
    t0 = time.perf_counter()
    out = run_overload_pair(sc)
    wall = time.perf_counter() - t0
    o = out["overload"]
    rec = {
        "num_apps": num_apps,
        "pool": sc.max_apps,
        "ticks": ticks,
        "wall_s": wall,
        "binary": out["binary"].summary(),
        "utility": out["utility"].summary(),
        "overload": o,
        "series": {"binary": out["binary"].series(),
                   "utility": out["utility"].series()},
    }
    r = o["delivered_utility_ratio"]
    adm = o["admission"]
    emit(f"sim_scenarios/{sc.name}/N{num_apps}x{ticks}", wall * 1e6,
         f"util_binary={r['binary']:.3f};util_utility={r['utility']:.3f};"
         f"util_improvement={r['improvement']:.3f};"
         f"deferred={o['deferred_app_ticks']};"
         f"shed_capped={o['shed_capped_app_ticks']};"
         f"shed_churn={o['shed_churn_events']};"
         f"infeasible_admissions={o['infeasible_admissions']};"
         f"admit={adm.get('admit', 0)};defer={adm.get('defer', 0)};"
         f"reject={adm.get('reject', 0)};"
         f"within_budget={o['within_budget']['utility']}")
    comment(f"{sc.name} (overload): delivered utility {r['binary']:.3f} -> "
            f"{r['utility']:.3f} of oracle ({r['improvement']:.2f}x), "
            f"{o['deferred_app_ticks']} deferred app-ticks, "
            f"{o['shed_capped_app_ticks']} shed-capped app-ticks, "
            f"{o['infeasible_admissions']} infeasible admissions")
    RESULTS[sc.name] = rec
    return rec


def bench_chaos_scenario(sc, num_apps: int, ticks: int):
    """Chaos scenarios run the degraded/oracle/static triple instead of the
    plain pair: the record keys the gate pins are the ``chaos`` scorecard
    (zero unsafe moves, recovery to NORMAL, bounded degraded-vs-oracle
    ratio) plus the usual ``compare`` of degraded against static."""
    t0 = time.perf_counter()
    out = run_chaos_pair(sc)
    wall = time.perf_counter() - t0
    c = out["chaos"]
    rec = {
        "num_apps": num_apps,
        "pool": sc.max_apps,
        "ticks": ticks,
        "wall_s": wall,
        "baseline": out["baseline"].summary(),
        "degraded": out["degraded"].summary(),
        "oracle": out["oracle"].summary(),
        "compare": out["compare"],
        "chaos": c,
        "series": {"degraded": out["degraded"].series(),
                   "oracle": out["oracle"].series()},
    }
    dvo = c["degraded_vs_oracle"]
    emit(f"sim_scenarios/{sc.name}/N{num_apps}x{ticks}", wall * 1e6,
         f"viol_degraded={dvo['degraded']};viol_oracle={dvo['oracle']};"
         f"chaos_ratio={dvo['ratio']:.3f};unsafe_moves={c['unsafe_moves']};"
         f"degraded_ticks={c['degraded_ticks']};"
         f"modes={'+'.join(c['modes_entered'])};"
         f"breaker_trips={c['breaker_trips']};"
         f"quarantined={c['telemetry_quarantined']};"
         f"budget_overruns={c['budget_overruns']};"
         f"recovered={c['recovered']}")
    comment(f"{sc.name} (chaos): violation ticks degraded {dvo['degraded']} "
            f"vs oracle {dvo['oracle']} ({dvo['ratio']:.2f}x), "
            f"{c['unsafe_moves']} unsafe moves, modes entered "
            f"{c['modes_entered']}, recovered={c['recovered']}")
    RESULTS[sc.name] = rec
    return rec


def bench_scenario(name: str, num_apps: int, ticks: int, seed: int = 0):
    sc = get_scenario(name, num_apps=num_apps, ticks=ticks, seed=seed)
    if sc.netlat:
        return bench_netlat_scenario(sc, num_apps, ticks)
    if sc.overload:
        # Overload routing wins over chaos: overload_capacity_loss composes
        # both, and its acceptance story is the utility scorecard (the
        # chaos machinery still runs inside the utility-armed controller).
        return bench_overload_scenario(sc, num_apps, ticks)
    if sc.chaos:
        return bench_chaos_scenario(sc, num_apps, ticks)
    t0 = time.perf_counter()
    out = run_pair(sc)
    wall = time.perf_counter() - t0
    cmp = out["compare"]
    rec = {
        "num_apps": num_apps,
        "pool": sc.max_apps,
        "ticks": ticks,
        "wall_s": wall,
        "baseline": out["baseline"].summary(),
        "balanced": out["balanced"].summary(),
        "compare": cmp,
        "series": {"baseline": out["baseline"].series(),
                   "balanced": out["balanced"].series()},
    }
    viol = cmp["slo_violation_ticks"]
    move = cmp["movement"]

    def fmt(r):                      # ratio may be None (0-baseline)
        return "n/a" if r is None else f"{r:.3f}"

    emit(f"sim_scenarios/{name}/N{num_apps}x{ticks}", wall * 1e6,
         f"viol_baseline={viol['baseline']};viol_balanced={viol['balanced']};"
         f"viol_ratio={fmt(viol['ratio'])};"
         f"excess_ratio={fmt(cmp['over_ideal_excess_integral']['ratio'])};"
         f"moves={cmp['total_moves']};move_cost={move['cost']:.1f};"
         f"move_budget={move['budget']};within_budget={move['within_budget']};"
         f"rebalances={cmp['rebalances']};"
         f"solver_s={cmp['solver_time_s']:.2f}")
    comment(f"{name}: violation ticks {viol['baseline']} -> "
            f"{viol['balanced']} ({fmt(viol['ratio'])}x), "
            f"{cmp['rebalances']} rebalances moved {cmp['total_moves']} apps "
            f"(cost {move['cost']:.1f}"
            + (f" of budget {move['budget']:.0f}" if move["budget"] else "")
            + ")")
    RESULTS[name] = rec
    return rec


def bench_service_loop(num_apps: int, ticks: int):
    """PR 9 streaming service: the same trajectory as a lockstep run and as
    an event stream through ``ServiceLoop``, on the two curated plain
    scenarios the acceptance pins.  The record keys the gate pins are the
    ``compare`` scorecard (quality ratios vs lockstep, >= 30% fewer full
    cooperate passes, zero dropped events, zero delta reverts) plus the
    loop's operational ``stats`` (events/s, re-solve p50/p99)."""
    from repro.sim import run_service_pair

    section = {}
    for name in ("steady_diurnal", "flash_crowd"):
        sc = get_scenario(name, num_apps=num_apps, ticks=ticks)
        t0 = time.perf_counter()
        pair = run_service_pair(sc)
        wall = time.perf_counter() - t0
        cmp = pair["service_compare"]
        stats = pair["service"].extra["service"]
        section[name] = {
            "num_apps": num_apps,
            "ticks": ticks,
            "wall_s": wall,
            "compare": cmp,
            "stats": stats,
        }
        viol = cmp["slo_violation_ticks"]
        fp = cmp["full_passes"]
        emit(f"sim_scenarios/service_loop/{name}/N{num_apps}x{ticks}",
             wall * 1e6,
             f"viol_lockstep={viol['lockstep']};viol_service={viol['service']};"
             f"full_passes={fp['lockstep']}->{fp['service']};"
             f"reduction={fp['reduction']:.3f};"
             f"delta_solves={cmp['delta_solves']};"
             f"noop_ticks={cmp['noop_ticks']};"
             f"dropped={cmp['dropped_events']};"
             f"reverts={cmp['delta_reverts']};"
             f"events_per_s={stats['events_per_s']:.0f};"
             f"resolve_p50_ms={stats['resolve_p50_ms']:.1f};"
             f"resolve_p99_ms={stats['resolve_p99_ms']:.1f}")
        comment(f"{name} (service): full passes {fp['lockstep']} -> "
                f"{fp['service']} ({fp['reduction']:.0%} fewer), "
                f"{cmp['delta_solves']} delta solves, "
                f"{cmp['noop_ticks']} noop ticks, violations "
                f"{viol['lockstep']} -> {viol['service']}, "
                f"{cmp['dropped_events']} dropped events")
    RESULTS["service_loop"] = section
    return section


def run(smoke: bool = False):
    comment(f"--- fleet simulator scenarios "
            f"(XLA path, CPU{', smoke' if smoke else ''}) ---")
    num_apps, ticks = (128, 24) if smoke else (400, 160)
    for name in list_scenarios():
        bench_scenario(name, num_apps, ticks)
    bench_service_loop(num_apps, ticks)
    bench_service_ingest(num_apps, ticks)

    # Smoke numbers must not clobber the tracked fleet-scale record.
    name = "BENCH_sim_smoke.json" if smoke else "BENCH_sim.json"
    out_path = os.path.normpath(os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "..", name))
    with open(out_path, "w") as f:
        json.dump(RESULTS, f, indent=2, sort_keys=True)
    comment(f"wrote {out_path}")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small sizes for CI (seconds, not minutes)")
    run(**vars(ap.parse_args()))
