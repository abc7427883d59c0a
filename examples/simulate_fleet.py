"""Drive the balancing controller through a dynamic fleet scenario.

The one-shot experiment (examples/rebalance_cluster.py) solves a single
Fig. 3 snapshot; this example runs the *system*: demand evolves every tick
(diurnal cycle, burst noise, flash crowds, churn), timed events rewrite the
cluster (capacity drains, region outages), and ``BalanceController``
decides tick by tick whether rebalancing is worth the movement cost.  The
SLO accountant scores the trajectory for both the controller and the
no-rebalance baseline.

Run:
  PYTHONPATH=src python examples/simulate_fleet.py                  # all
  PYTHONPATH=src python examples/simulate_fleet.py \\
      --scenario tier_drain --apps 400 --ticks 160 --verbose
  PYTHONPATH=src python examples/simulate_fleet.py \\
      --scenario fleet_scale --shards 4      # sharded solver path

Scenario how-to
---------------
A scenario is a ``WorkloadConfig`` (the demand process) plus a tuple of
timed events over a tick horizon.  The registry in ``repro/sim/scenario.py``
ships five: steady_diurnal, flash_crowd, tier_drain, region_outage,
churn_heavy.  To add your own, register a builder:

    from repro.sim import (Scenario, WorkloadConfig, CapacityScale,
                           FlashCrowd, scenario)

    @scenario("friday_deploy", "weekly deploy drains tier 1 during a spike")
    def _friday_deploy(num_apps, ticks, seed):
        return Scenario(
            name="friday_deploy", description="", ticks=ticks,
            num_apps=num_apps, seed=seed,
            workload=WorkloadConfig(diurnal_amp=0.25, burst_sigma=0.12),
            events=(FlashCrowd(at=ticks // 3, frac=0.10, magnitude=5.0),
                    CapacityScale(at=ticks // 3 + 2, tier=0, scale=0.3),
                    CapacityScale(at=(2 * ticks) // 3, tier=0, scale=1.0)))

then ``--scenario friday_deploy`` (or ``sim.get_scenario``) runs it.
Available events: ``CapacityScale`` (maintenance drains/restores, scale is
relative to as-built), ``RegionOutage``/``RegionRestore`` (capacity + SLO
eligibility loss on overlapping tiers, region goes latency-dark),
``FlashCrowd`` (demand spike on a random app subset, decays geometrically),
``ShardSkew`` (demand spike anchored to one region's data shards),
``ChurnRate`` (re-rate arrivals/retirements mid-run — traced state, so no
recompile).  Churned app counts ride the ``valid``-mask padding: shapes
never change, so the whole trajectory reuses one compiled solver per pow-2
bucket.

Scheduler levels
----------------
The controller's cooperation bus runs an ordered stack of
``core.levels.SchedulerLevel`` plugins.  The default is the paper's
region+host pair; ``--levels region,host,shard`` adds the shard locality
level (data-shard co-location vetting — what the ``shard_skew`` scenario
exercises), and a scenario may pin its own stack via ``Scenario.levels``.
Register a custom level (``register_level("mine", MyLevel)``) and it is
immediately addressable here — see docs/custom_scheduler_levels.md.

Anticipation
------------
Maintenance events are *declared*: ``CapacityScale``/``RegionOutage``/
``RegionRestore`` default to ``announced=True`` and publish advisories
(``Scenario.declared_events``) that the controller's planner
(``repro/core/planner.py``) turns into time-phased capacity targets — the
evacuation starts before the first ramp step instead of after it strands
incumbents, and every move is priced against the scenario's
``move_budget``.  ``--compare-anticipation`` runs the balanced policy
twice (planner on vs off) over the same trajectory:

  PYTHONPATH=src python examples/simulate_fleet.py \\
      --scenario tier_drain --compare-anticipation

Chaos
-----
``--chaos`` runs the control-plane fault family (telemetry_blackout,
solver_brownout, cascading_outage — or a single chaos scenario via
``--scenario``) through ``sim.run_chaos_pair``: the *degraded* run faces
the faults with the degraded-mode control plane armed
(``CHAOS_CONTROLLER``), the *oracle* twin replays the identical workload
with the faults stripped, and the scorecard reports what degradation cost:
operating-mode residency and audited transitions, unsafe moves committed
on faulted telemetry (must be 0), breaker trips, quarantined readings, and
the degraded-vs-oracle SLO-violation ratio.  See docs/degraded_modes.md
for the runbook interpretation of each figure:

  PYTHONPATH=src python examples/simulate_fleet.py --chaos --verbose

Overload
--------
``--overload`` runs the demand-side failure family (overload_surge,
overload_flash, overload_capacity_loss — or one of them via
``--scenario``) through ``sim.run_overload_pair``: the *utility* run arms
the full overload control plane (Henge-style utility curves, the admission
gate, the load shedder), the *binary* twin rides the identical trajectory
with none of it, and the scorecard reports what graceful degradation
bought: delivered-utility ratio vs the fractional-knapsack oracle for both
policies, deferred/shed-capped app-ticks, cap-churn against the movement
budget, and the two hard invariants (infeasible admissions and budget
overruns, both must be 0).  See docs/overload_and_admission.md for the
runbook interpretation:

  PYTHONPATH=src python examples/simulate_fleet.py --overload --verbose

Network
-------
``--netlat`` runs the network_degraded family (slow links, asymmetric
detours, jitter storms — or one of them via ``--scenario``) through
``sim.run_netlat_pair``: the *static* run vets placements against the
hard-coded 36 ms constant, the *measured* twin binds the latency-SLO
level (per-pair budgets calibrated from streaming P² sketches, vetted
against live p99 estimates), and the scorecard reports the placement-p99
integral ratio (must be < 1), budget-exceeding moves (measured must be
0), and the calibration/quarantine counters.  See docs/latency_slo.md:

  PYTHONPATH=src python examples/simulate_fleet.py --netlat --verbose

Metrics (see ``repro/sim/slo.py``): ``slo_violation_ticks`` integrates
app-ticks on SLO-ineligible tiers plus tier-ticks over the ideal line;
``over_ideal_excess_integral`` weights the latter by severity;
``total_moves`` counts moves and ``movement_cost`` prices them
(Madsen-style, ``core.planner.move_costs`` — the paper's goal 8 made a
budget); ``region_breach_app_ticks`` surfaces the maintenance placement
mode's bounded latency degradation.  ``BENCH_sim.json`` is regenerated by
``PYTHONPATH=src python -m benchmarks.sim_scenarios``.
"""
import argparse

from repro import (ControllerConfig, CoopConfig, get_scenario, list_scenarios,
                   run_netlat_pair, run_pair, run_scenario, run_service_pair)
from repro.sim import run_chaos_pair, run_overload_pair


def run_netlat(names, args):
    """--netlat: measured-vs-static budget scorecard per network scenario."""
    if args.scenario == "all":
        names = [n for n in sorted(list_scenarios())
                 if get_scenario(n, num_apps=8, ticks=8, seed=0).netlat]
    for name in names:
        sc = get_scenario(name, num_apps=args.apps, ticks=args.ticks,
                          seed=args.seed)
        if not sc.netlat:
            print(f"{name}: not a network scenario (no link weather for the "
                  f"measurement plane to see) — skipping")
            continue
        print(f"-- {name}: {sc.description}")
        out = run_netlat_pair(sc, verbose=args.verbose)
        c = out["netlat"]
        p99 = c["network_p99_integral"]
        print(f"   p99 integral       static {p99['static']:.1f} vs "
              f"measured {p99['measured']:.1f} (ratio {p99['ratio']:.4f})")
        peak = c["peak_network_p99_ms"]
        print(f"   peak p99           static {peak['static']:.1f} ms vs "
              f"measured {peak['measured']:.1f} ms")
        bex = c["budget_exceeding_moves"]
        print(f"   budget-exceeding   static {bex['static']} vs "
              f"measured {bex['measured']} (measured must be 0)")
        print(f"   moves              static {c['moves']['static']} vs "
              f"measured {c['moves']['measured']}")
        print(f"   calibrated         {c['calibrated']} "
              f"(relax {c['relax_factor']}, "
              f"{c['quarantined_samples']} quarantined samples)")


def run_service(names, args):
    """--service: event-stream service vs lockstep scorecard per scenario."""
    if args.scenario == "all":
        names = [n for n in sorted(list_scenarios())
                 if not (sc := get_scenario(n, num_apps=8, ticks=8,
                                            seed=0)).chaos and not sc.overload]
    for name in names:
        sc = get_scenario(name, num_apps=args.apps, ticks=args.ticks,
                          seed=args.seed)
        if sc.chaos or sc.overload:
            print(f"{name}: chaos/overload scenarios replay through their "
                  f"own harnesses — skipping")
            continue
        print(f"-- {name}: {sc.description}")
        out = run_service_pair(sc, verbose=args.verbose)
        c = out["service_compare"]
        fp = c["full_passes"]
        print(f"   full passes        lockstep {fp['lockstep']} vs "
              f"service {fp['service']} (reduction {fp['reduction']:.2f})")
        print(f"   delta solves       {c['delta_solves']} "
              f"({c['delta_fraction']:.2f} of solves), "
              f"{c['noop_ticks']} no-op ticks, "
              f"{c['delta_reverts']} parity reverts")
        v = c["slo_violation_ticks"]
        ratio = "n/a" if v["ratio"] is None else f"{v['ratio']:.2f}"
        print(f"   violation ticks    lockstep {v['lockstep']} vs "
              f"service {v['service']} (ratio {ratio})")
        print(f"   moves              lockstep {c['total_moves']['lockstep']} "
              f"vs service {c['total_moves']['service']}")
        print(f"   events             {c['events_applied']} applied, "
              f"{c['dropped_events']} dropped (must be 0)")


def run_chaos(names, args):
    """--chaos: degraded-vs-oracle scorecard per chaos scenario."""
    if args.scenario == "all":
        names = [n for n in sorted(list_scenarios())
                 if get_scenario(n, num_apps=8, ticks=8, seed=0).chaos]
    for name in names:
        sc = get_scenario(name, num_apps=args.apps, ticks=args.ticks,
                          seed=args.seed)
        if not sc.chaos:
            print(f"{name}: not a chaos scenario (has no control-plane "
                  f"fault windows) — skipping")
            continue
        print(f"-- {name}: {sc.description}")
        out = run_chaos_pair(sc, verbose=args.verbose)
        c = out["chaos"]
        modes = ", ".join(f"{m}:{t}" for m, t in c["mode_ticks"].items())
        print(f"   mode ticks         {modes}")
        for t in c["mode_transitions"]:
            print(f"   transition         tick {t['tick']:3d}  "
                  f"{t['from']:>12s} -> {t['to']:<12s} score {t['score']}")
        d, o = c["degraded_vs_oracle"]["degraded"], \
            c["degraded_vs_oracle"]["oracle"]
        print(f"   unsafe moves       {c['unsafe_moves']} (must be 0)")
        print(f"   violation ticks    degraded {d} vs oracle {o} "
              f"(ratio {c['degraded_vs_oracle']['ratio']:.2f})")
        print(f"   moves              degraded {c['moves']['degraded']} "
              f"vs oracle {c['moves']['oracle']}")
        print(f"   breaker trips      {c['breaker_trips']}")
        print(f"   quarantined        {c['telemetry_quarantined']} readings")
        print(f"   budget overruns    {c['budget_overruns']}")
        print(f"   recovered          {c['recovered']}")


def run_overload(names, args):
    """--overload: utility-vs-binary scorecard per overload scenario."""
    if args.scenario == "all":
        names = [n for n in sorted(list_scenarios())
                 if get_scenario(n, num_apps=8, ticks=8, seed=0).overload]
    for name in names:
        sc = get_scenario(name, num_apps=args.apps, ticks=args.ticks,
                          seed=args.seed)
        if not sc.overload:
            print(f"{name}: not an overload scenario (demand never outgrows "
                  f"the fleet) — skipping")
            continue
        print(f"-- {name}: {sc.description}")
        out = run_overload_pair(sc, verbose=args.verbose)
        o = out["overload"]
        r = o["delivered_utility_ratio"]
        print(f"   delivered utility  binary {r['binary']:.3f} vs "
              f"utility {r['utility']:.3f} of oracle "
              f"(improvement {r['improvement']:.2f}x)")
        adm = o["admission"]
        if adm:
            print(f"   admission          {adm.get('admit', 0)} admit, "
                  f"{adm.get('admit_degraded', 0)} degraded, "
                  f"{adm.get('defer', 0)} defer, {adm.get('reject', 0)} "
                  f"reject ({adm.get('backlog', 0)} backlogged)")
        print(f"   deferred           {o['deferred_app_ticks']} app-ticks")
        print(f"   shed-capped        {o['shed_capped_app_ticks']} app-ticks "
              f"({o['shed_events']} shed, {o['readmit_events']} readmitted, "
              f"{o['shed_churn_events']} churn events)")
        print(f"   moves              binary {o['moves']['binary']} vs "
              f"utility {o['moves']['utility']}")
        wb = o["within_budget"]
        print(f"   within budget      binary {wb['binary']} / "
              f"utility {wb['utility']} "
              f"({o['budget_overruns']['utility']} overruns)")
        print(f"   infeasible adm.    {o['infeasible_admissions']} "
              f"(must be 0)")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--scenario", default="all",
                    help=f"one of {sorted(list_scenarios())} or 'all'")
    ap.add_argument("--apps", type=int, default=240)
    ap.add_argument("--ticks", type=int, default=64)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--timeout-s", type=int, default=4,
                    help="per-tick solver budget knob")
    ap.add_argument("--restart-rounds", type=int, default=0,
                    help="vetted perturbation restarts per accepted solve")
    ap.add_argument("--levels", default=None,
                    help="comma-separated scheduler-level stack for the "
                         "cooperation bus (e.g. region,host,shard); default "
                         "lets each scenario pick its own (shard_skew runs "
                         "the three-level stack), others use region,host")
    ap.add_argument("--shards", type=int, default=None,
                    help="route the balanced controller's solves through the "
                         "S-shard partitioned fleet path (repro.shard); "
                         "default lets each scenario pick (fleet_scale runs "
                         "2 shards), others use the global solver")
    ap.add_argument("--no-anticipation", action="store_true",
                    help="ignore declared maintenance advisories (reactive "
                         "controller, the pre-PR-4 behaviour)")
    ap.add_argument("--compare-anticipation", action="store_true",
                    help="also run the balanced policy with the planner off "
                         "and print the proactive-vs-reactive delta")
    ap.add_argument("--chaos", action="store_true",
                    help="run the control-plane chaos family through "
                         "run_chaos_pair and print the degraded-vs-oracle "
                         "scorecard (see docs/degraded_modes.md)")
    ap.add_argument("--overload", action="store_true",
                    help="run the overload family through run_overload_pair "
                         "and print the utility-vs-binary scorecard (see "
                         "docs/overload_and_admission.md)")
    ap.add_argument("--netlat", action="store_true",
                    help="run the network_degraded family through "
                         "run_netlat_pair and print the measured-vs-static "
                         "budget scorecard (see docs/latency_slo.md)")
    ap.add_argument("--service", action="store_true",
                    help="replay scenarios as event streams through the "
                         "ServiceLoop (drift-triggered delta solves) and "
                         "print the service-vs-lockstep scorecard (see "
                         "docs/streaming_service.md)")
    ap.add_argument("--verbose", action="store_true",
                    help="per-tick trace")
    args = ap.parse_args()

    names = (sorted(list_scenarios()) if args.scenario == "all"
             else [args.scenario])
    if args.chaos:
        run_chaos(names, args)
        return
    if args.overload:
        run_overload(names, args)
        return
    if args.netlat:
        run_netlat(names, args)
        return
    if args.service:
        run_service(names, args)
        return
    levels = (tuple(n for n in args.levels.split(",") if n.strip())
              if args.levels else None)
    config = ControllerConfig(
        timeout_s=args.timeout_s, cooldown_rounds=2, shards=args.shards,
        coop=CoopConfig(restart_rounds=args.restart_rounds, levels=levels))

    print(f"{'scenario':16s} {'policy':9s} {'viol':>6s} {'excess':>8s} "
          f"{'peak d2b':>8s} {'moves':>6s} {'cost':>7s} {'rebal':>5s} "
          f"{'solver s':>8s}")
    for name in names:
        sc = get_scenario(name, num_apps=args.apps, ticks=args.ticks,
                          seed=args.seed)
        if args.verbose:
            print(f"-- {name}: {sc.description}")
        out = run_pair(sc, config=config,
                       anticipation=not args.no_anticipation,
                       verbose=args.verbose)
        for policy in ("baseline", "balanced"):
            s = out[policy].summary()
            print(f"{name:16s} {s['policy']:9s} "
                  f"{s['slo_violation_ticks']:6d} "
                  f"{s['over_ideal_excess_integral']:8.2f} "
                  f"{s['peak_d2b']:8.3f} {s['total_moves']:6d} "
                  f"{s['movement_cost']:7.1f} "
                  f"{s['rebalances']:5d} {s['solver_time_s']:8.2f}")
        ratio = out["compare"]["slo_violation_ticks"]["ratio"]
        if ratio is None:
            print(f"{'':16s} -> baseline had 0 violation ticks")
        else:
            print(f"{'':16s} -> violation-tick ratio {ratio:.2f} "
                  f"({'controller wins' if ratio < 1 else 'baseline holds'})")
        move = out["compare"]["movement"]
        if move["budget"] is not None:
            print(f"{'':16s} -> movement cost {move['cost']:.1f} of budget "
                  f"{move['budget']:.0f} "
                  f"({'within' if move['within_budget'] else 'OVERRUN'}, "
                  f"{move['overrun_ticks']} budget-bound ticks)")

        if args.compare_anticipation and sc.declared_events:
            # Same trajectory with the planner toggled: whichever mode the
            # main run used, the comparison re-runs the other one.
            if args.no_anticipation:
                ant = run_scenario(sc, policy="balanced", config=config,
                                   anticipation=True).summary()
                blind = out["balanced"].summary()
            else:
                blind = run_scenario(sc, policy="balanced", config=config,
                                     anticipation=False).summary()
                ant = out["balanced"].summary()
            print(f"{'':16s} anticipation on : viol="
                  f"{ant['slo_violation_ticks']:4d} "
                  f"cost={ant['movement_cost']:7.1f} "
                  f"breach_ticks={ant['region_breach_app_ticks']}")
            print(f"{'':16s} anticipation off: viol="
                  f"{blind['slo_violation_ticks']:4d} "
                  f"cost={blind['movement_cost']:7.1f} "
                  f"breach_ticks={blind['region_breach_app_ticks']}")
        elif args.compare_anticipation:
            print(f"{'':16s} (no declared events — nothing to anticipate)")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
