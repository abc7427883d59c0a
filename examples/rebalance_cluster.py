"""The paper's full experiment (§4) as a runnable scenario:

  * 5 tiers, paper SLO table, tier 3 hot,
  * all three integration variants (no_cnst / w_cnst / manual_cnst),
  * both engines (LocalSearch / OptimalSearch),
  * a failure event mid-scenario -> capacity shrink -> movement-bounded
    re-balance (the scheduler's fault-tolerance loop).

Run:  PYTHONPATH=src python examples/rebalance_cluster.py [--apps 600]

The cooperation knobs ride a ``CoopConfig`` and the lower-level scheduler
stack is a ``Hierarchy`` built from registry names — ``--levels
region,host,shard`` runs the three-level stack (the shard locality plugin
vetting data-shard co-location) through the exact same bus.  Registering
your own level is one call:

    from repro.core import SchedulerLevel, register_level

    class QuotaLevel(SchedulerLevel):
        name = "quota"
        def __init__(self, cluster): ...
        def vet(self, proposal): ...     # -> rejected app ids

    register_level("quota", QuotaLevel)  # then --levels region,host,quota
"""
import argparse


from repro import CoopConfig, Sptlb, generate_cluster
from repro.core import Hierarchy
from repro.distributed.fault import CapacityEvent, rebalance


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--apps", type=int, default=600)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--levels", default="region,host",
                    help="comma-separated scheduler-level stack for the "
                         "cooperation bus (registry names; e.g. "
                         "region,host,shard adds data-shard co-location "
                         "vetting)")
    ap.add_argument("--no-premask", action="store_true",
                    help="disable level pre-masking (the manual_cnst "
                         "feedback loop then re-learns each level's "
                         "feasibility one rejection round at a time, as in "
                         "the paper's plain variant)")
    args = ap.parse_args()

    cluster = generate_cluster(num_apps=args.apps, seed=args.seed)
    sptlb = Sptlb(cluster)
    hierarchy = Hierarchy.from_names(args.levels)

    print(f"levels: {args.levels}")
    print(f"{'variant':14s} {'engine':8s} {'d2b':>6s} {'p99 ms':>7s} "
          f"{'moved':>6s} {'rounds':>6s} {'time s':>7s} ok")
    for engine in ("local", "optimal"):
        for variant in ("no_cnst", "w_cnst", "manual_cnst"):
            cfg = CoopConfig(variant=variant, max_rounds=20,
                             premask=not args.no_premask)
            d = sptlb.balance(engine, timeout_s=30, config=cfg,
                              hierarchy=hierarchy)
            rounds = d.cooperation.feedback_rounds if d.cooperation else 1
            t = d.cooperation.total_time_s if d.cooperation else d.solve.solve_time_s
            print(f"{variant:14s} {engine:8s} {d.difference_to_balance:6.3f} "
                  f"{d.network_p99_ms:7.0f} {d.projected.num_moved:6d} "
                  f"{rounds:6d} {t:7.2f} {d.violations.ok}")

    print("\n-- host failure: tier 3 loses 25% capacity --")
    event = CapacityEvent("host_failure", tier=2, fraction=0.25)
    rebalanced, decision = rebalance(cluster, event)
    print(f"re-balance moved {decision.projected.num_moved} apps "
          f"(bounded by {decision.violations.move_budget}), "
          f"d2b {decision.difference_to_balance:.3f}, "
          f"constraints ok: {decision.violations.ok}")
    print("tier 3 util after failure+rebalance:",
          decision.projected.util_frac[2].round(2))


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
