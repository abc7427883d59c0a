"""Quickstart: the paper's pipeline end-to-end in ~30 seconds on CPU.

1. collect a 5-tier stream-processing cluster (paper §4 setup),
2. balance it with SPTLB under manual_cnst hierarchy co-operation,
3. compare against the greedy baseline (paper Fig. 3).

Run:  PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np

from repro import CoopConfig, Sptlb, generate_cluster, utilization_fraction


def main():
    # --- 1+2: SPTLB balancing (paper Figs 1-3) -----------------------------
    cluster = generate_cluster(num_apps=800, seed=0)
    sptlb = Sptlb(cluster)
    balanced = sptlb.balance("local", timeout_s=30,
                             config=CoopConfig(variant="no_cnst"))
    uf0, _ = utilization_fraction(cluster.problem, cluster.problem.assignment0)
    print("== SPTLB multi-objective balancing ==")
    print(f"initial  cpu util per tier: {np.asarray(uf0)[:, 0].round(2)}")
    print(f"balanced cpu util per tier: {balanced.projected.util_frac[:, 0].round(2)}")
    print(f"balanced mem util per tier: {balanced.projected.util_frac[:, 1].round(2)}")
    print(f"moved {balanced.projected.num_moved} apps "
          f"(budget {balanced.violations.move_budget}), "
          f"constraints ok: {balanced.violations.ok}")

    # --- 3: greedy baseline comparison (paper Fig. 3) ----------------------
    greedy = sptlb.balance("greedy-cpu")
    print("\n== greedy-cpu baseline (single-objective) ==")
    print(f"cpu util per tier : {greedy.projected.util_frac[:, 0].round(2)}  (balanced)")
    print(f"mem util per tier : {greedy.projected.util_frac[:, 1].round(2)}  (left unbalanced!)")

    # --- hierarchy co-operation (paper Figs 2, 4, 5) ------------------------
    coop = sptlb.balance("local", timeout_s=30,
                         config=CoopConfig(variant="manual_cnst",
                                           max_rounds=20))
    print("\n== manual_cnst co-operation with region/host schedulers ==")
    print(f"feedback rounds {coop.cooperation.feedback_rounds}, "
          f"avoid constraints learned {coop.cooperation.num_rejections}, "
          f"accepted: {coop.cooperation.accepted}")
    print(f"worst-case net latency: {coop.network_p99_ms:.0f} ms "
          f"(vs {balanced.network_p99_ms:.0f} ms hierarchy-blind)")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
