"""Benchmark entry point: one benchmark per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (comment lines start with '#').

  fig3  — SPTLB vs greedy, 3 objectives       (paper Fig. 3 a/b/c)
  fig4  — network p99 across integrations     (paper Fig. 4)
  fig5  — pareto: balance vs solve time       (paper Fig. 5)
  solver_scale — scheduler hot-spot scaling   (supporting)
"""
from __future__ import annotations

import argparse
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    choices=[None, "fig3", "fig4", "fig5", "solver_scale"])
    ap.add_argument("--num-apps", type=int, default=1200)
    ap.add_argument("--fast", action="store_true",
                    help="30s-timeout budgets only (CI-friendly)")
    args = ap.parse_args()

    timeouts = (30,) if args.fast else (30, 60, 600)

    from benchmarks import fig3_balance, fig4_network, fig5_pareto, solver_scale
    from benchmarks.common import comment

    t0 = time.time()
    print("name,us_per_call,derived")
    if args.only in (None, "fig3"):
        fig3_balance.run(args.num_apps)
    if args.only in (None, "fig4"):
        fig4_network.run(args.num_apps, timeouts=timeouts)
    if args.only in (None, "fig5"):
        fig5_pareto.run(args.num_apps, timeouts=timeouts)
    if args.only in (None, "solver_scale"):
        solver_scale.run()
    comment(f"total benchmark time: {time.time() - t0:.1f}s")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
