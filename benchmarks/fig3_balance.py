"""Paper Fig. 3 (a, b, c): SPTLB vs per-objective greedy schedulers.

Reproduces the claim: SPTLB balances cpu, mem AND task count in one mapping;
each greedy variant balances only its own objective and leaves the others
unbalanced (sometimes past the ideal limit).

Output: per-tier utilization tables (initial / SPTLB / greedy-{cpu,mem,task})
for each objective + the spread summary + claim checks.
"""
from __future__ import annotations

import numpy as np

from benchmarks.common import comment, emit, load_cluster
from repro.core import (GreedyConfig, LocalSearchConfig, solve_greedy,
                        solve_local, utilization_fraction, validate)


def run(num_apps: int = 1200, timeout_s: int = 30):
    cluster = load_cluster(num_apps)
    p = cluster.problem
    from repro.core.sptlb import TIMEOUT_BUDGETS
    budget = TIMEOUT_BUDGETS[timeout_s]

    results = {}
    import time
    t0 = time.perf_counter()
    res = solve_local(p, LocalSearchConfig(max_iters=budget))
    results["sptlb"] = (res, time.perf_counter() - t0)
    for obj in ("cpu", "mem", "task"):
        t0 = time.perf_counter()
        g = solve_greedy(p, GreedyConfig(objective=obj, max_steps=budget))
        results[f"greedy-{obj}"] = (g, time.perf_counter() - t0)

    uf0, tf0 = utilization_fraction(p, p.assignment0)
    uf0, tf0 = np.asarray(uf0), np.asarray(tf0)

    tables = {"cpu": {}, "mem": {}, "task": {}}
    spreads = {}
    for name, (res, dt) in results.items():
        uf, tf = utilization_fraction(p, res.assignment)
        uf, tf = np.asarray(uf), np.asarray(tf)
        tables["cpu"][name] = uf[:, 0]
        tables["mem"][name] = uf[:, 1]
        tables["task"][name] = tf
        spreads[name] = {
            "cpu": float(uf[:, 0].max() - uf[:, 0].min()),
            "mem": float(uf[:, 1].max() - uf[:, 1].min()),
            "task": float(tf.max() - tf.min()),
        }
        emit(f"fig3/{name}", dt * 1e6,
             f"spread_cpu={spreads[name]['cpu']:.3f};"
             f"spread_mem={spreads[name]['mem']:.3f};"
             f"spread_task={spreads[name]['task']:.3f};"
             f"moved={res.num_moved};feasible={validate(p, res.assignment).ok}")

    initial = {"cpu": uf0[:, 0], "mem": uf0[:, 1], "task": tf0}
    for objective in ("cpu", "mem", "task"):
        ideal = 0.8 if objective == "task" else 0.7
        comment(f"--- Fig 3 ({objective}): per-tier utilization fraction "
                f"(ideal {ideal:.0%}) ---")
        header = "tier     initial  " + "  ".join(
            f"{n:>12s}" for n in results)
        comment(header)
        for t in range(p.num_tiers):
            row = f"tier_{t+1}   {initial[objective][t]:6.2f}  " + "  ".join(
                f"{tables[objective][n][t]:12.2f}" for n in results)
            comment(row)

    # --- paper-claim checks ---
    claims = []
    s = spreads
    claims.append(("sptlb balances all three objectives",
                   all(s["sptlb"][o] < max(0.5 * (initial[o].max()
                                                  - initial[o].min()), 0.12)
                       for o in ("cpu", "mem", "task"))))
    for obj in ("cpu", "mem", "task"):
        others = [o for o in ("cpu", "mem", "task") if o != obj]
        claims.append((
            f"greedy-{obj} balances {obj} but leaves another objective "
            f">=1.5x worse than sptlb",
            s[f"greedy-{obj}"][obj] < 0.6 * (initial[obj].max()
                                             - initial[obj].min())
            and any(s[f"greedy-{obj}"][o] > 1.5 * s["sptlb"][o]
                    for o in others)))
    for text, ok in claims:
        comment(f"CLAIM [{'PASS' if ok else 'FAIL'}]: {text}")
    return spreads, claims


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    run()
