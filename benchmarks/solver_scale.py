"""Solver hot-spot scaling: move_eval throughput, batched-vs-single-move
LocalSearch iteration rate, cooperation-round phase split, and jit-cache
behaviour under drifting app counts (the paper's "TBs per second" scale
argument applied to the scheduler itself).

Emits CSV rows like every other benchmark AND writes ``BENCH_solver.json``
at the repo root so the solver-throughput trajectory is tracked PR-over-PR:
  * local_search: committed moves/sec for batch_moves=1 vs 16 (the PR 1
    acceptance number: >=5x at N=10_000),
  * cooperate: manual_cnst pass with region pre-masking off vs on —
    per-phase split (solve / pack / region / host glue / feedback), rounds,
    region+host rejection breakdown, pack dispatch/retrace counters (the
    PR 2 acceptance numbers: host_side_frac <= 0.10 and >=1.5x total
    speedup at N=10_000 with premask on, at 0 region rejections and an
    objective no worse than the unmasked path),
  * bucketing: LocalSearch retrace counts across drifting app counts with
    shape-bucketed padding on vs off.

Also benches the Pallas kernels in interpret mode for *correct-path* parity;
interpret-mode timing is NOT a TPU number (the roofline for the kernel is
derived in EXPERIMENTS.md §Roofline from its arithmetic intensity instead).

``--smoke`` shrinks every size so CI can run the whole file in seconds.
"""
from __future__ import annotations

import argparse
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import comment, emit, random_problem_arrays
from repro.core import (CoopConfig, LocalSearchConfig, Sptlb,
                        generate_cluster, solve_local)
from repro.core.sptlb import engine_fn
from repro.core.solver_local import local_search_trace_count
from repro.kernels import ops
from repro.shard import FleetConfig, solve_fleet, synthetic_fleet

RESULTS: dict = {}


def bench_move_eval(N: int, T: int, reps: int = 5):
    args = random_problem_arrays(N, T, seed=0)
    fn = jax.jit(lambda *a: ops.move_eval(*a, impl="xla"))
    fn(*args).block_until_ready()
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn(*args).block_until_ready()
        times.append(time.perf_counter() - t0)
    us = float(np.median(times)) * 1e6
    candidates_per_s = N * T / (us / 1e6)
    emit(f"solver_scale/move_eval/N{N}xT{T}", us,
         f"candidates_per_s={candidates_per_s:.3e}")
    RESULTS.setdefault("move_eval", {})[f"N{N}xT{T}"] = {
        "us_per_sweep": us, "candidates_per_s": candidates_per_s}
    return us


def bench_local_search_batched(N: int, sweeps: int = 64, batch: int = 16):
    """Committed-moves/sec of the top-k batched path vs the single-move path.

    Both variants run the same candidate-sweep budget; rate is measured on a
    second (jit-warm) run.
    """
    cluster = generate_cluster(num_apps=N, seed=1)
    p = cluster.problem
    rates = {}
    for bm in (1, batch):
        cfg = LocalSearchConfig(max_iters=sweeps, batch_moves=bm)
        solve_local(p, cfg)                                  # compile + warm
        t0 = time.perf_counter()
        res = solve_local(p, cfg)
        dt = time.perf_counter() - t0
        committed = res.extra["committed_moves"]
        rate = committed / dt if dt > 0 else float("inf")
        rates[bm] = rate
        emit(f"solver_scale/local_search/N{N}/batch{bm}", dt * 1e6,
             f"sweeps={res.extra['sweeps']};committed={committed};"
             f"moves_per_s={rate:.1f};objective={res.objective:.4g}")
        RESULTS.setdefault("local_search", {}).setdefault(f"N{N}", {})[
            f"batch{bm}"] = {
                "seconds": dt, "sweeps": res.extra["sweeps"],
                "committed_moves": committed, "moves_per_s": rate,
                "objective": res.objective}
    speedup = rates[batch] / rates[1] if rates[1] > 0 else float("inf")
    comment(f"N={N}: batched committed-move rate speedup = {speedup:.1f}x")
    RESULTS["local_search"][f"N{N}"]["speedup"] = speedup
    return speedup


def bench_cooperate(N: int, timeout_s: int = 8):
    """Cooperation section (PR 2 tentpole + PR 5 bus): per-phase split,
    rounds, per-level rejection breakdown, and pack dispatch/retrace
    counters of a manual_cnst pass with level pre-masking off vs on, all
    through the generic cooperation bus (``CoopConfig`` + default
    region+host ``Hierarchy``).  host_side_frac is everything that is
    neither the solver nor the levels' compiled dispatches (acceptance:
    <=0.10 at N=10_000 with premask on); bus_overhead_frac isolates the
    generic bus's own routing glue (wall-clock belonging to no phase),
    gated <= ~5% so the protocol refactor can never quietly tax the
    two-level hot path.  A third record runs the region+host+shard stack —
    the plugin-level cost is observable, not gated."""
    cluster = generate_cluster(num_apps=N, seed=2)
    s = Sptlb(cluster)
    rec = {}
    cases = {
        "unmasked": CoopConfig(premask=False),
        "premask": CoopConfig(premask=True),
        "shard_stack": CoopConfig(premask=True,
                                  levels=("region", "host", "shard")),
    }
    for label, cfg in cases.items():
        s.balance("local", timeout_s=timeout_s, config=cfg)      # warm jit
        d = s.balance("local", timeout_s=timeout_s, config=cfg)
        tm = dict(d.cooperation.timings)
        rec[label] = {**tm, "objective": d.solve.objective,
                      "d2b": d.difference_to_balance,
                      "accepted": d.cooperation.accepted}
        shard_rej = tm.get("shard_rejections", "-")
        emit(f"solver_scale/cooperate/N{N}/{label}", tm["total_s"] * 1e6,
             f"rounds={tm['rounds']};region_rej={tm['region_rejections']};"
             f"host_rej={tm['host_rejections']};shard_rej={shard_rej};"
             f"solve_s={tm['solve_s']:.3f};"
             f"pack_s={tm['pack_s']:.4f};"
             f"pack_dispatches={tm['pack_dispatches']};"
             f"pack_retraces={tm['pack_retraces']};"
             f"host_side_frac={tm['host_side_frac']:.3f};"
             f"bus_overhead_frac={tm['bus_overhead_frac']:.3f};"
             f"objective={d.solve.objective:.4g}")
    rec["speedup_premask"] = (rec["unmasked"]["total_s"]
                              / max(rec["premask"]["total_s"], 1e-12))
    comment(f"N={N}: premask {rec['speedup_premask']:.2f}x faster, "
            f"rounds {rec['unmasked']['rounds']} -> {rec['premask']['rounds']}, "
            f"region rejections {rec['unmasked']['region_rejections']} -> "
            f"{rec['premask']['region_rejections']}, host_side_frac "
            f"{rec['premask']['host_side_frac']:.3f}, bus_overhead_frac "
            f"{rec['premask']['bus_overhead_frac']:.3f}, 3-level stack "
            f"{rec['shard_stack']['total_s']:.3f}s")
    RESULTS.setdefault("cooperate", {})[f"N{N}"] = rec
    return rec


def bench_bucketing(sizes: tuple, timeout_s: int = 4):
    """LocalSearch retrace counts across drifting app counts."""
    counts = {}
    for bucketed in (True, False):
        total = 0
        for i, N in enumerate(sizes):
            cluster = generate_cluster(num_apps=N, seed=10 + i)
            fn = engine_fn("local", timeout_s, bucket_apps=bucketed)
            before = local_search_trace_count()
            fn(cluster.problem)
            total += local_search_trace_count() - before
        counts["bucketed" if bucketed else "unbucketed"] = total
    emit(f"solver_scale/bucketing/{'x'.join(map(str, sizes))}", 0.0,
         f"retraces_bucketed={counts['bucketed']};"
         f"retraces_unbucketed={counts['unbucketed']}")
    RESULTS["bucketing"] = {"sizes": list(sizes), **counts}
    return counts


def bench_shard_scale(cases, timeout_s: int = 30):
    """Sharded fleet pass (PR 8): apps/sec and rebalance-pass wall-clock vs
    shard count, on the vectorized synthetic fleet (generate_cluster's
    Python loops do not reach 100k+ apps).  One warm pass compiles the
    (S, Nb, Tb) executable; the measured pass is jit-warm, so the tracked
    number is steady-state rebalance latency, not compile time.  The hard
    invariant tracked alongside throughput: zero apps stranded after the
    partition -> solve -> merge -> coordinate pass."""
    clusters: dict = {}
    for N, T, S in cases:
        if (N, T) not in clusters:
            t0 = time.perf_counter()
            clusters[(N, T)] = synthetic_fleet(N, num_tiers=T, seed=9)
            comment(f"synthetic_fleet N={N} T={T} built in "
                    f"{time.perf_counter() - t0:.1f}s")
        cluster = clusters[(N, T)]
        cfg = FleetConfig(num_shards=S, timeout_s=timeout_s)
        solve_fleet(cluster, cfg)                            # compile + warm
        fd = solve_fleet(cluster, cfg)
        key = f"N{N}_S{S}"
        emit(f"solver_scale/shard_scale/{key}", fd.timings["total_s"] * 1e6,
             f"apps_per_s={fd.apps_per_s:.3e};stranded={fd.stranded};"
             f"migrations={fd.migrations};saturated={fd.saturated};"
             f"coord_frac={fd.coordinator_overhead_frac:.4f};"
             f"solve_s={fd.timings['solve_s']:.3f};"
             f"objective={fd.objective:.4g}")
        RESULTS.setdefault("shard_scale", {})[key] = {
            "apps": N, "tiers": T, "num_shards": S,
            "app_bucket": fd.sharded.app_bucket,
            "tier_bucket": fd.sharded.tier_bucket,
            "apps_per_s": fd.apps_per_s,
            "stranded": fd.stranded, "migrations": fd.migrations,
            "saturated": fd.saturated,
            "coordinator_overhead_frac": fd.coordinator_overhead_frac,
            "objective": fd.objective, **fd.timings}
    recs = RESULTS.get("shard_scale", {})
    if recs:
        best = max(recs.values(), key=lambda r: r["apps_per_s"])
        comment(f"shard_scale: best apps/sec {best['apps_per_s']:.3e} at "
                f"N={best['apps']} S={best['num_shards']}")


def bench_pallas_parity(N: int, T: int):
    t0 = time.perf_counter()
    comment("pallas interpret-mode parity check (runs the kernel bodies)")
    args = random_problem_arrays(N, T, seed=7)
    d_ref = ops.move_eval(*args, impl="xla")
    d_pal = ops.move_eval(*args, impl="pallas", interpret=True)
    err = float(jnp.max(jnp.abs(d_ref - d_pal))
                / (jnp.max(jnp.abs(d_ref)) + 1e-9))
    emit("solver_scale/move_eval_pallas_parity",
         (time.perf_counter() - t0) * 1e6, f"rel_err={err:.2e}")
    t0 = time.perf_counter()
    feas = jnp.ones((N, T), bool)
    s_ref, t_ref = ops.move_eval_best(*args, feas, jnp.int32(5), impl="xla")
    s_pal, t_pal = ops.move_eval_best(*args, feas, jnp.int32(5), impl="pallas", interpret=True)
    finite = np.isfinite(np.asarray(s_ref))
    scale = float(jnp.max(jnp.abs(jnp.where(finite, s_ref, 0.0)))) + 1e-9
    err = float(np.max(np.abs((np.asarray(s_pal) - np.asarray(s_ref))[finite]))
                / scale)
    tier_agree = float(np.mean(np.asarray(t_pal)[finite]
                               == np.asarray(t_ref)[finite]))
    emit("solver_scale/move_eval_best_pallas_parity",
         (time.perf_counter() - t0) * 1e6,
         f"rel_err={err:.2e};tier_agreement={tier_agree:.3f}")
    RESULTS["pallas_parity"] = {"rel_err": err, "tier_agreement": tier_agree}


def run(smoke: bool = False):
    comment(f"--- solver hot-spot scaling (XLA path, CPU{', smoke' if smoke else ''}) ---")
    if smoke:
        for N, T in ((1_000, 5), (2_000, 16)):
            bench_move_eval(N, T)
        bench_local_search_batched(500, sweeps=16)
        bench_cooperate(400, timeout_s=4)
        bench_bucketing((300, 320, 350), timeout_s=4)
        bench_shard_scale(((2_000, 16, 1), (2_000, 16, 4)))
        bench_pallas_parity(512, 16)
    else:
        for N, T in ((1_000, 5), (10_000, 16), (100_000, 64), (100_000, 128)):
            bench_move_eval(N, T)
        for N in (1_000, 3_000):
            bench_local_search_batched(N, sweeps=32)
        bench_local_search_batched(10_000, sweeps=64)   # the acceptance number
        bench_cooperate(10_000, timeout_s=8)
        bench_bucketing((3_000, 3_100, 3_250), timeout_s=4)
        bench_shard_scale(((100_000, 64, 4), (100_000, 64, 16),
                           (1_000_000, 64, 8), (1_000_000, 64, 32)))
        bench_pallas_parity(4_096, 128)

    # Smoke numbers must not clobber the tracked fleet-scale record.
    name = "BENCH_solver_smoke.json" if smoke else "BENCH_solver.json"
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
