"""LocalSearch engine (paper §3.2.1): greedy exploration of the move space.

"LocalSearch: Greedy exploration of search space to find a solution, can get
stuck in local minimums."

Each sweep scores *every* feasible single-app move with the exact closed-form
objective delta (core/delta.py — optionally the Pallas move_eval kernel).

Batched top-k move application: scoring the O(N*T) candidate sweep is the
expensive part, so committing only ONE move per sweep wastes almost all of
it.  Instead we reduce the sweep to a per-app best (score, tier), take the
``batch_moves`` best apps with ``lax.top_k``, and commit a conflict-free
subset in a ``lax.scan`` over the candidates in ascending-score order:

  * candidates are distinct apps by construction (one best tier per app),
  * each candidate is re-checked *incrementally* against the state left by
    the moves already accepted this sweep — destination capacity/task-limit
    headroom, the movement budget, and an exact O(T*R) delta re-evaluation
    (delta.single_move_delta) that must still be strictly improving,
  * the first candidate is exactly the single-move path's argmin and is
    accepted under exactly the old rule, so ``batch_moves=1`` reproduces the
    single-move trajectory bit-for-bit and convergence detection (no
    improving feasible move) is unchanged.

The loop runs under ``jax.lax.while_loop`` until no improving feasible move
exists or the sweep budget (the wall-clock "timeout" knob made deterministic)
runs out — but now commits up to k moves per sweep instead of 1.

An optional temperature turns best-improvement into Gumbel-softmax sampling
over improving moves — a restart-free way out of shallow local minima (kept 0
by default to stay faithful to the paper's description).  The temperature
path commits a single sampled move per sweep regardless of ``batch_moves``.
"""
from __future__ import annotations

import dataclasses
import time
from functools import partial
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.core import constraints as C
from repro.core import goals
from repro.core.delta import move_delta_cost, single_move_delta
from repro.core.problem import Problem, tier_loads

# Retrace counter: incremented at *trace* time only, so (after - before) == 0
# across a solve means the jit cache was hit (no recompilation).  Surfaced in
# SolveResult.extra and used by the shape-bucketing benchmarks.
_TRACE_COUNTS = {"local_search": 0}


def local_search_trace_count() -> int:
    """Number of times the jitted LocalSearch body has been (re)traced."""
    return _TRACE_COUNTS["local_search"]


@dataclasses.dataclass(frozen=True)
class LocalSearchConfig:
    max_iters: int = 512          # candidate-sweep budget (the timeout knob)
    tol: float = 1e-7             # minimum improvement to keep moving
    temperature: float = 0.0      # 0 = pure best-improvement
    seed: int = 0
    batch_moves: int = 16         # top-k moves committed per sweep (1 = legacy)
    # A rank-i>0 candidate is only committed if its exact re-evaluated delta
    # is at least ``batch_quality`` of the sweep-best delta.  This guards the
    # scarce movement budget: batch-committing merely-improving moves spends
    # budget the single-move path would have used on better moves later.
    # 0.0 = accept any improving candidate, 1.0 = only ties with the best.
    # 0.9 measured: converged-solution parity with single-move at N=300 and
    # a 6.5x committed-move rate at N=10_000 (0.5 trades ~15% quality for
    # 11x) — see benchmarks/solver_scale.py / BENCH_solver.json.
    batch_quality: float = 0.9


@dataclasses.dataclass
class SolveResult:
    assignment: jax.Array
    iterations: int
    converged: bool
    objective: float
    num_moved: int
    solve_time_s: float
    extra: dict = dataclasses.field(default_factory=dict)


def _weights_vector(problem: Problem) -> jax.Array:
    w = problem.weights
    return jnp.stack([w.under_ideal, w.resource_balance, w.task_balance,
                      w.movement_cost, w.criticality])


@partial(jax.jit, static_argnames=("max_iters", "temperature", "tol",
                                   "move_eval_fn", "move_best_fn",
                                   "batch_moves", "batch_quality"))
def _solve_local_jit(problem: Problem, key: jax.Array, x_init: jax.Array,
                     *, max_iters: int, temperature: float, tol: float,
                     move_eval_fn: Optional[Callable] = None,
                     move_best_fn: Optional[Callable] = None,
                     batch_moves: int = 1, batch_quality: float = 0.5):
    _TRACE_COUNTS["local_search"] += 1          # trace-time side effect only
    eval_fn = move_eval_fn or move_delta_cost
    wvec = _weights_vector(problem)
    util0, tasks0 = tier_loads(problem, x_init)
    N, T = problem.num_apps, problem.num_tiers
    k = max(1, min(int(batch_moves), N))
    feas = problem.feasible_mask()
    total_tasks = jnp.maximum(jnp.sum(problem.tasks), 1.0)
    total_crit = jnp.maximum(jnp.sum(problem.criticality), 1.0)

    def sweep_args(x, util, tasks):
        return (problem.demand, problem.tasks, problem.criticality,
                x, problem.assignment0,
                problem.capacity, problem.task_limit,
                problem.ideal_frac, problem.ideal_task_frac,
                util, tasks, wvec)

    def body_sampled(state):
        # Temperature > 0: legacy single-move Gumbel-softmax sampling.
        x, util, tasks, it, _, committed, key = state
        moves_left = C.moves_remaining(problem, x)
        delta = eval_fn(*sweep_args(x, util, tasks))
        mask = C.move_mask(problem, x, util, tasks, moves_left, feas)
        scores = jnp.where(mask, delta, jnp.inf)

        key, sub = jax.random.split(key)
        improving_mask = scores < -tol
        logits = jnp.where(improving_mask, -scores / temperature, -jnp.inf)
        flat = jax.random.categorical(sub, logits.reshape(-1))
        # If nothing improves, categorical over all -inf is undefined;
        # fall back to argmin (which will trigger convergence below).
        any_improving = jnp.any(improving_mask)
        flat = jnp.where(any_improving, flat, jnp.argmin(scores))

        n = flat // T
        t = flat % T
        best = scores[n, t]
        improving = best < -tol

        src = x[n]
        x_new = x.at[n].set(jnp.where(improving, t, src).astype(x.dtype))
        util_new = jnp.where(
            improving,
            util.at[src].add(-problem.demand[n]).at[t].add(problem.demand[n]),
            util)
        tasks_new = jnp.where(
            improving,
            tasks.at[src].add(-problem.tasks[n]).at[t].add(problem.tasks[n]),
            tasks)
        committed = committed + improving.astype(jnp.int32)
        return x_new, util_new, tasks_new, it + 1, ~improving, committed, key

    def body_topk(state):
        x, util, tasks, it, _, committed, key = state
        moves_left = C.moves_remaining(problem, x)
        if move_best_fn is not None:
            best_s, best_t = move_best_fn(*sweep_args(x, util, tasks),
                                          feas, moves_left)
        else:
            delta = eval_fn(*sweep_args(x, util, tasks))
            mask = C.move_mask(problem, x, util, tasks, moves_left, feas)
            scores = jnp.where(mask, delta, jnp.inf)
            best_t = jnp.argmin(scores, axis=1).astype(jnp.int32)
            best_s = jnp.min(scores, axis=1)

        # lax.top_k is stable on ties, so cand_n[0] is exactly the flat
        # row-major argmin the single-move path would pick.
        top_neg, cand_n = jax.lax.top_k(-best_s, k)
        cand_s = -top_neg                                   # ascending scores
        cand_t = best_t[cand_n]
        improving = cand_s[0] < -tol                        # convergence

        def commit(carry, inp):
            x, util, tasks, left, acc = carry
            idx, n, t, s = inp
            src = x[n]
            d_exact = single_move_delta(
                n, t, src, problem.demand, problem.tasks, problem.criticality,
                problem.assignment0, problem.capacity, problem.task_limit,
                problem.ideal_frac, problem.ideal_task_frac,
                util, tasks, wvec, total_tasks, total_crit)
            already = src != problem.assignment0[n]
            fits = (jnp.all(util[t] + problem.demand[n]
                            <= problem.capacity[t] + C.FEAS_TOL)
                    & (tasks[t] + problem.tasks[n]
                       <= problem.task_limit[t] + C.FEAS_TOL))
            budget_ok = already | (left > 0)
            # Candidate 0 saw exactly this state during the sweep: trust the
            # sweep score (bit-parity with the single-move path).  Later
            # candidates must still improve against the *updated* state AND
            # be within the quality window of the sweep-best move — budget
            # spent on merely-improving moves is budget the single-move path
            # would have spent on better moves later.  Budget-neutral moves
            # (already-moved apps re-targeting) skip the window.
            window_ok = d_exact <= batch_quality * cand_s[0]
            good_enough = (d_exact < -tol) & (window_ok | already)
            still_improving = jnp.where(idx == 0, s < -tol, good_enough)
            accept = ((s < -tol) & still_improving & fits & budget_ok
                      & (t != src))
            x = x.at[n].set(jnp.where(accept, t, src).astype(x.dtype))
            util = jnp.where(
                accept,
                util.at[src].add(-problem.demand[n])
                    .at[t].add(problem.demand[n]),
                util)
            tasks = jnp.where(
                accept,
                tasks.at[src].add(-problem.tasks[n])
                     .at[t].add(problem.tasks[n]),
                tasks)
            going_home = t == problem.assignment0[n]
            spend = jnp.where(already, jnp.where(going_home, -1, 0), 1)
            left = left - jnp.where(accept, spend, 0)
            acc = acc + accept.astype(jnp.int32)
            return (x, util, tasks, left, acc), None

        (x_new, util_new, tasks_new, _, acc), _ = jax.lax.scan(
            commit, (x, util, tasks, moves_left, jnp.int32(0)),
            (jnp.arange(k), cand_n, cand_t, cand_s))
        return (x_new, util_new, tasks_new, it + 1, ~improving,
                committed + acc, key)

    body = body_sampled if temperature > 0.0 else body_topk

    def cond(state):
        _, _, _, it, done, _, _ = state
        return (~done) & (it < max_iters)

    init = (x_init, util0, tasks0, jnp.int32(0), jnp.bool_(False),
            jnp.int32(0), key)
    x, util, tasks, it, done, committed, _ = jax.lax.while_loop(
        cond, body, init)
    obj = goals.objective(problem, x)
    return x, it, done, committed, obj


def solve_local(problem: Problem, config: LocalSearchConfig = LocalSearchConfig(),
                *, move_eval_fn: Optional[Callable] = None,
                move_best_fn: Optional[Callable] = None,
                init_assignment: Optional[jax.Array] = None) -> SolveResult:
    """Run LocalSearch; returns assignment + host-side stats.

    ``init_assignment`` warm-starts the search (movement budget is still
    accounted against ``problem.assignment0``) — used by OptimalSearch's
    refinement pass and by incremental re-balancing after failures.

    ``move_best_fn`` optionally replaces the sweep + per-app-argmin reduction
    with a fused implementation (kernels.ops.move_eval_best); it receives the
    move_eval argument tuple plus (feasible_mask, moves_left) and must return
    (best_score[N], best_tier[N]) with +inf for infeasible apps.

    ``SolveResult.extra`` reports: sweeps, committed_moves, batch_moves,
    retraced (False == jit cache hit), trace_count, and solve_s.
    """
    t0 = time.perf_counter()
    traces_before = local_search_trace_count()
    key = jax.random.PRNGKey(config.seed)
    x0 = problem.assignment0 if init_assignment is None else init_assignment
    x, it, done, committed, obj = _solve_local_jit(
        problem, key, x0, max_iters=config.max_iters,
        temperature=config.temperature, tol=config.tol,
        move_eval_fn=move_eval_fn, move_best_fn=move_best_fn,
        batch_moves=config.batch_moves, batch_quality=config.batch_quality)
    x = jax.block_until_ready(x)
    dt = time.perf_counter() - t0
    return SolveResult(
        assignment=x,
        iterations=int(it),
        converged=bool(done),
        objective=float(obj),
        num_moved=int(jnp.sum((x != problem.assignment0) & problem.valid)),
        solve_time_s=dt,
        extra={
            "sweeps": int(it),
            "committed_moves": int(committed),
            "batch_moves": config.batch_moves,
            "retraced": local_search_trace_count() > traces_before,
            "trace_count": local_search_trace_count(),
            "solve_s": dt,
        },
    )
