"""Exact O(N*T) delta-cost evaluation for all single-app candidate moves.

This is the LocalSearch hot-spot: at Meta scale (1e5 apps x 1e2 tiers) each
solver iteration scores every (app, tier) candidate.  The math below computes
the *exact* change of the scalarized objective (goals.objective) if app n is
re-assigned to tier t, in closed form from per-tier sufficient statistics —
no re-aggregation over apps.

The flat-array signature exists so that:
  * solver_local.py calls it through kernels/ops.py (XLA or Pallas impl),
  * kernels/ref.py re-exports it as the oracle for the Pallas kernel tests.

Derivation (per resource r, moving n: a -> t, load fractions f):
  f_a' = f_a - d[n,r]/C[a,r],   f_t' = f_t + d[n,r]/C[t,r]
  balance  = sum_u (f_u - mean)^2 = sum_u f_u^2 - T * mean^2
  d(sum f^2) = f_a'^2 - f_a^2 + f_t'^2 - f_t^2
  d(mean)    = (d[n,r]/C[t,r] - d[n,r]/C[a,r]) / T
  d(balance) = d(sum f^2) - T * ((mean + d(mean))^2 - mean^2)
  d(hinge)   = h(f_a')^2 - h(f_a)^2 + h(f_t')^2 - h(f_t)^2,  h(x)=max(0, x-ideal)
Movement / criticality terms flip with the move indicator delta.

Three entry points share the math:
  * ``move_delta_cost``     — the full [N, T] candidate sweep,
  * ``single_move_delta``   — one (app, tier) candidate re-evaluated against a
                              *partially updated* state; the incremental
                              re-check inside the batched top-k commit scan
                              (solver_local applies k moves per sweep and must
                              keep every accepted move strictly improving),
  * ``move_best_per_app``   — sweep + feasibility mask + per-app (score, tier)
                              argmin reduction fused in one jitted call; the
                              XLA oracle for kernels/move_eval.py's fused-best
                              Pallas kernel (output bandwidth N*2 vs N*T).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def at_tier(table: jax.Array, src: jax.Array) -> jax.Array:
    """``table[src]`` for every app, as a select over the tier axis.

    Exactly one term of each sum is non-zero, so this is bit-identical to
    the gather.  Inside the sweep loop a gather with an app-sized result is
    not fused; this select-and-reduce is, with apps on the lanes.
    """
    T = table.shape[0]
    onehot = src[:, None] == jnp.arange(T)[None, :]              # [N, T]
    onehot = onehot.reshape(onehot.shape + (1,) * (table.ndim - 1))
    return jnp.sum(jnp.where(onehot, table[None], 0.0), axis=1)


def move_delta_cost(
    demand: jax.Array,        # f32[N, R]
    tasks: jax.Array,         # f32[N]
    criticality: jax.Array,   # f32[N]
    assignment: jax.Array,    # i32[N] current
    assignment0: jax.Array,   # i32[N] original
    capacity: jax.Array,      # f32[T, R]
    task_limit: jax.Array,    # f32[T]
    ideal_frac: jax.Array,    # f32[T, R]
    ideal_task_frac: jax.Array,  # f32[T]
    util: jax.Array,          # f32[T, R] current absolute loads
    tier_tasks: jax.Array,    # f32[T]    current task loads
    weights: jax.Array,       # f32[5] (under_ideal, resource_balance,
                              #         task_balance, movement, criticality)
) -> jax.Array:
    """Returns delta[N, T]: objective change if app n moves to tier t.

    delta[n, assignment[n]] is exactly 0 (no-op move).
    """
    N, R = demand.shape
    T = capacity.shape[0]
    f = util / capacity                          # [T, R]
    g = tier_tasks / task_limit                  # [T]
    mean_f = jnp.mean(f, axis=0)                 # [R]
    mean_g = jnp.mean(g)

    # Per-app source-tier quantities (selects, not gathers: see at_tier).
    src = assignment                             # [N]
    C_src = at_tier(capacity, src)               # [N, R]
    f_src = at_tier(f, src)                      # [N, R]
    ideal_src = at_tier(ideal_frac, src)         # [N, R]
    d_over_Csrc = demand / C_src                 # [N, R]
    f_src_new = f_src - d_over_Csrc              # [N, R]

    # Destination quantities, broadcast over T.
    d_over_Cdst = demand[:, None, :] / capacity[None, :, :]        # [N, T, R]
    f_dst = f[None, :, :]                                          # [1, T, R]
    f_dst_new = f_dst + d_over_Cdst                                # [N, T, R]

    # --- goal 6: resource balance delta ---
    d_sumsq = (f_src_new[:, None, :] ** 2 - f_src[:, None, :] ** 2
               + f_dst_new ** 2 - f_dst ** 2)                      # [N, T, R]
    d_mean = (d_over_Cdst - d_over_Csrc[:, None, :]) / T           # [N, T, R]
    new_mean = mean_f[None, None, :] + d_mean
    d_balance = d_sumsq - T * (new_mean ** 2 - mean_f[None, None, :] ** 2)
    d_resource_balance = jnp.sum(d_balance, axis=-1)               # [N, T]

    # --- goal 5: under-ideal hinge delta (resources) ---
    def h2(x, ideal):
        h = jnp.maximum(x - ideal, 0.0)
        return h * h

    d_hinge = (h2(f_src_new[:, None, :], ideal_src[:, None, :])
               - h2(f_src[:, None, :], ideal_src[:, None, :])
               + h2(f_dst_new, ideal_frac[None, :, :])
               - h2(f_dst, ideal_frac[None, :, :]))                # [N, T, R]
    d_under_ideal = jnp.sum(d_hinge, axis=-1)                      # [N, T]

    # --- task-count analogues (goals 5 + 7) ---
    K_src = at_tier(task_limit, src)                               # [N]
    g_src = at_tier(g, src)
    gideal_src = at_tier(ideal_task_frac, src)
    k_over_Ksrc = tasks / K_src
    g_src_new = g_src - k_over_Ksrc

    k_over_Kdst = tasks[:, None] / task_limit[None, :]             # [N, T]
    g_dst = g[None, :]
    g_dst_new = g_dst + k_over_Kdst

    d_sumsq_t = (g_src_new[:, None] ** 2 - g_src[:, None] ** 2
                 + g_dst_new ** 2 - g_dst ** 2)
    d_mean_t = (k_over_Kdst - k_over_Ksrc[:, None]) / T
    new_mean_t = mean_g + d_mean_t
    d_task_balance = d_sumsq_t - T * (new_mean_t ** 2 - mean_g ** 2)

    d_under_ideal = d_under_ideal + (
        h2(g_src_new[:, None], gideal_src[:, None]) - h2(g_src[:, None], gideal_src[:, None])
        + h2(g_dst_new, ideal_task_frac[None, :]) - h2(g_dst, ideal_task_frac[None, :]))

    # --- goals 8 + 9: movement indicator delta ---
    was_moved = (assignment != assignment0).astype(jnp.float32)    # [N]
    will_move = (jnp.arange(T)[None, :] != assignment0[:, None]).astype(jnp.float32)
    d_moved = will_move - was_moved[:, None]                       # [N, T] in {-1, 0, 1}
    total_tasks = jnp.maximum(jnp.sum(tasks), 1.0)
    total_crit = jnp.maximum(jnp.sum(criticality), 1.0)
    d_movement = d_moved * (tasks / total_tasks)[:, None]
    d_criticality = d_moved * (criticality / total_crit)[:, None]

    delta = (weights[0] * d_under_ideal
             + weights[1] * d_resource_balance
             + weights[2] * d_task_balance
             + weights[3] * d_movement
             + weights[4] * d_criticality)

    # Self-moves are exactly zero by construction up to fp error; pin them.
    self_move = jnp.arange(T)[None, :] == assignment[:, None]
    return jnp.where(self_move, 0.0, delta)


def single_move_delta(
    n: jax.Array,             # i32[] candidate app
    t: jax.Array,             # i32[] candidate destination tier
    src: jax.Array,           # i32[] app n's *current* tier
    demand: jax.Array,        # f32[N, R]
    tasks: jax.Array,         # f32[N]
    criticality: jax.Array,   # f32[N]
    assignment0: jax.Array,   # i32[N]
    capacity: jax.Array,      # f32[T, R]
    task_limit: jax.Array,    # f32[T]
    ideal_frac: jax.Array,    # f32[T, R]
    ideal_task_frac: jax.Array,  # f32[T]
    util: jax.Array,          # f32[T, R] *current* absolute loads
    tier_tasks: jax.Array,    # f32[T]
    weights: jax.Array,       # f32[5]
    total_tasks: jax.Array,   # f32[] precomputed sum(tasks) (clamped >= 1)
    total_crit: jax.Array,    # f32[] precomputed sum(criticality) (>= 1)
) -> jax.Array:
    """Exact scalar objective delta for ONE candidate move n: src -> t.

    Same closed forms as ``move_delta_cost`` but O(T*R) instead of O(N*T*R),
    so the batched commit scan can re-score each surviving top-k candidate
    against the state left behind by the moves already accepted this sweep.
    """
    T = capacity.shape[0]
    f = util / capacity                                  # [T, R]
    g = tier_tasks / task_limit                          # [T]
    mean_f = jnp.mean(f, axis=0)
    mean_g = jnp.mean(g)

    def h2(x, ideal):
        h = jnp.maximum(x - ideal, 0.0)
        return h * h

    d = demand[n]                                        # [R]
    dC_src = d / capacity[src]
    dC_dst = d / capacity[t]
    f_src, f_dst = f[src], f[t]
    f_src_new = f_src - dC_src
    f_dst_new = f_dst + dC_dst
    d_sumsq = f_src_new ** 2 - f_src ** 2 + f_dst_new ** 2 - f_dst ** 2
    new_mean = mean_f + (dC_dst - dC_src) / T
    d_resource_balance = jnp.sum(d_sumsq - T * (new_mean ** 2 - mean_f ** 2))
    d_under = jnp.sum(h2(f_src_new, ideal_frac[src]) - h2(f_src, ideal_frac[src])
                      + h2(f_dst_new, ideal_frac[t]) - h2(f_dst, ideal_frac[t]))

    k = tasks[n]
    dK_src = k / task_limit[src]
    dK_dst = k / task_limit[t]
    g_src, g_dst = g[src], g[t]
    g_src_new = g_src - dK_src
    g_dst_new = g_dst + dK_dst
    d_sumsq_t = g_src_new ** 2 - g_src ** 2 + g_dst_new ** 2 - g_dst ** 2
    new_mean_t = mean_g + (dK_dst - dK_src) / T
    d_task_balance = d_sumsq_t - T * (new_mean_t ** 2 - mean_g ** 2)
    d_under = d_under + (h2(g_src_new, ideal_task_frac[src])
                         - h2(g_src, ideal_task_frac[src])
                         + h2(g_dst_new, ideal_task_frac[t])
                         - h2(g_dst, ideal_task_frac[t]))

    was_moved = (src != assignment0[n]).astype(jnp.float32)
    will_move = (t != assignment0[n]).astype(jnp.float32)
    d_moved = will_move - was_moved
    d_movement = d_moved * tasks[n] / total_tasks
    d_criticality = d_moved * criticality[n] / total_crit

    return (weights[0] * d_under
            + weights[1] * d_resource_balance
            + weights[2] * d_task_balance
            + weights[3] * d_movement
            + weights[4] * d_criticality)


def move_best_per_app(
    demand: jax.Array, tasks: jax.Array, criticality: jax.Array,
    assignment: jax.Array, assignment0: jax.Array,
    capacity: jax.Array, task_limit: jax.Array,
    ideal_frac: jax.Array, ideal_task_frac: jax.Array,
    util: jax.Array, tier_tasks: jax.Array, weights: jax.Array,
    feasible: jax.Array,      # bool[N, T] static SLO/avoid/validity mask
    moves_left: jax.Array,    # i32[] remaining movement budget
) -> tuple[jax.Array, jax.Array]:
    """Fused sweep + move-mask + per-app argmin: (best_score[N], best_tier[N]).

    Mask semantics match constraints.move_mask exactly (capacity/task-limit
    headroom with the same 1e-6 tolerance, budget, SLO/avoid, no self-moves);
    infeasible apps get score +inf.  This is the reduction the batched
    LocalSearch actually needs — only the top-k of these N scores is ever
    looked at — and the contract the fused Pallas kernel is tested against.
    """
    from repro.core.constraints import destination_fits

    T = capacity.shape[0]
    delta = move_delta_cost(demand, tasks, criticality, assignment,
                            assignment0, capacity, task_limit, ideal_frac,
                            ideal_task_frac, util, tier_tasks, weights)
    fits = destination_fits(demand, tasks, capacity, task_limit,
                            util, tier_tasks)
    already_moved = assignment != assignment0
    budget_ok = already_moved[:, None] | (moves_left > 0)
    not_self = jnp.arange(T)[None, :] != assignment[:, None]
    mask = feasible & fits & budget_ok & not_self
    scores = jnp.where(mask, delta, jnp.inf)
    best_t = jnp.argmin(scores, axis=1).astype(jnp.int32)
    best_s = jnp.min(scores, axis=1)
    return best_s, best_t
