"""Hard constraints (paper §3.2.1, items 1-4) — move masks and validators.

Constraints are "all equally important to be satisfiable to get a valid
solution".  The solvers enforce them *by construction* through the move mask;
``validate`` is the post-hoc oracle used by tests, the decision-execution
stage (§3.3: "decision evaluation can also result in finding bugs with the
solver"), and the hierarchy loop.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core.problem import Problem, tier_loads

# Absolute slack on the destination-headroom checks (constraints 1-2).  The
# single source of truth for every re-statement of the fit test: move_mask,
# the fused-best oracle (delta.move_best_per_app), the batched commit scan
# (solver_local), and the Pallas kernel's fraction-space form.
FEAS_TOL = 1e-6


def destination_fits(demand: jax.Array, tasks: jax.Array,
                     capacity: jax.Array, task_limit: jax.Array,
                     util: jax.Array, tier_tasks: jax.Array) -> jax.Array:
    """bool[N, T]: app n's demand fits tier t's remaining headroom
    (constraints 1 + 2, incremental form shared by all sweep paths)."""
    fits = jnp.all(util[None, :, :] + demand[:, None, :]
                   <= capacity[None, :, :] + FEAS_TOL, axis=-1)
    return fits & (tier_tasks[None, :] + tasks[:, None]
                   <= task_limit[None, :] + FEAS_TOL)


@dataclasses.dataclass(frozen=True)
class Violations:
    """Host-side constraint report."""

    capacity_exceeded: bool       # constraint 1
    task_limit_exceeded: bool     # constraint 2
    move_budget_exceeded: bool    # constraint 3
    slo_violated: bool            # constraint 4
    avoid_violated: bool          # hierarchy avoid pairs (modelled like 4)
    num_moved: int
    move_budget: int

    @property
    def ok(self) -> bool:
        return not (self.capacity_exceeded or self.task_limit_exceeded
                    or self.move_budget_exceeded or self.slo_violated
                    or self.avoid_violated)


def validate(problem: Problem, assignment: jax.Array,
             *, allow_preexisting: bool = True) -> Violations:
    """Check all hard constraints on a final assignment.

    ``allow_preexisting``: the initial (collected) state may already violate
    capacity — the paper's tier 3 starts hot.  A solution is only charged for
    violations it *introduces or keeps for apps it was free to move*; with the
    flag set we compare against the initial state's violations per tier.
    """
    util, tasks = tier_loads(problem, assignment)
    util0, tasks0 = tier_loads(problem, problem.assignment0)

    cap_over = util > problem.capacity + 1e-4
    task_over = tasks > problem.task_limit + 1e-4
    if allow_preexisting:
        cap_over = cap_over & ~(util0 > problem.capacity + 1e-4)
        task_over = task_over & ~(tasks0 > problem.task_limit + 1e-4)

    moved = assignment != problem.assignment0
    num_moved = int(jnp.sum(moved))
    budget = int(problem.move_budget)

    slo_ok = problem.slo_allowed[assignment, problem.slo]      # [N]
    avoid_hit = problem.avoid[jnp.arange(problem.num_apps), assignment]
    # Apps that never moved keep their (possibly grandfathered) placement.
    slo_bad = jnp.any(~slo_ok & moved)
    avoid_bad = jnp.any(avoid_hit & moved)

    return Violations(
        capacity_exceeded=bool(jnp.any(cap_over)),
        task_limit_exceeded=bool(jnp.any(task_over)),
        move_budget_exceeded=num_moved > budget,
        slo_violated=bool(slo_bad),
        avoid_violated=bool(avoid_bad),
        num_moved=num_moved,
        move_budget=budget,
    )


def move_mask(problem: Problem, assignment: jax.Array,
              util: jax.Array, tasks: jax.Array,
              moves_left: jax.Array,
              feasible: jax.Array | None = None) -> jax.Array:
    """bool[N, T]: is moving app n to tier t feasible *right now*?

    Encodes constraints 1-4 incrementally:
      1/2: destination tier load + app demand must stay within capacity/limit
      3:   if the app has not moved yet, the move budget must not be exhausted
           (moving an already-moved app again, or back home, is budget-neutral
           or budget-freeing)
      4:   SLO table + avoid matrix membership.

    ``feasible`` is ``problem.feasible_mask()`` computed once by a caller
    that builds masks in a loop; it does not depend on the assignment.
    """
    T = problem.num_tiers
    feas = problem.feasible_mask() if feasible is None else feasible  # SLO + avoid

    # Capacity feasibility at destination: util[t] + d[n] <= C[t] (both resources).
    fits = destination_fits(problem.demand, problem.tasks, problem.capacity,
                            problem.task_limit, util, tasks)

    # Movement budget: an app not yet moved consumes budget unless target ==
    # current tier; an app already moved can re-target freely (its budget is
    # already spent; moving home refunds).
    already_moved = assignment != problem.assignment0           # [N]
    have_budget = moves_left > 0
    budget_ok = already_moved[:, None] | have_budget            # [N, T]
    # Staying put is always "feasible" but never an improvement; exclude it so
    # argmax never proposes a no-op.
    not_self = jnp.arange(T)[None, :] != assignment[:, None]

    return feas & fits & budget_ok & not_self


def moves_remaining(problem: Problem, assignment: jax.Array) -> jax.Array:
    moved = jnp.sum((assignment != problem.assignment0).astype(jnp.int32))
    return problem.move_budget - moved
