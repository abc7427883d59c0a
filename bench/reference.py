"""The plain reference: the fleet as f64 numpy arrays, its rules and objective.

Written from the source paper's problem statement (section 3.2) and the
configuration's stated guarantees, independent of the program: it imports
nothing of ``repro`` and reads only the data the benchmark made itself
(``bench/fleets.py``) and the events it submitted (``bench/schedule.py``).

``Reference.check`` judges one applied placement against the incumbent it
replaced: per-tier capacity and task limits (no tier may be pushed over a
limit it was under), SLO eligibility of every moved app, no live app left on
an ineligible tier, the movement budget (at most ``ceil(move_frac * N)``
apps move), an f64 objective against the objective the device reported, and
no objective worse than the incumbent's.  For a pass of the whole
cooperation bus it also judges the two lower levels' guarantees: every
moved app's data region lies within the latency budget of every region of
its new tier (the region level), and every app moved into a tier fits,
first-fit decreasing, onto the tier's hosts beside the apps that stayed
there (the host level).  ``d2b_mean`` replays the event log against the
applied placements for the time-weighted difference to balance (the
paper's Fig. 5 metric) under the true demand.

Events reach the reference through ``replay``, which hands each to the
``replay`` function of its kind's module, ``bench/events/<kind>.py``.
"""

from __future__ import annotations

import numpy as np

import plugins

# Goal weights of the paper's section 3.2.1 goals 5-9 (under ideal, resource
# balance, task balance, movement, criticality), decade-separated so the
# scalar objective keeps the goals' priority order.
WEIGHTS = (1e4, 1e3, 1e2, 1e1, 1e0)
# Hard-limit slack relative to the limit: the device sums ~1e3 f32 demands
# per tier, about 1e-6 relative error; 1e-5 leaves room and catches any real
# overload.
LIMIT_RTOL = 1e-5
# Objective magnitudes below this are compared in absolute terms.
OBJECTIVE_FLOOR = 10.0


class Reference:
    """The fleet as f64 arrays; telemetry is applied as it was submitted."""

    def __init__(self, fleet, region_budget_ms: float | None = None):
        f64 = lambda v: np.asarray(v, np.float64).copy()  # noqa: E731
        self.demand = f64(fleet.demand)
        self.tasks = f64(fleet.tasks)
        self.crit = f64(fleet.criticality)
        self.slo = np.asarray(fleet.slo, np.int64)
        self.capacity = f64(fleet.capacity)
        self.task_limit = f64(fleet.task_limit)
        T = self.capacity.shape[0]
        self.ideal = np.full_like(self.capacity, fleet.ideal_frac)
        self.ideal_t = np.full(T, fleet.ideal_task_frac)
        self.slo_allowed = np.asarray(fleet.slo_allowed, bool)
        self.move_frac = float(fleet.move_frac)
        self.app_region = np.asarray(fleet.app_region, np.int64)
        self.tier_regions = np.asarray(fleet.tier_regions, bool)
        self.region_latency = f64(fleet.region_latency)
        self.hosts_per_tier = np.asarray(fleet.hosts_per_tier, np.int64)
        self.host_capacity = f64(fleet.host_capacity)
        self.region_budget_ms = region_budget_ms

    @property
    def num_apps(self) -> int:
        return self.demand.shape[0]

    def replay(self, kind: str, payload: dict) -> None:
        plugins.load("events", kind).replay(self, payload)

    def loads(self, x):
        T = self.capacity.shape[0]
        util = np.stack([np.bincount(x, self.demand[:, r], minlength=T)
                         for r in range(self.capacity.shape[1])], axis=1)
        return util, np.bincount(x, self.tasks, minlength=T)

    def objective(self, x, x0) -> float:
        util, tasks = self.loads(x)
        uf = util / self.capacity
        tf = tasks / self.task_limit
        under_ideal = (np.sum(np.maximum(uf - self.ideal, 0.0) ** 2)
                       + np.sum(np.maximum(tf - self.ideal_t, 0.0) ** 2))
        balance = np.sum((uf - uf.mean(axis=0, keepdims=True)) ** 2)
        task_balance = np.sum((tf - tf.mean()) ** 2)
        moved = (x != x0).astype(np.float64)
        movement = np.sum(moved * self.tasks) / max(np.sum(self.tasks), 1.0)
        crit = np.sum(moved * self.crit) / max(np.sum(self.crit), 1.0)
        terms = (under_ideal, balance, task_balance, movement, crit)
        return float(sum(w * t for w, t in zip(WEIGHTS, terms)))

    def objective_lowp(self, x, x0, dtype) -> float:
        """The same objective computed on the device in ``dtype``: the
        control, the reference put in the program's place one precision
        below the configuration's float32."""
        import jax
        import jax.numpy as jnp

        T = self.capacity.shape[0]
        c = lambda v: jnp.asarray(np.asarray(v, np.float32)).astype(dtype)  # noqa: E731
        xj = jnp.asarray(x)
        util = jax.ops.segment_sum(c(self.demand), xj, num_segments=T)
        tasks = jax.ops.segment_sum(c(self.tasks), xj, num_segments=T)
        uf = util / c(self.capacity)
        tf = tasks / c(self.task_limit)
        under = (jnp.sum(jnp.maximum(uf - c(self.ideal), 0) ** 2)
                 + jnp.sum(jnp.maximum(tf - c(self.ideal_t), 0) ** 2))
        balance = jnp.sum((uf - uf.mean(axis=0, keepdims=True)) ** 2)
        task_balance = jnp.sum((tf - tf.mean()) ** 2)
        moved = c(np.asarray(x) != np.asarray(x0))
        movement = jnp.sum(moved * c(self.tasks)) / jnp.maximum(jnp.sum(c(self.tasks)), 1)
        crit = jnp.sum(moved * c(self.crit)) / jnp.maximum(jnp.sum(c(self.crit)), 1)
        terms = (under, balance, task_balance, movement, crit)
        total = sum(jnp.asarray(w, dtype) * t for w, t in zip(WEIGHTS, terms))
        return float(total.astype(jnp.float32))

    def over_latency_budget(self, x, x0) -> np.ndarray:
        """Moved apps whose data region lies farther than the budget from
        some region of their new tier (a tier's hosts may sit in any of its
        regions, so the worst one counts; a tier with no region is out of
        reach)."""
        moved = np.where(x != x0)[0]
        in_tier = self.tier_regions[x[moved]]                      # [M, G]
        lat = self.region_latency[self.app_region[moved]]          # [M, G]
        worst = np.where(in_tier, lat, -np.inf).max(axis=1, initial=-np.inf)
        worst[~in_tier.any(axis=1)] = np.inf
        return moved[worst > self.region_budget_ms]

    def unpacked_newcomers(self, x, x0) -> np.ndarray:
        """Apps moved into a tier that do not fit on its hosts.

        Each tier that received a moved app is packed first-fit decreasing:
        its apps (those that stayed and those moved in), largest resource
        demand first (ties by app id), each onto the first of the tier's
        ``hosts_per_tier`` hosts of ``host_capacity`` where it fits in every
        resource.  An app that stayed may fail (the incumbent placement is
        not the pass's to mend); an app moved in may not.  The tiers are
        packed side by side, one app of each per step."""
        newcomer = x != x0
        tiers = np.unique(x[newcomer])
        if tiers.size == 0:
            return np.zeros(0, np.int64)
        members = [np.where(x == t)[0] for t in tiers]
        members = [m[np.argsort(-self.demand[m].max(axis=1), kind="stable")] for m in members]
        hosts = self.hosts_per_tier[tiers]
        R = self.host_capacity.size
        free = np.full((tiers.size, int(hosts.max()), R), -np.inf)
        for i, h in enumerate(hosts):
            free[i, :h] = self.host_capacity
        tol = LIMIT_RTOL * self.host_capacity
        width = max(m.size for m in members)
        order = np.full((tiers.size, width), -1, np.int64)
        for i, m in enumerate(members):
            order[i, :m.size] = m
        rows = np.arange(tiers.size)
        failed = []
        for k in range(width):
            apps = order[:, k]
            live = apps >= 0
            d = np.where(live[:, None], self.demand[np.maximum(apps, 0)], 0.0)
            fits = np.all(free >= d[:, None, :] - tol, axis=2)     # [T', H]
            any_fit = fits.any(axis=1)
            first = np.argmax(fits, axis=1)
            placed = live & any_fit
            free[rows[placed], first[placed]] -= d[placed]
            lost = apps[live & ~any_fit]
            failed.extend(lost[newcomer[lost]].tolist())
        return np.asarray(sorted(failed), np.int64)

    def check(self, x, x0, reported: float, bus: bool = False
              ) -> tuple[list, float, float, float]:
        """Broken rules of placement ``x`` over incumbent ``x0``, the
        relative gap between the f64 objective and ``reported``, and the f64
        objectives of ``x`` and ``x0``.  ``bus``: the placement came from a
        pass of the whole cooperation bus, so the region and host levels'
        guarantees hold too."""
        x = np.asarray(x, np.int64)
        x0 = np.asarray(x0, np.int64)
        fails = []
        util, tasks = self.loads(x)
        util0, tasks0 = self.loads(x0)
        cap = self.capacity * (1 + LIMIT_RTOL)
        lim = self.task_limit * (1 + LIMIT_RTOL)
        # A tier already over a limit under the incumbent may stay over: the
        # solver is charged only for overload it introduces.
        cap_bad = (util > cap) & ~(util0 > cap)
        if cap_bad.any():
            fails.append(f"capacity exceeded on tiers {np.where(cap_bad.any(1))[0][:8]}")
        task_bad = (tasks > lim) & ~(tasks0 > lim)
        if task_bad.any():
            fails.append(f"task limit exceeded on tiers {np.where(task_bad)[0][:8]}")
        moved = x != x0
        ok_slo = self.slo_allowed[x, self.slo]
        if (moved & ~ok_slo).any():
            fails.append(f"{int((moved & ~ok_slo).sum())} moved apps on SLO-ineligible tiers")
        if (~ok_slo).any():
            fails.append(f"{int((~ok_slo).sum())} stranded apps")
        budget = int(np.ceil(self.move_frac * self.num_apps))
        if moved.sum() > budget:
            fails.append(f"{int(moved.sum())} moves over the budget of {budget}")
        if bus:
            far = self.over_latency_budget(x, x0)
            if far.size:
                fails.append(f"{far.size} moved apps beyond the {self.region_budget_ms} ms "
                             f"region latency budget (apps {far[:5].tolist()})")
            unpacked = self.unpacked_newcomers(x, x0)
            if unpacked.size:
                fails.append(f"{unpacked.size} moved apps fit on no host of their new tier "
                             f"(apps {unpacked[:5].tolist()})")
        obj, obj0 = self.objective(x, x0), self.objective(x0, x0)
        scale = max(abs(obj), OBJECTIVE_FLOOR)
        gap = abs(obj - reported) / scale
        return fails, gap, obj, obj0

    def d2b(self, util, tasks) -> float:
        """Difference to balance (Fig. 5): the worst distance of a tier's
        utilization, per resource and in tasks, from the fleet's mean."""
        total = self.demand.sum(axis=0) / self.capacity.sum(axis=0)
        total_t = self.tasks.sum() / self.task_limit.sum()
        worst = np.abs(util / self.capacity - total[None, :]).max()
        return float(max(worst, np.abs(tasks / self.task_limit - total_t).max()))


def d2b_mean(fleet, schedule, window_s: float, x_start, placements) -> float:
    """Time-weighted mean of the difference to balance over ``[0, window_s]``.

    The true demand changes at each event's due time; the placement changes
    when a step that applied one ends (``placements``: ascending
    ``(t_end_s, assignment)``).  Between changes the value is constant."""
    ref = Reference(fleet)
    x = np.asarray(x_start, np.int64)
    changes = [(float(t), 0, k) for k, t in enumerate(schedule.due) if t < window_s]
    changes += [(float(t), 1, k) for k, (t, _) in enumerate(placements) if t < window_s]
    changes.sort()
    total, t_prev, value = 0.0, 0.0, ref.d2b(*ref.loads(x))
    for t, kind, k in changes:
        total += value * (t - t_prev)
        t_prev = t
        if kind == 0:
            ref.replay(schedule.kinds[k], schedule.payloads[k])
        else:
            x = np.asarray(placements[k][1], np.int64)
        value = ref.d2b(*ref.loads(x))
    total += value * (window_s - t_prev)
    return total / window_s
