#!/usr/bin/env python3
"""Bring-up check: the streaming control loop end to end on one TPU.

    python chip_smoke.py              # one chip (what a chip check runs)
    python chip_smoke.py --chips 4    # the sharded fleet pass on a 4-chip mesh

One chip.  A regional fleet of 100,000 streaming apps over 64 tiers and 8
regions (``repro.shard.synthetic_fleet``, seed 0) is wrapped in a
``ServiceLoop`` with the default ``ControllerConfig()`` (no fault machinery,
so a solver exception propagates) and 8 delta shards.  Windows of
``TelemetryDelta`` events — demand drift on a Zipf-skewed, moving hot set of
apps, made from the seed — are submitted and ``step()`` is called after each
window until the loop has applied at least one *full* pass (global
cooperate over the region and host levels, host packing by
``pack_ffd_tiers`` on the device) and one *delta* pass (the batched shard
solve over the dirty shards).  The same windows then run a second time on a
fresh loop over the same fleet: the first run is set-up (it compiles every
shape), the second is the measured run, and the retrace counters across it
should not move.

Every applied assignment is checked by ``Reference``, a plain numpy f64
model of the fleet written here (it does not call the package's
validators): per-tier capacity and task limits, SLO eligibility, the
movement budget, zero stranded apps, an f64 objective that agrees with the
device's reported objective within ``OBJECTIVE_RTOL``, and no worse than
the incumbent's.

Four chips (``--chips 4``) runs only one full ``solve_fleet`` pass at S=16
under ``jax.set_mesh`` over four devices, and the same pass on one device,
and checks that the shard batch and its result span the four devices, that
both passes merge to the same assignment, and that the f64 checks hold.

Everything runs in this one process.  The last line of standard output is
one JSON object, ``{"ok": true, "device": {"platform", "kind", "count"}}``;
it is printed only when every phase passed.  With no TPU the script says so
and exits 2.  ``--rehearse-on-cpu`` (with ``--apps`` / ``--tiers``) exists
for the tests that rehearse this script on the CPU at a small size; a chip
run never passes it.  This is a single bring-up run, not a benchmark.
"""

from __future__ import annotations

import argparse
import collections
import dataclasses
import json
import os
import pathlib
import sys
import time

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parent
NUM_APPS = 100_000
NUM_TIERS = 64
NUM_REGIONS = 8
DELTA_SHARDS = 8
MESH_SHARDS = 16
SEED = 0
ZIPF_S = 1.1
EVENT_BATCH = 512       # apps per TelemetryDelta record
MAX_WINDOWS = 12
# The device reports its objective in f32 while the reference sums in f64.
# Over 1e5 apps and 64 tiers the f32 loads carry about 1e-6 relative error,
# and the balance terms square differences of them; 1e-4 relative (with a
# 1e-3 absolute floor for objectives near zero) leaves a wide margin over
# that and still fails on any real disagreement.
OBJECTIVE_RTOL = 1e-4
OBJECTIVE_ATOL = 1e-3
# Hard-limit slack, relative to the limit: f32 per-tier sums of ~1e3 apps.
LIMIT_RTOL = 1e-5


class CheckFailed(RuntimeError):
    pass


# ---------------------------------------------------------------------------
# the f64 reference
# ---------------------------------------------------------------------------

class Reference:
    """The fleet as plain f64 arrays, with its own constraint checks and
    objective.  Telemetry is applied to it exactly as it is submitted."""

    def __init__(self, problem):
        a = lambda v: np.asarray(v, np.float64)  # noqa: E731
        self.demand = a(problem.demand)
        self.tasks = a(problem.tasks)
        self.crit = a(problem.criticality)
        self.slo = np.asarray(problem.slo, np.int64)
        self.valid = np.asarray(problem.valid, bool)
        self.capacity = a(problem.capacity)
        self.task_limit = a(problem.task_limit)
        self.ideal = a(problem.ideal_frac)
        self.ideal_t = a(problem.ideal_task_frac)
        self.slo_allowed = np.asarray(problem.slo_allowed, bool)
        self.move_frac = float(problem.move_frac)
        w = problem.weights
        self.weights = [float(w.under_ideal), float(w.resource_balance),
                        float(w.task_balance), float(w.movement_cost),
                        float(w.criticality)]

    def apply(self, event) -> None:
        ids = np.asarray(event.app_ids, np.int64)
        self.demand[ids] = np.asarray(event.demand, np.float64)
        self.tasks[ids] = np.asarray(event.tasks, np.float64)

    def loads(self, x):
        T = self.capacity.shape[0]
        live = self.valid
        util = np.zeros_like(self.capacity)
        for r in range(util.shape[1]):
            util[:, r] = np.bincount(x[live], self.demand[live, r], minlength=T)
        tasks = np.bincount(x[live], self.tasks[live], minlength=T)
        return util, tasks

    def objective(self, x, x0) -> float:
        util, tasks = self.loads(x)
        uf = util / self.capacity
        tf = tasks / self.task_limit
        over = np.maximum(uf - self.ideal, 0.0)
        over_t = np.maximum(tf - self.ideal_t, 0.0)
        under_ideal = np.sum(over ** 2) + np.sum(over_t ** 2)
        balance = np.sum((uf - uf.mean(axis=0, keepdims=True)) ** 2)
        task_balance = np.sum((tf - tf.mean()) ** 2)
        moved = (x != x0).astype(np.float64)
        movement = np.sum(moved * self.tasks) / max(np.sum(self.tasks), 1.0)
        crit = np.sum(moved * self.crit) / max(np.sum(self.crit), 1.0)
        terms = [under_ideal, balance, task_balance, movement, crit]
        return float(sum(w * t for w, t in zip(self.weights, terms)))

    def check(self, x, x0, reported: float) -> dict:
        """Raise ``CheckFailed`` on any broken rule; returns the numbers."""
        x = np.asarray(x, np.int64)
        x0 = np.asarray(x0, np.int64)
        fails = []
        util, tasks = self.loads(x)
        util0, tasks0 = self.loads(x0)
        cap = self.capacity * (1 + LIMIT_RTOL)
        lim = self.task_limit * (1 + LIMIT_RTOL)
        # A tier already over its limit under the incumbent may stay over
        # (the solver is charged only for overload it introduces).
        cap_bad = (util > cap) & ~(util0 > cap)
        task_bad = (tasks > lim) & ~(tasks0 > lim)
        if cap_bad.any():
            fails.append(f"capacity exceeded on tiers {np.where(cap_bad.any(1))[0][:8]}")
        if task_bad.any():
            fails.append(f"task limit exceeded on tiers {np.where(task_bad)[0][:8]}")
        moved = (x != x0) & self.valid
        ok_slo = self.slo_allowed[x, self.slo]
        if (moved & ~ok_slo).any():
            fails.append(f"{int((moved & ~ok_slo).sum())} moved apps on SLO-ineligible tiers")
        stranded = int((self.valid & ~ok_slo).sum())
        if stranded:
            fails.append(f"{stranded} stranded apps")
        budget = int(np.ceil(self.move_frac * self.valid.sum()))
        if moved.sum() > budget:
            fails.append(f"{int(moved.sum())} moves over the budget of {budget}")
        obj = self.objective(x, x0)
        obj0 = self.objective(x0, x0)
        tol = max(OBJECTIVE_ATOL, OBJECTIVE_RTOL * abs(obj))
        if not abs(obj - reported) <= tol:
            fails.append(f"f64 objective {obj!r} vs device {reported!r} (tol {tol:.3g})")
        if obj > obj0 + tol:
            fails.append(f"objective {obj!r} worse than the incumbent's {obj0!r}")
        if fails:
            raise CheckFailed("; ".join(fails))
        return {"moved": int(moved.sum()), "budget": budget,
                "objective_f64": obj, "objective_device": reported,
                "incumbent_f64": obj0}


# ---------------------------------------------------------------------------
# telemetry windows
# ---------------------------------------------------------------------------

class Windows:
    """Telemetry windows: Zipf-skewed demand drift over a moving hot set.

    Window 0 is a fleet-wide surge: apps are drawn with probability
    proportional to 1/rank^ZIPF_S of their tier in a random tier ranking,
    so a few tiers take most of it (a FULL pass).  Windows inside the
    controller's cooldown are calm jitter below the dirty threshold.  Later
    windows drift a regional hot arc: apps on the tiers nearest a centre
    that moves every window, Zipf-weighted by ring distance from it, so the
    drift lands on a few shards (a delta pass).  Their demand is scaled so
    that the centre tier's load rises by ``self.rise`` of its capacity —
    between the drift detector's delta and full gates — and the rise adapts
    to what the loop decided: a FULL asks for less, a quiet tick for more.
    """

    def __init__(self, E, fleet, seed: int, cooldown: int):
        self.E = E
        self.rng = np.random.default_rng([seed, 11])
        self.N, self.T = fleet.problem.num_apps, fleet.problem.num_tiers
        self.cooldown = cooldown
        self.rise = 0.10

    def _draw(self, weight_of_tier, x, frac: float):
        p = weight_of_tier[x]
        k = min(max(1, int(frac * self.N)), int(np.count_nonzero(p)))
        return np.sort(self.rng.choice(self.N, size=k, replace=False, p=p / p.sum()))

    def _events(self, ids, demand, tasks, now: int):
        out = []
        for s in range(0, ids.size, EVENT_BATCH):
            sl = slice(s, s + EVENT_BATCH)
            out.append(self.E.TelemetryDelta(
                app_ids=tuple(int(i) for i in ids[sl]),
                demand=np.asarray(demand[sl], np.float32),
                tasks=np.asarray(tasks[sl], np.float32), collected_at=now))
        return out

    def next(self, ref: Reference, x, now: int, last_full: int, last_action: str):
        """The events of window ``now``; ``x`` is the incumbent mapping."""
        T = self.T
        if now == 0:
            rank = np.empty(T)
            rank[self.rng.permutation(T)] = np.arange(T)
            ids = self._draw(1.0 / (1.0 + rank) ** ZIPF_S, x, 0.06)
            factor = 1.8
        elif now - last_full < self.cooldown:
            ids = np.sort(self.rng.choice(self.N, size=max(1, self.N // 200),
                                          replace=False))
            factor = 1.0
        else:
            if last_action == "full":
                self.rise *= 0.7
            elif last_action == "noop":
                self.rise = min(0.3, self.rise * 1.3)
            centre = int(self.rng.integers(T))
            dist = np.abs(np.arange(T) - centre)
            dist = np.minimum(dist, T - dist)
            weight = np.where(dist < max(1, T // 16),
                              1.0 / (1.0 + dist) ** ZIPF_S, 0.0)
            ids = self._draw(weight, x, 0.01)
            util, _ = ref.loads(x)
            r = int(np.argmax(util[centre] / ref.capacity[centre]))
            on_centre = ids[x[ids] == centre]
            share = ref.demand[on_centre, r].sum() / ref.capacity[centre, r]
            factor = float(np.clip(1.0 + self.rise / max(share, 1e-9), 1.05, 3.0))
        jitter = self.rng.lognormal(0.0, 0.02, (ids.size, 1))
        demand = ref.demand[ids] * factor * jitter
        return self._events(ids, demand, ref.tasks[ids], now)


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def _say(*parts) -> None:
    print(*parts, flush=True)


def run_loop(ctx, fleet, windows=None):
    """One ServiceLoop over ``fleet``: generate windows (``windows=None``)
    or replay recorded ones.  Returns (windows, step records, loop stats)."""
    jax, repro = ctx["jax"], ctx["repro"]
    from repro.service import events as E

    loop = repro.ServiceLoop(fleet, config=repro.ServiceConfig(num_shards=DELTA_SHARDS))
    ctl = loop.controller
    if ctl.config.fault is not None:
        raise CheckFailed("the smoke runs the default ControllerConfig (fault=None)")
    ref = Reference(fleet.problem)
    x_inc = np.asarray(fleet.problem.assignment0).copy()
    gen = Windows(E, fleet, SEED, ctl.config.cooldown_rounds) if windows is None else None
    recorded, steps = [], []
    last_full, last_action = -(10 ** 9), "noop"
    applied = {"full": 0, "delta": 0}
    count = MAX_WINDOWS if windows is None else len(windows)
    for now in range(count):
        events = (gen.next(ref, x_inc, now, last_full, last_action)
                  if gen is not None else windows[now])
        recorded.append(events)
        for ev in events:
            loop.submit(ev)
            ref.apply(ev)
        t0 = time.perf_counter()
        out = loop.step(now=now)
        jax.block_until_ready(ctl.cluster.problem.assignment0)
        wall = time.perf_counter() - t0
        rec = {"now": now, "action": out.action, "events": out.events_drained,
               "ran": out.result is not None and out.result.triggered,
               "applied": out.applied, "wall_s": wall, "reason": out.reason}
        if out.result is not None and out.result.triggered:
            last_action = out.action
        else:
            last_action = "noop" if out.action == "noop" else last_action
        if out.applied:
            dec = out.result.decision
            x = np.asarray(dec.assignment)
            if not np.array_equal(np.asarray(ctl.cluster.problem.assignment0), x):
                raise CheckFailed(f"t={now}: the applied mapping is not the incumbent")
            view = np.asarray(ctl.cluster.problem.demand, np.float32)
            if not np.array_equal(view, ref.demand.astype(np.float32)):
                raise CheckFailed(f"t={now}: the solved demand differs from what was submitted")
            try:
                rec.update(ref.check(x, x_inc, float(dec.solve.objective)))
            except CheckFailed as e:
                raise CheckFailed(f"t={now} {out.action} pass: {e}") from None
            if out.action == "full":
                coop = dec.cooperation
                if coop is None:
                    raise CheckFailed(f"t={now}: full pass without the cooperation bus")
                levels = coop.timings.levels
                if not {"region", "host"} <= set(levels):
                    raise CheckFailed(f"t={now}: full pass levels {sorted(levels)}")
                if levels["host"].get("pack_dispatches", 0) < 1:
                    raise CheckFailed(f"t={now}: full pass packed no host bins")
                if coop.timings.breakers:
                    raise CheckFailed(f"t={now}: breakers engaged: {coop.timings.breakers}")
                rec.update(rounds=int(coop.timings.rounds),
                           pack_dispatches=int(levels["host"]["pack_dispatches"]))
                last_full = now
            else:
                sharded = dec.solve.extra["sharded"]
                if not out.result.delta or sharded["solved_shards"] < 1:
                    raise CheckFailed(f"t={now}: delta pass solved no shards")
                rec.update(solved_shards=int(sharded["solved_shards"]),
                           num_shards=int(sharded["num_shards"]),
                           reverted=bool(sharded["reverted"]))
            applied[out.action] += 1
            x_inc = x
        steps.append(rec)
        if windows is None and applied["full"] and applied["delta"]:
            break
    if loop.dropped_events != 0:
        raise CheckFailed(f"dropped_events = {loop.dropped_events}")
    # The generating run must reach both passes; a replay checks whatever
    # it applied and reports a different decision sequence.
    if windows is None and not (applied["full"] and applied["delta"]):
        raise CheckFailed(f"applied passes {applied}: need a full and a delta pass")
    return recorded, steps, loop.stats()


def phase_one_chip(ctx, args) -> None:
    jax, repro = ctx["jax"], ctx["repro"]
    from repro.core.solver_local import local_search_trace_count
    from repro.kernels.pack import pack_trace_count
    from repro.shard import synthetic_fleet
    from repro.shard.solve import shard_batch_trace_count

    def counters():
        return {"local_search": local_search_trace_count(),
                "shard_batch": shard_batch_trace_count(),
                "pack": pack_trace_count()}

    t0 = time.perf_counter()
    fleet = synthetic_fleet(args.apps, num_tiers=args.tiers,
                            num_regions=NUM_REGIONS, seed=SEED)
    _say(f"fleet: {fleet.problem.num_apps} apps x {fleet.problem.num_tiers} tiers x "
         f"{NUM_REGIONS} regions, {int(fleet.hosts_per_tier.sum())} hosts, "
         f"built from seed {SEED} in {time.perf_counter() - t0:.2f} s")

    compile_s = ctx["compile_s"]
    c0 = sum(compile_s.values())
    t0 = time.perf_counter()
    windows, warm_steps, _ = run_loop(ctx, fleet)
    warm_s = time.perf_counter() - t0
    warm_compile = sum(compile_s.values()) - c0
    _say(f"set-up: first run of {len(windows)} windows took {warm_s:.2f} s, "
         f"of which {warm_compile:.2f} s tracing and compiling")

    fleet = synthetic_fleet(args.apps, num_tiers=args.tiers,
                            num_regions=NUM_REGIONS, seed=SEED)
    before, c1 = counters(), sum(compile_s.values())
    _, steps, stats = run_loop(ctx, fleet, windows)
    after = counters()
    retraces = {k: after[k] - before[k] for k in after}
    _say("measured run (the same windows on a fresh loop):")
    for rec in steps:
        extra = ""
        if rec["applied"]:
            extra = (f" moved={rec['moved']}/{rec['budget']} "
                     f"obj f64={rec['objective_f64']!r} device={rec['objective_device']!r} "
                     f"incumbent={rec['incumbent_f64']!r}")
            if "rounds" in rec:
                extra += f" rounds={rec['rounds']} pack_dispatches={rec['pack_dispatches']}"
            if "solved_shards" in rec:
                extra += (f" shards={rec['solved_shards']}/{rec['num_shards']}"
                          f" reverted={rec['reverted']}")
        _say(f"  t={rec['now']:2d} {rec['action']:5s} events={rec['events']:4d} "
             f"ran={rec['ran']!s:5s} applied={rec['applied']!s:5s} "
             f"step={rec['wall_s'] * 1e3:.1f} ms{extra}")
        _say(f"        reason: {rec['reason']}")
    if [r["action"] for r in steps] != [r["action"] for r in warm_steps]:
        _say("note: the measured run decided differently from the set-up run")
    _say(f"passes: full={stats['full_solves']} delta={stats['delta_solves']} "
         f"applied={stats['solves_applied']} noop_ticks={stats['noop_ticks']} "
         f"dropped_events={stats['dropped_events']} events={stats['events_applied']}")
    _say(f"retraces across the measured run (0 expected): {retraces}"
         + ("" if not any(retraces.values()) else "  <- NOT ZERO: shapes compiled inside the run"))
    _say(f"tracing/compiling inside the measured run: {sum(compile_s.values()) - c1:.2f} s")
    _say(f"peak device memory: {ctx['peak']()}")


def phase_four_chips(ctx, args) -> None:
    jax = ctx["jax"]
    from repro.distributed.sharding import place_shard_batch
    from repro.shard import FleetConfig, solve_fleet, synthetic_fleet

    fleet = synthetic_fleet(args.apps, num_tiers=args.tiers,
                            num_regions=NUM_REGIONS, seed=SEED)
    p = fleet.problem
    _say(f"fleet: {p.num_apps} apps x {p.num_tiers} tiers, S={MESH_SHARDS} shards")
    # The drifted fleet: the same surge the one-chip phase opens with, so
    # the pass has real work to do.
    import jax.numpy as jnp
    from repro.service import events as E

    ref = Reference(p)
    surge = Windows(E, fleet, SEED, cooldown=0)
    for ev in surge.next(ref, np.asarray(p.assignment0), 0, -1, "noop"):
        ref.apply(ev)
    fleet = dataclasses.replace(fleet, problem=dataclasses.replace(
        p, demand=jnp.asarray(ref.demand, jnp.float32)))
    x0 = np.asarray(p.assignment0)
    cfg = FleetConfig(num_shards=MESH_SHARDS)

    t0 = time.perf_counter()
    one = solve_fleet(fleet, cfg)
    t_one = time.perf_counter() - t0
    devices = set(jax.devices()[:1])
    if one.solve.x.sharding.device_set != devices:
        raise CheckFailed(f"one-device pass ran on {one.solve.x.sharding.device_set}")
    mesh = jax.make_mesh((4,), ("data",))
    with jax.set_mesh(mesh):
        t0 = time.perf_counter()
        four = solve_fleet(fleet, cfg)
        t_four = time.perf_counter() - t0
        placed, n = place_shard_batch(four.sharded.problems)
    want = set(mesh.devices.flat)
    spans = {len(leaf.sharding.device_set) for leaf in jax.tree.leaves(placed)}
    if n != MESH_SHARDS or spans != {4} or {d for leaf in jax.tree.leaves(placed)
                                            for d in leaf.sharding.device_set} != want:
        raise CheckFailed(f"shard batch spans {spans} devices, not the 4 of the mesh")
    if four.solve.x.sharding.device_set != want:
        raise CheckFailed(f"result spans {four.solve.x.sharding.device_set}")
    _say(f"shard batch: {n} shards over {len(want)} devices "
         f"({sorted(str(d) for d in want)}); result on {len(four.solve.x.sharding.device_set)}")
    if not np.array_equal(one.assignment, four.assignment):
        diff = int(np.sum(one.assignment != four.assignment))
        raise CheckFailed(f"4-device and 1-device passes differ on {diff} apps")
    for name, fd, secs in (("one device", one, t_one), ("four devices", four, t_four)):
        rec = ref.check(fd.assignment, x0, float(fd.objective))
        _say(f"{name}: pass {secs:.2f} s (compilation included), moved "
             f"{rec['moved']}/{rec['budget']}, obj f64={rec['objective_f64']!r} "
             f"device={rec['objective_device']!r} incumbent={rec['incumbent_f64']!r}, "
             f"reverted={fd.timings['reverted']}, stranded={fd.stranded}")
    _say("merged assignments equal: True")
    _say(f"peak device memory: {ctx['peak']()}")


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the ServiceLoop phase; 4: only the sharded fleet "
                         "pass on a 4-device mesh against one device")
    ap.add_argument("--rehearse-on-cpu", action="store_true",
                    help="test-only: accept the CPU backend (virtual devices "
                         "for --chips 4); a chip run never passes this")
    ap.add_argument("--apps", type=int, default=NUM_APPS,
                    help="rehearsal size only")
    ap.add_argument("--tiers", type=int, default=NUM_TIERS,
                    help="rehearsal size only")
    args = ap.parse_args(argv)
    if not args.rehearse_on_cpu and (args.apps, args.tiers) != (NUM_APPS, NUM_TIERS):
        ap.error("--apps/--tiers are for --rehearse-on-cpu only")
    if args.rehearse_on_cpu and args.chips == 4:
        os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")

    import jax

    devices = jax.devices()
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices)}
    _say("chip_smoke: a single bring-up run, not a benchmark")
    _say(f"device: platform={device['platform']} kind={device['kind']} count={device['count']}")
    if dev.platform != "tpu" and not args.rehearse_on_cpu:
        print(f"chip_smoke: no TPU found (JAX reports platform {dev.platform!r}); "
              "this check runs only on a TPU", file=sys.stderr, flush=True)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"JAX reports {len(devices)}", file=sys.stderr, flush=True)
        return 2

    sys.path.insert(0, str(ROOT / "src"))
    try:
        import repro
        from repro.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: cannot import the repro package from {ROOT / 'src'}: {e}",
              file=sys.stderr, flush=True)
        return 3
    _say(f"compile cache: {enable_compile_cache()}")

    compile_s = collections.defaultdict(float)

    def on_duration(name, secs, **_):
        if name.startswith("/jax/core/compile/"):
            compile_s[name] += secs

    jax.monitoring.register_event_duration_secs_listener(on_duration)

    def peak():
        stats = dev.memory_stats() or {}
        value = stats.get("peak_bytes_in_use")
        return "not reported by this backend" if value is None else f"{value} bytes"

    ctx = {"jax": jax, "repro": repro, "compile_s": compile_s, "peak": peak}
    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            phase_four_chips(ctx, args)
        else:
            phase_one_chip(ctx, args)
    except CheckFailed as e:
        print(f"chip_smoke: CHECK FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    _say(f"total wall time {time.perf_counter() - t0:.2f} s, of which "
         f"{sum(compile_s.values()):.2f} s tracing and compiling")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
