#!/usr/bin/env python3
"""Chip benchmark of the streaming control loop: one cell, one run.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell (an entry of ``workloads`` in ``BENCHMARK.json``) names a fleet
configuration (``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<traffic>.json``); each names the modules that build it
(``bench/plugins.py``).  The run, in order:

1. refuses a machine whose JAX finds no TPU, or fewer chips than the cell
   asks for (exit 2, no result);
2. turns on JAX's persistent compilation cache (``repro.compile_cache``);
3. builds the fleet from the seed (``bench/fleets.py``);
4. builds every event due in the window, from the seed alone
   (``bench/schedule.py``);
5. warms every shape the window uses, as the traffic file's ``warm_up``
   says: a full cooperation pass that balances the fleet (the window starts
   from its placement), one more on the demand the schedule reports by each
   instant under ``full_at_s``, and, where the traffic makes delta passes
   (``delta``), one over each count of dirty shards the batched shard solve
   can see;
6. measures for ``--seconds``: when an event falls due the harness submits
   every event that is due and calls ``ServiceLoop.step()``, as the loop's
   own ``serve(batch_ticks=True)`` does; when none is due it sleeps until
   the next one is.  An event's decision latency runs from its due time to
   the end of the step that drained it, so a stall shows in every event
   queued behind it (open loop).  Events due in the window whose step ends
   after it closes are still timed.

After the window the f64 reference (``bench/reference.py``) replays the
event log against every applied placement; that decides ``correct``.  The
last line of standard output is one JSON object: ``correct``, ``attempted``
(events due in the window), ``failed`` (events dropped or whose step
raised), ``metrics`` (the cell's end-to-end metrics, or with ``--trace 1``
its per-layer metrics, each read by ``bench/metrics/<name>.py``), ``device``
and, last, ``checks``: each number compared beside its limit.  The same
numbers end standard error.

``--rehearse-on-cpu`` (with ``--apps``/``--tiers``) is for the tests under
``bench/tests`` only: it accepts the CPU backend at a small size and prints
no device metric.  ``run(..., control=True)`` (``bench/probe.py --control``)
also reads the check's control: the reference's objective computed in
bfloat16 in the device's place; no benchmark run asks for it.
"""

from __future__ import annotations

import time

T_PROCESS = time.time()

import argparse  # noqa: E402
import collections  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

import numpy as np  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

import fleets  # noqa: E402
import plugins  # noqa: E402
import reference  # noqa: E402
import schedule as schedule_mod  # noqa: E402
import trace_reduce  # noqa: E402

# Limits of the numbers compared (see PERF.md for the readings behind each).
OBJ_GAP_LIMIT = 1e-3
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class NoChip(RuntimeError):
    pass


def load_cell(name: str) -> tuple[dict, dict, dict, dict]:
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {c["name"]: c for c in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; have {sorted(cells)}")
    cell = cells[name]
    config = json.loads((BENCH / "configs" / f"{cell['config']}.json").read_text())
    traffic = json.loads((BENCH / "traffic" / f"{cell['traffic']}.json").read_text())
    return spec, cell, config, traffic


def metrics_for(spec: dict, cell: str, trace: bool) -> list[dict]:
    group = spec["per_layer"] if trace else spec["end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


def _say(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


@dataclasses.dataclass
class RunData:
    """What one run recorded; the metric readers take their numbers from it."""

    cell: str
    seconds: float
    setup_s: float = 0.0
    latencies_ms: np.ndarray = None     # per event due in the window; NaN = failed
    steps: list = dataclasses.field(default_factory=list)
    d2b_mean: float | None = None
    compiles_in_window: int = 0
    trace: dict | None = None


# ---------------------------------------------------------------------------
# set-up
# ---------------------------------------------------------------------------

def _shard_shape(cluster, num_shards: int):
    from repro.core.problem import bucket_size
    from repro.shard.partition import plan_shards

    plan = plan_shards(cluster, num_shards)
    widest = int(np.bincount(plan.app_shard, minlength=plan.num_shards).max())
    return plan.num_shards, bucket_size(max(widest, 1)), max(len(t) for t in plan.shard_tiers)


def _reported_by(fleet, sched, start, upto_s: float):
    """``start`` (a cluster) with the demand the schedule's events due
    before ``upto_s`` report."""
    import jax.numpy as jnp

    ref = reference.Reference(fleet)
    for k in range(int(np.searchsorted(sched.due, upto_s))):
        ref.replay(sched.kinds[k], sched.payloads[k])
    p = start.problem
    return dataclasses.replace(start, problem=dataclasses.replace(
        p, demand=jnp.asarray(ref.demand, jnp.float32),
        tasks=jnp.asarray(ref.tasks, jnp.float32)))


def warm_up(fleet, sched, num_shards: int, spec: dict):
    """Compile what the window runs, on a controller of its own that is told
    to solve every time, and return the fleet the window starts from.

    A full cooperation pass balances the fleet as built: the window starts
    from that placement, the steady state of a fleet this controller runs.
    More full passes, from that placement, on the demand the schedule's
    events report by each of ``spec["full_at_s"]``'s instants, compile the
    feedback buckets that traffic asks for.  Where the traffic makes delta
    passes (``spec["delta"]``), a delta pass over 1..S dirty shards (the
    batched solver compiles once per count) for each shard shape those
    placements give."""
    from repro.core.controller import BalanceController, ControllerConfig, TickInput

    cluster = fleets.to_cluster(fleet)
    ctl = BalanceController(cluster, ControllerConfig(trigger_d2b=-1.0, cooldown_rounds=0))
    ctl.step(TickInput(cluster=cluster, now=0))
    start = ctl.cluster
    solved = [start]
    for now, t in enumerate(spec.get("full_at_s", ()), start=1):
        state = _reported_by(fleet, sched, start, t)
        res = ctl.step(TickInput(cluster=state, now=now))
        solved.append(ctl.cluster if res.applied else state)
    if not spec.get("delta", False):
        return start, {"full_passes": len(solved)}
    shapes, now = set(), len(solved)
    for c in solved:
        shape = _shard_shape(c, num_shards)
        if shape in shapes:
            continue
        shapes.add(shape)
        for k in range(1, shape[0] + 1):
            ctl.step(TickInput(cluster=c, now=now, dirty_shards=tuple(range(k)),
                               num_shards=num_shards))
            now += 1
    return start, {"full_passes": len(solved), "shard_shapes": sorted(shapes)}


# ---------------------------------------------------------------------------
# the measured window
# ---------------------------------------------------------------------------

def _record_step(out, loop, t0, t1, i, j, x_inc):
    """One step's record; for an applied pass also what the check needs."""
    import jax

    rec = {"t0": t0, "t1": t1, "action": out.action, "first": i, "last": j,
           "ran": out.result is not None and out.result.decision is not None,
           "applied": out.applied}
    if rec["ran"]:
        dec = out.result.decision
        sharded = dec.solve.extra.get("sharded")
        if sharded is not None:
            rec.update(solve_s=float(sharded["solve_s"]),
                       solved_shards=int(sharded["solved_shards"]),
                       num_shards=int(sharded["num_shards"]),
                       reverted=bool(sharded["reverted"]))
        if dec.cooperation is not None:
            tm = dec.cooperation.timings
            rec.update(coop_solve_s=float(tm.solve_s), rounds=int(tm.rounds),
                       levels=sorted(tm.levels),
                       pack_dispatches=int(tm.levels.get("host", {}).get("pack_dispatches", 0)),
                       breakers=bool(tm.breakers))
    if rec["applied"]:
        dec = out.result.decision
        problem = loop.controller.cluster.problem
        rec.update(x=np.array(jax.device_get(problem.assignment0)),
                   x_inc=x_inc,
                   demand=np.array(jax.device_get(problem.demand)),
                   reported=float(dec.solve.objective),
                   delta=bool(out.result.delta))
    return rec


def measure(loop, sched, seconds: float, events) -> tuple[list, float, int]:
    """Drive the loop open-loop over the schedule; returns the step records,
    the window's start on the host clock and the index of the first event
    that was never drained (len(sched) when all were)."""
    import jax

    due = sched.due
    n = len(sched)
    steps = []
    x_inc = np.asarray(loop.controller.cluster.problem.assignment0).copy()
    i = 0
    t_start = time.perf_counter()

    def step(i, j):
        nonlocal x_inc
        with jax.profiler.TraceAnnotation("bench.submit"):
            for k in range(i, j):
                loop.submit(events[k])
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.step"):
            out = loop.step()
            jax.block_until_ready(loop.controller.cluster.problem.assignment0)
        t1 = time.perf_counter()
        rec = _record_step(out, loop, t0 - t_start, t1 - t_start, i, j, x_inc)
        if rec["applied"]:
            x_inc = rec["x"]
        steps.append(rec)

    with jax.profiler.TraceAnnotation("bench.window"):
        while True:
            now = time.perf_counter() - t_start
            if now >= seconds:
                break
            if i < n and due[i] <= now:
                j = int(np.searchsorted(due, now, side="right"))
                try:
                    step(i, j)
                except Exception as e:  # the run reports the failure, then stops
                    _say(f"bench: step raised {type(e).__name__}: {e}")
                    return steps, t_start, i
                i = j
            else:
                wake = min(due[i] if i < n else seconds, seconds)
                with jax.profiler.TraceAnnotation("bench.sleep"):
                    time.sleep(max(0.0, wake - now))
    if i < n:  # events due before the close, not yet drained
        try:
            step(i, n)
        except Exception as e:
            _say(f"bench: step raised {type(e).__name__}: {e}")
            return steps, t_start, i
        i = n
    return steps, t_start, i


# ---------------------------------------------------------------------------
# the check
# ---------------------------------------------------------------------------

def check(fleet, config, sched, steps, control: bool) -> dict:
    """Replay the events against every applied placement with the f64
    reference.  Returns the numbers compared."""
    import jax.numpy as jnp

    ref = reference.Reference(fleet, region_budget_ms=float(config["region_latency_budget_ms"]))
    applied = 0
    gaps, control_gaps, fails, mismatch = [], [], [], 0
    for rec in steps:
        for k in range(rec["first"], rec["last"]):
            ref.replay(sched.kinds[k], sched.payloads[k])
        if not rec["applied"]:
            continue
        applied += 1
        where = f"step at {rec['t0']:.3f} s ({rec['action']})"
        bus = rec["action"] == "full" and not rec["delta"]
        mismatch += int(np.sum(np.any(rec["demand"] != ref.demand.astype(np.float32), axis=1)))
        broken, gap, obj, obj0 = ref.check(rec["x"], rec["x_inc"], rec["reported"], bus=bus)
        gaps.append(gap)
        if control:
            lowp = ref.objective_lowp(rec["x"], rec["x_inc"], jnp.bfloat16)
            control_gaps.append(abs(obj - lowp) / max(abs(obj), reference.OBJECTIVE_FLOOR))
        if obj > obj0 + OBJ_GAP_LIMIT * max(abs(obj0), reference.OBJECTIVE_FLOOR):
            broken.append(f"objective {obj!r} worse than the incumbent's {obj0!r}")
        if bus:
            if not {"region", "host"} <= set(rec.get("levels", ())):
                broken.append(f"full pass levels {rec.get('levels')}")
            if rec.get("pack_dispatches", 0) < 1:
                broken.append("full pass packed no host bins")
        if rec["delta"] and rec.get("solved_shards", 0) < 1:
            broken.append("delta pass solved no shards")
        fails += [f"{where}: {b}" for b in broken]
    out = {"applied_passes": applied, "obj_gap": max(gaps, default=0.0),
           "rule_breaks": len(fails), "demand_mismatch": mismatch, "fail_text": fails}
    if control:
        # The control: the reference in bfloat16 in the device's place.
        out["obj_gap_program"] = out["obj_gap"]
        out["obj_gap"] = max(control_gaps, default=0.0)
    return out


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def run(cell_name: str, seed: int, seconds: float, trace: bool, *,
        rehearse: bool = False, control: bool = False, sizes: dict | None = None,
        traffic: dict | None = None, t_process: float | None = None) -> dict:
    """One run of a cell; returns the result line as a dict.  ``sizes`` and
    ``traffic`` override the configuration's sizes and the traffic file
    (rehearsals and ``bench/probe.py`` only)."""
    import jax

    spec, cell, config, cell_traffic = load_cell(cell_name)
    traffic = cell_traffic if traffic is None else traffic
    t_process = T_PROCESS if t_process is None else t_process
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu" and not rehearse:
        raise NoChip(f"JAX finds no TPU (platform {dev.platform!r}); this benchmark runs on a TPU")
    if len(devices) < int(cell["chips"]):
        raise NoChip(f"the cell asks for {cell['chips']} chips, JAX finds {len(devices)}")
    sys.path.insert(0, str(ROOT / "src"))
    from repro.compile_cache import enable_compile_cache
    from repro.service import ServiceConfig, ServiceLoop

    _say(f"bench: compile cache {enable_compile_cache()}")
    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, secs, fun_name="?", **_: compiles.append((time.perf_counter(), fun_name))
        if name == COMPILE_EVENT else None)

    args = dict(config["args"], **(sizes or {}))
    fleet = fleets.build(dict(config, args=args), seed)
    sched = schedule_mod.build(fleet, traffic, seed, seconds)
    events = [plugins.load("events", kind).program_event(payload, due)
              for kind, payload, due in zip(sched.kinds, sched.payloads, sched.due)]
    num_shards = int(config["service"]["num_shards"])
    start, warm = warm_up(fleet, sched, num_shards, traffic["warm_up"])
    loop = ServiceLoop(start, config=ServiceConfig(num_shards=num_shards))
    prime = loop.step()     # the drift detector's baseline
    x_start = np.asarray(loop.controller.cluster.problem.assignment0).copy()
    _say(f"bench: {cell_name} seed {seed}: {fleet.num_apps} apps x {fleet.num_tiers} tiers, "
         f"{len(sched)} events due in {seconds} s; warm-up {warm}; "
         f"priming step {prime.action} (applied {prime.applied})")

    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    setup_s = time.time() - t_process
    steps, t_start, undrained = measure(loop, sched, seconds, events)
    t_close = t_start + seconds
    if trace:
        t0 = time.perf_counter()
        jax.profiler.stop_trace()
        _say(f"bench: trace written in {time.perf_counter() - t0:.1f} s")
    dropped = loop.dropped_events
    memory = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    del loop, start

    data = RunData(cell=cell_name, seconds=seconds, setup_s=setup_s)
    t_end = max(t_close, t_start + (steps[-1]["t1"] if steps else 0.0))
    in_window = collections.Counter(f for t, f in compiles if t_start <= t <= t_end)
    data.compiles_in_window = sum(in_window.values())
    if in_window:
        _say(f"bench: compiled inside the window: {dict(in_window)}")
    lat = np.full(len(sched), np.nan)
    for rec in steps:
        lat[rec["first"]:rec["last"]] = (rec["t1"] - sched.due[rec["first"]:rec["last"]]) * 1e3
    data.latencies_ms = lat
    data.steps = steps
    failed = int(np.isnan(lat).sum()) + int(dropped)
    placements = [(r["t1"], r["x"]) for r in steps if r["applied"]]
    data.d2b_mean = reference.d2b_mean(fleet, sched, seconds, x_start, placements)
    checks = check(fleet, config, sched, steps, control)
    if trace:
        files = sorted(pathlib.Path(trace_dir).rglob("*.xplane.pb"))
        if files:
            t0 = time.perf_counter()
            data.trace = trace_reduce.reduce_file(files[-1], [r["action"] for r in steps])
            _say(f"bench: trace of {files[-1].stat().st_size} bytes reduced in "
                 f"{time.perf_counter() - t0:.1f} s")
        shutil.rmtree(trace_dir, ignore_errors=True)

    metrics = {}
    for m in metrics_for(spec, cell_name, trace):
        if m["source"] == "device_trace" and dev.platform != "tpu":
            continue  # no device number from a CPU run
        value = plugins.load("metrics", m["name"]).read(data)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}

    compared = {
        "failed_events": (failed, "== 0"),
        "applied_passes": (checks["applied_passes"], ">= 1"),
        "rule_breaks": (checks["rule_breaks"], "== 0"),
        "demand_mismatch_apps": (checks["demand_mismatch"], "== 0"),
        "obj_gap": (checks["obj_gap"], f"<= {OBJ_GAP_LIMIT}"),
    }
    correct = (failed == 0 and checks["applied_passes"] >= 1 and checks["rule_breaks"] == 0
               and checks["demand_mismatch"] == 0 and checks["obj_gap"] <= OBJ_GAP_LIMIT)
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices),
              "memory_peak_bytes": memory}
    result = {"correct": bool(correct), "attempted": len(sched), "failed": failed,
              "metrics": metrics, "device": device}
    if trace and data.trace is not None:
        device.update(busy_s=data.trace["busy_s"], window_s=data.trace["window_s"])
        result["breakdown"] = {"device_ops": data.trace["device_ops"],
                               "idle_gaps": data.trace["idle_gaps"]}
    result["checks"] = {k: {"value": v, "limit": lim} for k, (v, lim) in compared.items()}
    if control:
        result["checks"]["obj_gap_program"] = {"value": checks["obj_gap_program"],
                                               "limit": f"<= {OBJ_GAP_LIMIT}"}

    _summary(data, sched, steps, undrained)
    for text in checks["fail_text"][:20]:
        _say(f"bench: CHECK {text}")
    for k, (v, lim) in compared.items():
        _say(f"bench: check {k} {v!r} (limit {lim})")
    return result


def _summary(data: RunData, sched, steps, undrained: int) -> None:
    kinds = {}
    for r in steps:
        kinds.setdefault(r["action"], []).append((r["t1"] - r["t0"]) * 1e3)
    mix = ", ".join(f"{k} {len(v)} (mean {np.mean(v):.1f} ms)" for k, v in sorted(kinds.items()))
    deltas = [r for r in steps if r["ran"] and r.get("solved_shards")]
    moved = sum(int(np.sum(r["x"] != r["x_inc"])) for r in steps if r["applied"])
    lat = data.latencies_ms[~np.isnan(data.latencies_ms)]
    _say(f"bench: setup_s {data.setup_s!r}; steps: {mix}")
    if deltas:
        share = np.mean([r["solved_shards"] / r["num_shards"] for r in deltas])
        _say(f"bench: shard passes {len(deltas)}, mean share of shards solved {share!r}")
    _say(f"bench: applied passes {sum(r['applied'] for r in steps)}, apps moved {moved} "
         f"({moved * 60.0 / data.seconds!r} per minute); events {len(sched)}, "
         f"undrained {len(sched) - undrained}; compiles in window {data.compiles_in_window}")
    if lat.size:
        # An event waits behind a solve when its own step ran a solver, or
        # when it fell due while a step that ran one was under way.
        behind = np.zeros(len(sched), bool)
        for r in steps:
            if r["ran"]:
                behind[r["first"]:r["last"]] = True
                behind[(sched.due >= r["t0"]) & (sched.due < r["t1"])] = True
        _say(f"bench: latency ms p50 {np.percentile(lat, 50)!r} p95 {np.percentile(lat, 95)!r} "
             f"max {lat.max()!r}; share of events behind a solve {behind.mean()!r}; "
             f"d2b_mean {data.d2b_mean!r}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-on-cpu", action="store_true",
                    help="test-only: accept the CPU backend at a small size")
    ap.add_argument("--apps", type=int, help="rehearsal size only")
    ap.add_argument("--tiers", type=int, help="rehearsal size only")
    args = ap.parse_args(argv)
    sizes = {k: v for k, v in (("num_apps", args.apps), ("num_tiers", args.tiers))
             if v is not None}
    if sizes and not args.rehearse_on_cpu:
        ap.error("--apps/--tiers are for --rehearse-on-cpu only")
    try:
        result = run(args.workload, args.seed, args.seconds, bool(args.trace),
                     rehearse=args.rehearse_on_cpu, sizes=sizes)
    except NoChip as e:
        _say(f"bench: {e}")
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
