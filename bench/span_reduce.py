"""Reduce the program's spans and counters in a profiler trace.

Reads the host planes of a ``jax.profiler.ProfileData`` and nothing else,
so a trace taken on the CPU reduces as well as one taken on the chip.
The program writes its spans with ``repro.spans`` (``service.*``,
``controller.*``, ``shard.*``, ``bus.*``); the harness writes ``bench.*``.
Inside the harness's ``bench.window`` span (the whole trace where there is
none) the reduction gives:

* ``spans``: for each span name the count, the total time and the self
  time (the duration less its children on the same thread line);
* ``within``: for each span name, the count and total time of each span
  nested inside it at any depth (``shard.solve`` inside ``shard.pass``);
* ``steps``: the spans grouped under their enclosing ``service.step``, by
  the ``action`` stat of the step's ``service.decision`` counter
  (``unclassified`` for a step without one), with the count of steps;
* ``stats``: the stats of every span and counter that carries any, in
  time order (``shard.lanes``, ``service.decision``, ...);
* ``idle_by_span``: the device's idle time summed by the innermost span
  (program or harness) that covers it, on the trace's own clock, averaged
  over the devices as ``trace_reduce``'s idle share is, largest first
  (empty for a trace without a device plane);
* ``layers``: the per-layer numbers read off the above (``layer_numbers``),
  each None where its span or counter is absent.

    python3 bench/span_reduce.py <trace.xplane.pb>

prints the reduction of one trace as JSON, for a trace taken by
``bench/run.py --trace 1`` or by ``jax.profiler.trace`` around a live
``ServiceLoop`` (docs/streaming_service.md, "Tracing").
"""

from __future__ import annotations

import collections
import json
import sys

import trace_reduce

SPAN_PREFIXES = ("service.", "controller.", "shard.", "bus.", "bench.")
WINDOW_SPAN = "bench.window"
STEP_SPAN = "service.step"
DECISION = "service.decision"
OUTSIDE = "outside spans"


def host_lines(profile) -> list[list[tuple]]:
    """Per host thread line, its spans as ``(start_ns, end_ns, name, stats)``
    sorted by start (outer before inner at equal starts)."""
    lines = []
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            spans = [(e.start_ns, e.end_ns, e.name, dict(e.stats))
                     for e in line.events if e.name.startswith(SPAN_PREFIXES)]
            if spans:
                lines.append(sorted(spans, key=lambda s: (s[0], -s[1])))
    return lines


def nesting(spans) -> list[int]:
    """The index of each span's parent on its line (-1 for none); ``spans``
    sorted as ``host_lines`` gives them."""
    parents, stack = [], []
    for i, (s, e, *_rest) in enumerate(spans):
        while stack and spans[stack[-1]][1] <= s:
            stack.pop()
        parents.append(stack[-1] if stack else -1)
        stack.append(i)
    return parents


def self_times(spans, parents) -> list[float]:
    """Each span's duration less its children's, in the trace's units."""
    own = [e - s for s, e, *_rest in spans]
    for i, p in enumerate(parents):
        if p >= 0:
            own[p] -= spans[i][1] - spans[i][0]
    return own


def innermost_segments(spans) -> list[tuple]:
    """Cut the time the spans cover into ``(start, end, name)`` pieces, each
    named after the innermost span over it (the latest started of those
    still open); ``spans`` as ``(start, end, name, ...)`` in any order."""
    out, stack, t = [], [], None
    for s, e, name, *_rest in sorted(spans, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][0] <= s:
            end, top = stack.pop()
            if end > t:
                out.append((t, end, top))
                t = end
        if stack and s > t:
            out.append((t, s, stack[-1][1]))
        stack.append((e, name))
        t = s
    while stack:
        end, top = stack.pop()
        if end > t:
            out.append((t, end, top))
            t = end
    return out


def idle_by_span(gaps, spans) -> list[list]:
    """Device idle time summed by the innermost span covering it
    (``OUTSIDE`` where none does): ``[[name, time], ...]``, largest first.
    ``gaps`` are disjoint ``(start, end)`` idle intervals."""
    segments = innermost_segments(spans)
    total = collections.Counter()
    j = 0
    for gs, ge in sorted(gaps):
        t = gs
        while j < len(segments) and segments[j][1] <= t:
            j += 1
        k = j
        while t < ge and k < len(segments):
            ss, se, name = segments[k]
            if ss >= ge:
                break
            if ss > t:
                total[OUTSIDE] += ss - t
                t = ss
            cut = min(se, ge)
            total[name] += cut - t
            t = cut
            if se <= ge:
                k += 1
        if t < ge:
            total[OUTSIDE] += ge - t
    return [[n, v] for n, v in total.most_common() if v > 0]


def _entry():
    return {"n": 0, "total_s": 0.0, "self_s": 0.0}


def reduce_lines(lines, gaps=()) -> dict:
    """The reduction of ``host_lines``' output (times in ns, given in s);
    ``gaps`` (device idle intervals on the same clock) feed
    ``idle_by_span``."""
    windows = [(s, e) for line in lines for s, e, n, _ in line if n == WINDOW_SPAN]
    lo, hi = windows[0] if windows else (float("-inf"), float("inf"))
    spans = collections.defaultdict(_entry)
    within = collections.defaultdict(lambda: collections.defaultdict(
        lambda: {"n": 0, "total_s": 0.0}))
    per_step = collections.defaultdict(lambda: collections.defaultdict(_entry))
    stats = collections.defaultdict(list)
    step_action, step_keys = {}, []
    kept = []
    for li, line in enumerate(lines):
        parents = nesting(line)
        own = self_times(line, parents)
        for i, (s, e, name, st) in enumerate(line):
            if not lo <= s <= hi:
                continue
            kept.append((s, e, name))
            dur = (e - s) * 1e-9
            rec = spans[name]
            rec["n"] += 1
            rec["total_s"] += dur
            rec["self_s"] += own[i] * 1e-9
            if st:
                stats[name].append((s, st))
            step = None
            p = parents[i]
            seen = set()
            while p >= 0:
                outer = line[p][2]
                if outer not in seen:  # a span counts once inside each name
                    seen.add(outer)
                    w = within[outer][name]
                    w["n"] += 1
                    w["total_s"] += dur
                if outer == STEP_SPAN and step is None:
                    step = (li, p)
                p = parents[p]
            if name == STEP_SPAN:
                step = (li, i)
                step_keys.append(step)
            if name == DECISION and step is not None:
                step_action[step] = str(st.get("action", "unclassified"))
            if step is not None:
                per_step[step][name]["n"] += 1
                per_step[step][name]["total_s"] += dur
                per_step[step][name]["self_s"] += own[i] * 1e-9
    steps = {}
    for step in step_keys:
        action = step_action.get(step, "unclassified")
        group = steps.setdefault(action, {"n": 0, "spans": collections.defaultdict(_entry)})
        group["n"] += 1
        for name, rec in per_step[step].items():
            for k, v in rec.items():
                group["spans"][name][k] += v
    return {
        "spans": {k: dict(v) for k, v in spans.items()},
        "within": {k: {i: dict(w) for i, w in v.items()} for k, v in within.items()},
        "steps": {a: {"n": g["n"], "spans": {k: dict(v) for k, v in g["spans"].items()}}
                  for a, g in steps.items()},
        "stats": {k: [st for _, st in sorted(v, key=lambda x: x[0])] for k, v in stats.items()},
        "idle_by_span": [[n, v * 1e-9] for n, v in idle_by_span(gaps, kept)],
    }


def device_gaps(profile, lines) -> tuple[list, int]:
    """The device's idle intervals (ns) inside the window, over every device
    plane, and the number of devices.  The window is ``bench.window``, else
    the stretch the program's spans cover."""
    windows = [(s, e) for line in lines for s, e, n, _ in line if n == WINDOW_SPAN]
    if windows:
        lo, hi = windows[0]
    elif lines:
        lo = min(line[0][0] for line in lines)
        hi = max(e for line in lines for _, e, _, _ in line)
    else:
        return [], 0
    devices = [p for p in profile.planes if trace_reduce._DEVICE.match(p.name)]
    gaps = []
    for plane in devices:
        ops = trace_reduce._line(plane, "XLA Ops")
        merged = trace_reduce._union(trace_reduce._clip(
            [(e.start_ns, e.end_ns) for e in (ops.events if ops is not None else ())], lo, hi))
        edges = [lo] + [v for se in merged for v in se] + [hi]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    return gaps, len(devices)


def _mean_in_noop_steps(red, name):
    noop = red["steps"].get("noop")
    rec = (noop or {}).get("spans", {}).get(name)
    return rec["total_s"] * 1e3 / noop["n"] if rec else None


def _host_ms(red, outer, inner):
    """Mean time (ms) of an ``outer`` span less its ``inner`` children."""
    rec = red["spans"].get(outer)
    if not rec:
        return None
    solve = red["within"].get(outer, {}).get(inner, {"total_s": 0.0})
    return (rec["total_s"] - solve["total_s"]) * 1e3 / rec["n"]


def _lane_idle_frac(red):
    lanes = [s for s in red["stats"].get("shard.lanes", ())
             if int(s["lanes"]) and int(s["iters_max"])]
    if not lanes:
        return None
    return sum(1.0 - int(s["iters_sum"]) / (int(s["lanes"]) * int(s["iters_max"]))
               for s in lanes) / len(lanes)


def _solves_per_pass(red):
    rec = red["spans"].get("bus.pass")
    if not rec:
        return None
    return red["within"].get("bus.pass", {}).get("bus.solve", {"n": 0})["n"] / rec["n"]


def _evaluate_ms(red):
    rec = red["spans"].get("controller.evaluate")
    return rec["total_s"] * 1e3 / rec["n"] if rec else None


LAYER_NUMBERS = {
    # service frontend: mean time per quiet step (drift decision noop)
    "frontend.drain_ms": lambda red: _mean_in_noop_steps(red, "service.drain"),
    "frontend.scope_ms": lambda red: _mean_in_noop_steps(red, "service.scope"),
    "frontend.drift_ms": lambda red: _mean_in_noop_steps(red, "service.drift"),
    # controller: mean evaluation of a pass's decision
    "controller.evaluate_ms": _evaluate_ms,
    # shard solve: a sharded pass's host share, and the lanes of its
    # batched solve that wait on the slowest shard
    "shard.host_ms": lambda red: _host_ms(red, "shard.pass", "shard.solve"),
    "shard.lane_idle_frac": _lane_idle_frac,
    # cooperation bus: a full pass's host share, and its solves
    "bus.host_ms": lambda red: _host_ms(red, "bus.pass", "bus.solve"),
    "bus.solves_per_pass": _solves_per_pass,
}


def layer_numbers(red) -> dict:
    """The per-layer numbers of one reduction, None where absent."""
    return {name: f(red) for name, f in LAYER_NUMBERS.items()}


def reduce_profile(profile) -> dict:
    """``reduce_lines`` over a ``ProfileData``'s host planes, with the
    device's idle time put down to spans and the per-layer numbers."""
    lines = host_lines(profile)
    gaps, num_devices = device_gaps(profile, lines)
    out = reduce_lines(lines, gaps)
    out["idle_by_span"] = [[n, v / num_devices] for n, v in out["idle_by_span"]]
    out["layers"] = layer_numbers(out)
    return out


def reduce_file(path) -> dict:
    from jax.profiler import ProfileData

    return reduce_profile(ProfileData.from_file(str(path)))


if __name__ == "__main__":
    if len(sys.argv) != 2:
        raise SystemExit("usage: python3 bench/span_reduce.py <trace.xplane.pb>")
    print(json.dumps(reduce_file(sys.argv[1]), indent=1))
