"""Reduce a profiler trace (``.xplane.pb``) to the benchmark's device numbers.

Reads the trace with ``jax.profiler.ProfileData`` and nothing else:

* the measured window: the host span ``bench.window`` the harness wraps
  around it;
* device busy time: on each device plane (``/device:TPU:<n>``), the union of
  the intervals of its ``XLA Ops`` events inside the window, averaged over
  the devices; the idle share is 1 - busy / window;
* device time per jitted module: the durations of the ``XLA Modules``
  events, keyed by module name with its ``(<id>)`` suffix dropped.  Every
  chip runs a module's executions in the same order, so the k-th event of
  a module on each plane is one execution; an execution takes as long as
  its slowest chip, which is what a pass over a mesh waits for, and the
  module's time is the sum of those (``modules_n`` counts the events on
  every plane).  On one device this is the plain sum;
* the top device ops by time on the chip that spent most on each, and the
  longest stretches of device idle time, each cut at the edges of the
  harness spans (``bench.*``) on the host and named after the span the
  host was in.

A trace without a device plane (a run on the CPU) yields no device numbers.
"""

from __future__ import annotations

import bisect
import collections
import re

WINDOW_SPAN = "bench.window"
STEP_SPAN = "bench.step"
SPAN_PREFIX = "bench."
_SUFFIX = re.compile(r"\(\d+\)$")
_DEVICE = re.compile(r"^/device:TPU:\d+$")


def module_name(name: str) -> str:
    return _SUFFIX.sub("", name)


def op_name(name: str) -> str:
    """``%fusion.3 = f32[...] fusion(...)`` -> ``fusion.3``."""
    return name.split(" = ", 1)[0].lstrip("%")


def _union(intervals):
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def _line(plane, name):
    for line in plane.lines:
        if line.name == name:
            return line
    return None


def _idle_segments(gaps, spans, top: int):
    """The ``top`` longest stretches of device idle time, each cut at the
    edges of the harness spans on the host and named after the span it lies
    in ("outside harness spans" where none covers it).  ``spans`` are
    ``(start, end, name)``, sorted and not nested."""
    starts = [s for s, _, _ in spans]
    best: list = []
    for s, e in sorted(gaps, key=lambda g: g[1] - g[0], reverse=True):
        if len(best) >= top and e - s <= best[-1][1]:
            break  # no piece of a shorter gap can beat the ones kept
        pieces, t = [], s
        i = max(0, bisect.bisect_right(starts, s) - 1)
        while t < e and i < len(spans):
            hs, he, name = spans[i]
            if he <= t:
                i += 1
                continue
            if hs > t:
                pieces.append(("outside harness spans", min(hs, e) - t))
                t = min(hs, e)
                continue
            pieces.append((name, min(he, e) - t))
            t = min(he, e)
            i += 1
        if t < e:
            pieces.append(("outside harness spans", e - t))
        best = sorted(best + pieces, key=lambda p: p[1], reverse=True)[:top]
    return best


def reduce_profile(profile, step_labels=(), top: int = 10) -> dict | None:
    """Device numbers of one ``ProfileData``; None where it has no device.

    ``step_labels`` names the harness's ``bench.step`` spans in the order
    they started (the drift decision of each step), so that an idle gap
    inside a step says which kind of step it was."""
    host_spans = []
    for plane in profile.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                host_spans += [(e.name, e.start_ns, e.end_ns) for e in line.events
                               if e.name.startswith(SPAN_PREFIX)]
    windows = [(s, e) for n, s, e in host_spans if n == WINDOW_SPAN]
    devices = [p for p in profile.planes if _DEVICE.match(p.name)]
    if not windows or not devices:
        return None
    lo, hi = windows[0]
    window_ns = hi - lo
    busy_ns, gaps = [], []
    op_ns = collections.Counter()           # op -> most time on one device
    mod_ns = collections.defaultdict(list)  # module -> slowest device per execution
    mod_n = collections.Counter()
    for plane in devices:
        ops = _line(plane, "XLA Ops")
        events = list(ops.events) if ops is not None else []
        spans = _clip([(e.start_ns, e.end_ns) for e in events], lo, hi)
        merged = _union(spans)
        busy_ns.append(sum(e - s for s, e in merged))
        ops_here = collections.Counter()
        for e in events:
            if lo <= e.start_ns < hi:
                ops_here[op_name(e.name)] += e.duration_ns
        for name, ns in ops_here.items():
            op_ns[name] = max(op_ns[name], ns)
        edges = [lo] + [v for se in merged for v in se] + [hi]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
        mods = _line(plane, "XLA Modules")
        runs = collections.defaultdict(list)
        for e in sorted(mods.events if mods is not None else (), key=lambda e: e.start_ns):
            if lo <= e.start_ns < hi:
                runs[module_name(e.name)].append(e.duration_ns)
        for name, durations in runs.items():
            mod_n[name] += len(durations)
            slowest = mod_ns[name]
            for k, ns in enumerate(durations):
                if k < len(slowest):
                    slowest[k] = max(slowest[k], ns)
                else:
                    slowest.append(ns)
    inner = sorted((s, e, n) for n, s, e in host_spans if n != WINDOW_SPAN)
    steps = iter(step_labels)
    inner = [(s, e, f"{n}.{next(steps, 'unlabelled')}" if n == STEP_SPAN else n)
             for s, e, n in inner]
    segments = _idle_segments(gaps, inner, top)
    busy = sum(busy_ns) / len(busy_ns)
    return {
        "window_s": window_ns / 1e9,
        "busy_s": busy / 1e9,
        "idle_frac": 1.0 - busy / window_ns if window_ns > 0 else None,
        "modules_s": {k: sum(v) / 1e9 for k, v in mod_ns.items()},
        "modules_n": dict(mod_n),
        "device_ops": [[n, v / 1e9] for n, v in op_ns.most_common(top)],
        "idle_gaps": [[n, d / 1e9] for n, d in segments],
        "num_devices": len(devices),
    }


def reduce_file(path: str, step_labels=()) -> dict | None:
    from jax.profiler import ProfileData

    return reduce_profile(ProfileData.from_file(str(path)), step_labels)
