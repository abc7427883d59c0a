"""Spans and counters of the control loop, on the profiler's clock.

The loop times its phases with ``span``: a ``jax.profiler.TraceAnnotation``
that lands in any profiler trace taken around it (``jax.profiler.trace``),
nested under the span that encloses it, with ``stats`` attached as the
event's stats.  Where the phase also feeds a timing record the program
keeps (``CoopTimings``, ``solve_fleet``'s ``timings``), ``into``/``key``
add the elapsed wall-clock seconds to it, so the record and the trace are
read off one stopwatch.

``count`` marks a zero-length event that carries numbers (a counter at a
layer boundary: the drift decision, the lanes of a batched solve).

With no profiler running a span costs the annotation's enabled check and
two clock reads.  Spans go at step and phase granularity only, never per
event, per app or per sweep iteration.  docs/streaming_service.md lists
every span and counter and what each tells an operator.
"""

from __future__ import annotations

import time

from jax.profiler import TraceAnnotation


class span:
    """``with span(name, into=None, key=None, **stats):`` annotates the
    block as ``name``; with ``into`` it adds the block's elapsed seconds to
    ``into[key]`` (a dict) or ``into.<key>`` (any other object, such as a
    dataclass) when the block completes."""

    __slots__ = ("_annotation", "_into", "_key", "_t")

    def __init__(self, name: str, into=None, key: str | None = None, **stats):
        self._annotation = TraceAnnotation(name, **stats)
        self._into = into
        self._key = key

    def __enter__(self):
        self._t = time.perf_counter()
        self._annotation.__enter__()
        return self

    def __exit__(self, *exc):
        self._annotation.__exit__(*exc)
        into = self._into
        if into is not None and exc[0] is None:
            elapsed = time.perf_counter() - self._t
            if isinstance(into, dict):
                into[self._key] = into.get(self._key, 0.0) + elapsed
            else:
                setattr(into, self._key, getattr(into, self._key) + elapsed)
        return False


def count(name: str, **values) -> None:
    """Emit a zero-length event ``name`` that carries ``values`` as stats."""
    with TraceAnnotation(name, **values):
        pass
