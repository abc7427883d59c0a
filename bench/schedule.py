"""The one traffic generator: a traffic file's parameters -> an event schedule.

A traffic mix is a data file, ``bench/traffic/<name>.json``.  Its
``processes`` list names the processes that make the traffic, each by
``kind`` (a module ``bench/processes/<kind>.py``) with its parameters; the
file's ``jitter_sigma`` is the lognormal noise of every reading.  During
set-up, from ``--seed`` alone, this module builds every event that falls
due in the measured window: its due time (seconds from the window's start),
its kind (a module ``bench/events/<kind>.py``) and its payload.  Nothing
here looks at what the control loop decides, so the load is open loop: a
slow step delays no event's due time.

What a reading reports is the fleet's *true* demand at its due time: each
app's demand as built, times a multiplier that the processes set.  A
process module has ``ORDER`` and ``timeline(b, spec, seconds)``: in file
order, each process draws what it needs up front and returns its timed
actions; the actions then run in time order (at one instant, a lower
``ORDER`` first, so that multiplier changes land before the readings taken
at the same instant).  All counts are fixed by the file, so every seed
sends the same number of events of the same sizes, only over other apps
and values.
"""

from __future__ import annotations

import dataclasses

import numpy as np

import plugins


@dataclasses.dataclass
class Schedule:
    due: np.ndarray       # f64[E] seconds from the window's start, ascending
    kinds: list           # E event kinds (``bench/events/<kind>.py``)
    payloads: list        # E payload dicts

    def __len__(self) -> int:
        return int(self.due.size)


class Builder:
    """The state the processes share: the fleet, each app's true-demand
    multiplier, the seeded generator, and the events emitted so far."""

    def __init__(self, fleet, seed: int, jitter_sigma: float):
        self.fleet = fleet
        self.base = fleet.demand.astype(np.float32)
        self.base_tasks = fleet.tasks.astype(np.float32)
        self.mult = np.ones(fleet.num_apps, np.float64)
        self.layers = {}   # one multiplier per app for each process that sets one
        self.sigma = float(jitter_sigma)
        self.rng = np.random.default_rng(np.random.SeedSequence([int(seed), 12]))
        self.out = []  # (due, order, kind, payload)

    def emit(self, due: float, kind: str, payload: dict) -> None:
        self.out.append((float(due), len(self.out), kind, payload))

    def zipf_draw(self, weight_of_app: np.ndarray, k: int) -> np.ndarray:
        """``k`` distinct apps drawn with probability proportional to
        ``weight_of_app``, in ascending order."""
        k = min(k, int(np.count_nonzero(weight_of_app)))
        p = weight_of_app / weight_of_app.sum()
        return np.sort(self.rng.choice(weight_of_app.size, size=k, replace=False, p=p))

    def set_factor(self, layer, ids, factor: float) -> None:
        """Set process ``layer``'s multiplier of ``ids``; an app's true
        demand scales by the product of every process's multiplier."""
        own = self.layers.setdefault(layer, np.ones(self.fleet.num_apps))
        own[ids] = factor
        self.mult[ids] = np.prod([m[ids] for m in self.layers.values()], axis=0)

    def step_changes(self, layer, steps, record_apps: int) -> list:
        """Timed actions for ``steps``, a list of ``(time, apps, factor)``:
        at each step its apps take the factor and the previous step's apps
        return to base, and every app touched reports at once."""
        out, prev = [], np.zeros(0, np.int64)
        for t, apps, factor in steps:
            def act(b, t=t, back=prev, apps=apps, factor=factor):
                b.set_factor(layer, back, 1.0)
                b.set_factor(layer, apps, factor)
                b.report(t, np.union1d(back, apps), record_apps)
            out.append((t, act))
            prev = apps
        return out

    def report(self, due: float, ids, record_apps: int) -> None:
        """Telemetry records of the true demand of ``ids``, ``record_apps``
        apps to a record, each reading with the mix's jitter."""
        ids = np.asarray(ids, np.int64)
        jitter = (self.rng.lognormal(0.0, self.sigma, (ids.size, 1)) if self.sigma else 1.0)
        demand = self.base[ids] * (self.mult[ids, None] * jitter)
        tasks = self.base_tasks[ids]
        for s in range(0, ids.size, record_apps):
            sl = slice(s, s + record_apps)
            self.emit(due, "telemetry", {
                "app_ids": ids[sl].copy(),
                "demand": np.ascontiguousarray(demand[sl], np.float32),
                "tasks": np.ascontiguousarray(tasks[sl], np.float32)})


def build(fleet, traffic: dict, seed: int, seconds: float) -> Schedule:
    """Every event due in ``[0, seconds)`` for this fleet, traffic and seed."""
    b = Builder(fleet, seed, traffic.get("jitter_sigma", 0.0))
    actions = []
    for rank, spec in enumerate(traffic["processes"]):
        proc = plugins.load("processes", spec["kind"])
        for t, act in proc.timeline(b, spec, seconds):
            if t < seconds:
                actions.append((float(t), int(proc.ORDER), rank, len(actions), act))
    for *_, act in sorted(actions, key=lambda a: a[:4]):
        act(b)
    events = sorted((e for e in b.out if e[0] < seconds), key=lambda e: e[:2])
    return Schedule(due=np.asarray([e[0] for e in events], np.float64),
                    kinds=[e[2] for e in events], payloads=[e[3] for e in events])
