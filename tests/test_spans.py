"""Spans and counters of the control loop (``repro.spans``) and their
reduction (``bench/span_reduce.py``).

A small ``ServiceLoop`` runs a quiet step, a delta pass and a full pass
under ``jax.profiler.trace`` on the CPU; the reduction of that trace must
hold every span and counter the loop writes, nest them as the code does,
classify each step by its drift decision, and agree with the timing
records the program keeps.  The reduction's self times and idle
attribution are checked on hand-made intervals and on a recorded chip
trace, and its per-layer numbers on the reduction of the traced loop.
"""

import dataclasses
import json
import pathlib
import subprocess
import sys

import numpy as np
import pytest

from repro.core import BalanceController, ControllerConfig, generate_cluster
from repro.service import (DELTA, FULL, NOOP, CapacityUpdate, DriftConfig,
                           ServiceConfig, ServiceLoop, TelemetryDelta)
from repro.spans import count, span

BENCH = pathlib.Path(__file__).resolve().parents[1] / "bench"
sys.path.insert(0, str(BENCH))

import span_reduce  # noqa: E402
import trace_reduce  # noqa: E402

SPANS = ("service.step", "service.drain", "service.scope", "service.drift",
         "service.commit", "controller.decide", "controller.balance",
         "controller.solve", "controller.evaluate", "shard.pass",
         "shard.partition", "shard.solve", "shard.merge", "shard.coordinator",
         "bus.pass", "bus.premask", "bus.vet", "bus.solve", "bus.feedback",
         "bus.revert", "bus.budget", "bus.pack")
COUNTERS = {"service.decision": {"action", "dirty"},
            "shard.lanes": {"lanes", "iters_max", "iters_sum"}}
# (inner, outer): every ``inner`` span lies inside an ``outer`` one.
NESTED = [("service.drain", "service.step"), ("service.scope", "service.step"),
          ("service.drift", "service.step"), ("service.commit", "service.step"),
          ("controller.decide", "service.step"),
          ("controller.balance", "service.step"),
          ("controller.solve", "controller.balance"),
          ("controller.evaluate", "controller.balance"),
          ("shard.pass", "controller.solve"), ("bus.pass", "controller.solve"),
          ("shard.partition", "shard.pass"), ("shard.solve", "shard.pass"),
          ("shard.merge", "shard.pass"), ("shard.coordinator", "shard.pass"),
          ("shard.lanes", "shard.pass"), ("bus.premask", "bus.pass"),
          ("bus.vet", "bus.pass"), ("bus.solve", "bus.pass"),
          ("bus.feedback", "bus.pass"), ("bus.revert", "bus.pass"),
          # the budget trim closes a bus pass and a sharded pass alike
          ("bus.budget", "controller.solve"), ("bus.pack", "bus.vet"),
          ("service.decision", "service.step")]
LAYER_NUMBERS = ("frontend.drain_ms", "frontend.scope_ms", "frontend.drift_ms",
               "controller.evaluate_ms", "shard.host_ms", "shard.lane_idle_frac",
               "bus.host_ms", "bus.solves_per_pass")


# ---------------------------------------------------------------------------
# the helper
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class _Clock:
    seconds: float = 0.25


def test_span_feeds_a_dict_key_and_a_dataclass_field():
    d = {"solve_s": 1.0}
    clock = _Clock()
    with span("test.dict", into=d, key="solve_s", level="host"):
        pass
    with span("test.new", into=d, key="fresh_s"):
        pass
    with span("test.field", into=clock, key="seconds"):
        pass
    assert 1.0 < d["solve_s"] < 1.1
    assert 0.0 < d["fresh_s"] < 0.1
    assert 0.25 < clock.seconds < 0.35
    with pytest.raises(ValueError):
        with span("test.raise", into=d, key="raised_s"):
            raise ValueError
    assert "raised_s" not in d   # a block that raised adds nothing
    count("test.counter", lanes=3, action="noop")   # no profiler: a no-op


# ---------------------------------------------------------------------------
# the reduction on hand-made intervals
# ---------------------------------------------------------------------------

def _line(*spans):
    return sorted(((s, e, n, st) for s, e, n, *rest in spans
                   for st in [rest[0] if rest else {}]), key=lambda x: (x[0], -x[1]))


def test_self_time_subtracts_direct_children_only():
    line = _line((0, 100, "service.step"), (10, 40, "service.drain"),
                 (15, 25, "bus.pack"), (50, 90, "service.drift"))
    parents = span_reduce.nesting(line)
    assert [line[p][2] if p >= 0 else None for p in parents] == [
        None, "service.step", "service.drain", "service.step"]
    own = dict(zip((s[2] for s in line), span_reduce.self_times(line, parents)))
    assert own == {"service.step": 30, "service.drain": 20, "bus.pack": 10,
                   "service.drift": 40}


@pytest.mark.parametrize("gaps,want", [
    # one gap inside the innermost span
    ([(16, 20)], [["bus.pack", 4]]),
    # a gap across child and parent edges
    ([(5, 30)], [["service.step", 5], ["service.drain", 5 + 5], ["bus.pack", 10]]),
    # gaps partly outside every span
    ([(-10, 5), (95, 120)], [["outside spans", 10 + 20], ["service.step", 5 + 5]]),
    # several gaps, in any order
    ([(60, 70), (12, 14)], [["service.drift", 10], ["service.drain", 2]]),
])
def test_idle_goes_to_the_innermost_span(gaps, want):
    spans = [(0, 100, "service.step"), (10, 40, "service.drain"),
             (15, 25, "bus.pack"), (50, 90, "service.drift")]
    got = span_reduce.idle_by_span(gaps, spans)
    assert sorted(got) == sorted(want)
    assert sum(v for _, v in got) == sum(e - s for s, e in gaps)


def test_steps_are_classified_by_their_decision_counter():
    line = _line((0, 1000, "bench.window"),
                 (10, 100, "service.step"), (20, 30, "service.drain"),
                 (40, 40, "service.decision", {"action": "delta", "dirty": 2}),
                 (50, 90, "shard.pass"), (55, 80, "shard.solve"),
                 (200, 240, "service.step"), (205, 215, "service.drain"),
                 (220, 220, "service.decision", {"action": "noop", "dirty": 0}),
                 (300, 330, "service.step"), (305, 310, "service.drain"),
                 (320, 320, "service.decision", {"action": "noop", "dirty": 0}),
                 (1500, 1600, "service.step"))   # after the window: left out
    out = span_reduce.reduce_lines([line])   # times in ns, read in s
    assert out["steps"]["noop"]["n"] == 2 and out["steps"]["delta"]["n"] == 1
    assert out["steps"]["noop"]["spans"]["service.drain"]["total_s"] == pytest.approx(15e-9)
    assert out["steps"]["delta"]["spans"]["shard.solve"]["total_s"] == pytest.approx(25e-9)
    assert out["spans"]["service.step"]["n"] == 3
    assert out["spans"]["shard.pass"]["self_s"] == pytest.approx(15e-9)
    assert out["within"]["shard.pass"]["shard.solve"] == {"n": 1, "total_s": pytest.approx(25e-9)}
    assert out["within"]["service.step"]["shard.solve"]["total_s"] == pytest.approx(25e-9)
    assert [s["action"] for s in out["stats"]["service.decision"]] == [
        "delta", "noop", "noop"]


SMALL_TRACE = BENCH / "tests" / "data" / "small.xplane.pb"


def test_recorded_chip_trace_puts_all_idle_time_down_to_a_span():
    out = trace_reduce.reduce_file(SMALL_TRACE)
    idle = span_reduce.reduce_file(SMALL_TRACE)["idle_by_span"]
    assert sum(v for _, v in idle) == pytest.approx(out["window_s"] - out["busy_s"], rel=1e-9)
    assert {n for n, _ in idle} <= {"bench.window", "bench.step", "bench.sleep"}
    assert idle[0][0] == "bench.sleep"


def test_command_line_prints_the_reduction_of_a_trace():
    done = subprocess.run([sys.executable, str(BENCH / "span_reduce.py"), str(SMALL_TRACE)],
                          capture_output=True, text=True, check=True)
    out = json.loads(done.stdout)
    assert out["spans"]["bench.window"]["n"] == 1 and out["idle_by_span"]
    # a trace of a program without spans of its own: no per-layer number
    assert set(out["layers"]) == set(LAYER_NUMBERS)
    assert all(v is None for v in out["layers"].values())


# ---------------------------------------------------------------------------
# a traced service loop
# ---------------------------------------------------------------------------

def _loop_events(cluster):
    p = cluster.problem
    demand, tasks = np.asarray(p.demand), np.asarray(p.tasks)
    live = np.flatnonzero(np.asarray(p.valid))
    quiet = live[40:60]
    hot = live[:6]
    return [
        # a quiet step: readings equal to the reference
        TelemetryDelta(app_ids=tuple(int(i) for i in quiet), demand=demand[quiet],
                       tasks=tasks[quiet], collected_at=1),
        # a delta pass: a few apps double their demand
        TelemetryDelta(app_ids=tuple(int(i) for i in hot), demand=demand[hot] * 2.0,
                       tasks=tasks[hot], collected_at=2),
        # a full pass: a structural change
        CapacityUpdate(capacity=np.asarray(p.capacity) * 0.85),
    ]


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    import jax
    from jax.profiler import ProfileData

    cluster = generate_cluster(num_apps=96, seed=0)
    # Standing-imbalance gates out of the way: the delta pass comes from
    # the dirty apps alone, the full pass from the capacity change.
    drift = DriftConfig(d2b_full=10.0, over_ideal_full=10.0, full_threshold=10.0,
                        d2b_delta=0.0, floor_margin=0.0)
    # A controller told to solve whenever the loop asks (as the benchmark's
    # warm-up does), so the full pass runs the bus.
    controller = BalanceController(cluster, ControllerConfig(trigger_d2b=-1.0,
                                                             cooldown_rounds=0))
    loop = ServiceLoop(controller=controller,
                       config=ServiceConfig(num_shards=3, drift=drift))
    events = _loop_events(cluster)
    out_dir = tmp_path_factory.mktemp("trace")
    results = []
    with jax.profiler.trace(str(out_dir)):
        for now, event in enumerate(events, start=1):
            loop.submit(event)
            results.append(loop.step(now))
    path = sorted(out_dir.rglob("*.xplane.pb"))[-1]
    reduced = span_reduce.reduce_profile(ProfileData.from_file(str(path)))
    return loop, results, reduced


def test_loop_takes_a_quiet_step_a_delta_and_a_full_pass(traced):
    loop, results, _ = traced
    assert [r.action for r in results] == [NOOP, DELTA, FULL]
    assert results[1].result.delta and results[2].result.decision.cooperation
    assert loop.step_count == 3 and loop.stats()["steps"] == 3
    assert all(r.latency_s > 0 for r in results)


@pytest.mark.parametrize("name", SPANS)
def test_every_span_is_in_the_trace(traced, name):
    assert traced[2]["spans"][name]["n"] >= 1


@pytest.mark.parametrize("name", sorted(COUNTERS))
def test_every_counter_carries_its_stats(traced, name):
    stats = traced[2]["stats"][name]
    assert stats and all(COUNTERS[name] <= set(s) for s in stats)


@pytest.mark.parametrize("inner,outer", NESTED)
def test_children_lie_inside_their_parent(traced, inner, outer):
    red = traced[2]
    assert red["within"][outer][inner]["n"] == red["spans"][inner]["n"]


def test_parents_cover_their_children_on_the_trace_clock(traced):
    red = traced[2]
    for name, rec in red["spans"].items():
        assert 0 <= rec["self_s"] <= rec["total_s"] + 1e-9, name
        for inner, w in red["within"].get(name, {}).items():
            if inner != name:
                assert w["total_s"] <= rec["total_s"] + 1e-9, (name, inner)


def test_steps_are_classified_by_service_decision(traced):
    loop, results, red = traced
    assert {a: g["n"] for a, g in red["steps"].items()} == {NOOP: 1, DELTA: 1, FULL: 1}
    assert [s["action"] for s in red["stats"]["service.decision"]] == [NOOP, DELTA, FULL]
    assert "shard.pass" in red["steps"][DELTA]["spans"]
    assert "bus.pass" in red["steps"][FULL]["spans"]
    assert red["within"]["bus.pass"]["bus.budget"]["n"] == 1
    assert set(red["steps"][NOOP]["spans"]) == {
        "service.step", "service.drain", "service.scope", "service.drift",
        "service.decision"}
    lanes = red["stats"]["shard.lanes"]
    assert lanes[0]["lanes"] == int(results[1].result.decision.solve.extra[
        "sharded"]["solved_shards"])


def test_trace_agrees_with_the_programs_timing_records(traced):
    _, results, red = traced
    tm = results[2].result.decision.cooperation.timings
    assert red["spans"]["bus.solve"]["total_s"] == pytest.approx(tm.solve_s, rel=0.02)
    assert red["spans"]["bus.pass"]["total_s"] == pytest.approx(tm.total_s, rel=0.02)
    sharded = results[1].result.decision.solve.extra["sharded"]
    assert red["spans"]["shard.solve"]["total_s"] == pytest.approx(sharded["solve_s"], rel=0.02)
    assert red["spans"]["shard.pass"]["total_s"] == pytest.approx(sharded["total_s"], rel=0.02)
    steps = sum(r.latency_s for r in results)
    assert red["spans"]["service.step"]["total_s"] == pytest.approx(steps, rel=0.02)


@pytest.mark.parametrize("name", LAYER_NUMBERS)
def test_metric_reads_the_traced_loop(traced, name):
    red = traced[2]
    value = red["layers"][name]
    assert value is not None and value >= 0
    if name == "bus.solves_per_pass":
        assert value == red["spans"]["bus.solve"]["n"]
    if name == "shard.host_ms":
        want = red["spans"]["shard.pass"]["total_s"] - red["spans"]["shard.solve"]["total_s"]
        assert value == pytest.approx(want * 1e3)
    if name == "shard.lane_idle_frac":
        assert 0 <= value < 1


@pytest.mark.parametrize("name", LAYER_NUMBERS)
def test_metric_is_silent_without_its_span(name):
    """A trace of a program without these spans (only the harness's) gives
    no reading."""
    bench_only = span_reduce.reduce_lines([_line((0, 100, "bench.window"),
                                                 (10, 20, "bench.step"))])
    assert span_reduce.layer_numbers(bench_only)[name] is None
