"""The per-layer metrics read from the program's spans, and the trace
reduction's device numbers per chip.

Each ``bench/metrics/<name>.py`` of a ``span_reduce.LAYER_NUMBERS`` entry
returns the reduction's number, on the recorded chip trace and on hand-made
spans that give every layer a reading, and None without a traced run.  On
a hand-made profile of two devices, a module's time is that of its slowest
device in each execution, and an op's that of the device that spent most
on it.
"""

import json
import pathlib
import sys

import numpy as np
import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import plugins  # noqa: E402
import run as bench_run  # noqa: E402
import span_reduce  # noqa: E402
import trace_reduce  # noqa: E402

TRACE = BENCH / "tests" / "data" / "small.xplane.pb"
SPEC = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
NAMES = sorted(span_reduce.LAYER_NUMBERS)


def _line(*spans):
    return sorted(((s, e, n, st) for s, e, n, *rest in spans
                   for st in [rest[0] if rest else {}]), key=lambda x: (x[0], -x[1]))


def _every_layer():
    """A window with a quiet step, a sharded pass and a bus pass: every
    layer number reads."""
    lanes = {"lanes": 8, "iters_max": 10, "iters_sum": 60}
    return span_reduce.reduce_lines([_line(
        (0, 10_000, "bench.window"),
        (100, 200, "service.step"), (110, 120, "service.drain"),
        (120, 150, "service.scope"), (150, 190, "service.drift"),
        (190, 190, "service.decision", {"action": "noop"}),
        (1000, 3000, "service.step"), (1000, 1000, "service.decision", {"action": "delta"}),
        (1100, 2900, "controller.balance"), (1100, 2500, "controller.solve"),
        (1100, 2400, "shard.pass"), (1200, 2000, "shard.solve"),
        (2000, 2000, "shard.lanes", lanes), (2500, 2800, "controller.evaluate"),
        (4000, 9000, "service.step"), (4000, 4000, "service.decision", {"action": "full"}),
        (4100, 8900, "controller.balance"), (4100, 8000, "controller.solve"),
        (4100, 7900, "bus.pass"), (4200, 5000, "bus.solve"), (5500, 6500, "bus.solve"),
        (8000, 8500, "controller.evaluate"),
    )])


def _run_data(spans):
    data = bench_run.RunData(cell="regional.hotspot", seconds=1.0)
    data.spans = spans
    return data


@pytest.mark.parametrize("name", NAMES)
def test_reader_returns_the_reduction_of_the_recorded_trace(name):
    red = span_reduce.reduce_file(TRACE)
    assert plugins.load("metrics", name).read(_run_data(red)) == red["layers"][name]


@pytest.mark.parametrize("name", NAMES)
def test_reader_returns_the_layer_number(name):
    red = _every_layer()
    red["layers"] = span_reduce.layer_numbers(red)
    want = red["layers"][name]
    assert want is not None
    assert plugins.load("metrics", name).read(_run_data(red)) == want


def test_hand_made_layer_numbers():
    numbers = span_reduce.layer_numbers(_every_layer())
    assert numbers["frontend.drift_ms"] == pytest.approx(40e-6)
    assert numbers["shard.host_ms"] == pytest.approx((1300 - 800) * 1e-6)
    assert numbers["shard.lane_idle_frac"] == pytest.approx(1 - 60 / 80)
    assert numbers["bus.host_ms"] == pytest.approx((3800 - 1800) * 1e-6)
    assert numbers["bus.solves_per_pass"] == 2
    assert numbers["controller.evaluate_ms"] == pytest.approx(400e-6)


@pytest.mark.parametrize("name", NAMES)
def test_reader_is_silent_without_a_trace(name):
    assert plugins.load("metrics", name).read(_run_data(None)) is None


@pytest.mark.parametrize("name", NAMES)
def test_benchmark_lists_the_metric(name):
    entry = {m["name"]: m for m in SPEC["per_layer"]}[name]
    cells = {c["name"] for c in SPEC["workloads"]}
    assert entry["source"] in ("program_span", "program_counter")
    assert set(entry["workloads"]) <= cells
    assert entry["moves"] in {m["name"] for m in SPEC["end_to_end"]}


# ---------------------------------------------------------------------------
# device numbers per chip
# ---------------------------------------------------------------------------

class _Event:
    def __init__(self, name, start, end):
        self.name, self.start_ns, self.end_ns = name, start, end
        self.duration_ns = end - start


class _Line:
    def __init__(self, name, events):
        self.name, self.events = name, events


class _Plane:
    def __init__(self, name, lines):
        self.name, self.lines = name, lines


class _Profile:
    def __init__(self, planes):
        self.planes = planes


def _device(n, modules, ops):
    return _Plane(f"/device:TPU:{n}", [
        _Line("XLA Modules", [_Event(name, s, e) for name, s, e in modules]),
        _Line("XLA Ops", [_Event(name, s, e) for name, s, e in ops])])


def _profile(devices):
    host = _Plane("/host:CPU", [_Line("python", [
        _Event("bench.window", 0, 10_000), _Event("bench.step", 100, 9_000)])])
    return _Profile([host] + devices)


def test_module_time_is_per_device():
    # two executions of one module on two devices: the first is slower on
    # device 0, the second on device 1; a pass waits for its slowest device
    one = _device(0, [("jit_batched(3)", 1000, 5000), ("jit_batched(3)", 6000, 7000)],
                  [("%fusion.1 = f32[8]", 1000, 5000), ("%fusion.1 = f32[8]", 6000, 7000)])
    other = _device(1, [("jit_batched(7)", 1200, 3200), ("jit_batched(7)", 6000, 9000)],
                    [("%fusion.1 = f32[8]", 1200, 3200), ("%fusion.1 = f32[8]", 6000, 9000)])
    both = trace_reduce.reduce_profile(_profile([one, other]), ["delta"])
    alone = trace_reduce.reduce_profile(_profile([one]), ["delta"])
    assert both["num_devices"] == 2 and alone["num_devices"] == 1
    assert both["modules_s"]["jit_batched"] == pytest.approx((4000 + 3000) / 1e9)
    assert both["modules_n"] == {"jit_batched": 4}
    # an op's time is that of the device that spent most on it
    assert both["device_ops"] == [["fusion.1", pytest.approx((4000 + 1000) / 1e9)]]
    assert alone["modules_s"]["jit_batched"] == pytest.approx((4000 + 1000) / 1e9)
    assert alone["device_ops"] == [["fusion.1", pytest.approx((4000 + 1000) / 1e9)]]
    # the busy share is a mean over devices
    assert both["busy_s"] == pytest.approx(np.mean([5000, 5000]) / 1e9)


def test_traced_rehearsal_reads_the_span_metrics(monkeypatch):
    """The harness reduces its trace's spans before deleting it: a traced
    run on the CPU (a delta pass and quiet steps) prints every span metric
    of its cell, as the reduction reads it."""
    seen = []
    reduce_profile = span_reduce.reduce_profile
    monkeypatch.setattr(span_reduce, "reduce_profile",
                        lambda profile: seen.append(reduce_profile(profile)) or seen[-1])
    out = bench_run.run("regional.hotspot", 3_141_592_653, 6.0, True, rehearse=True,
                        sizes={"num_apps": 2000, "num_tiers": 16})
    assert out["correct"], out["checks"]
    want = {m["name"] for m in bench_run.metrics_for(SPEC, "regional.hotspot", True)
            if m["name"] in span_reduce.LAYER_NUMBERS}
    assert want == {"frontend.drain_ms", "frontend.scope_ms", "frontend.drift_ms",
                    "controller.evaluate_ms", "shard.host_ms", "shard.lane_idle_frac"}
    assert len(seen) == 1
    for name in want:
        assert out["metrics"][name]["value"] == seen[0]["layers"][name]
