"""The trace reduction on a small trace recorded once on a TPU v5e
(``record_trace.py``): three 512x512 steps between 10 ms sleeps."""

import pathlib
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import trace_reduce  # noqa: E402

TRACE = BENCH / "tests" / "data" / "small.xplane.pb"


@pytest.fixture(scope="module")
def reduced():
    return trace_reduce.reduce_file(TRACE, ["full", "delta", "noop"])


def test_window_and_busy_time(reduced):
    assert reduced["num_devices"] == 1
    assert reduced["window_s"] == pytest.approx(0.03432183)
    # The union of the device's op intervals inside the window: two of the
    # three launches (the first one's device timestamps fall 1.1 ms before
    # the window span opens on the host clock).
    assert reduced["busy_s"] == pytest.approx(3.54e-06)
    assert reduced["idle_frac"] == pytest.approx(1 - 3.54e-06 / 0.03432183)
    assert 0 < reduced["idle_frac"] < 1


def test_module_time_and_ops(reduced):
    assert reduced["modules_n"] == {"jit__lambda": 2}
    assert reduced["modules_s"]["jit__lambda"] == pytest.approx(3.552e-06)
    names = [n for n, _ in reduced["device_ops"]]
    assert names[0] == "fusion"
    assert all(" = " not in n for n in names)


def test_idle_gaps_are_cut_at_harness_spans(reduced):
    gaps = reduced["idle_gaps"]
    assert len(gaps) == 10
    # The three 10 ms sleeps, less the device work that falls inside them.
    assert [round(s, 6) for _, s in gaps[:3]] == [0.010985, 0.009546, 0.009155]
    assert [n for n, _ in gaps[:3]] == ["bench.sleep"] * 3
    durations = [s for _, s in gaps]
    assert durations == sorted(durations, reverse=True)
    # No piece is longer than the idle gap it came from.
    assert gaps[0][1] <= 0.012949101


def test_step_labels_follow_span_order():
    from jax.profiler import ProfileData

    profile = ProfileData.from_file(str(TRACE))
    out = trace_reduce.reduce_profile(profile, ["full", "delta", "noop"], top=50)
    labels = {label for label, _ in out["idle_gaps"]}
    assert {"bench.step.full", "bench.step.delta", "bench.step.noop"} <= labels
    assert labels <= {"bench.sleep", "bench.step.full", "bench.step.delta",
                      "bench.step.noop", "outside harness spans"}


def test_no_device_plane_gives_nothing():
    class Empty:
        planes = ()

    assert trace_reduce.reduce_profile(Empty()) is None
