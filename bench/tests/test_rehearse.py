"""CPU rehearsals of the benchmark's cells at a small size.

Each runs ``bench/run.py``'s whole path in this process, with the look for a
chip skipped (``rehearse=True``): fleet and schedule from the seed, warm-up,
an open-loop window, the f64 reference check.  ``test_faults`` and
``test_bus_levels`` break the timed path underneath and see ``correct``
come out false; ``test_control`` puts the reference's bfloat16 objective in
the device's place and sees the objective comparison fail.  A run on the
CPU prints no device metric.
"""

import copy
import dataclasses
import json
import pathlib
import sys

import numpy as np
import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import run as bench_run  # noqa: E402

SMALL = {"regional.hotspot": {"num_apps": 3000, "num_tiers": 16},
         "regional.surge": {"num_apps": 3000, "num_tiers": 16}}
SEED = 3_141_592_653   # above 2**31


def rehearse(cell, seconds=6.0, trace=False, control=False, seed=SEED, traffic=None):
    return bench_run.run(cell, seed, seconds, trace, rehearse=True, control=control,
                         sizes=SMALL[cell], traffic=traffic)


def _cells():
    spec = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    return [c["name"] for c in spec["workloads"]]


@pytest.mark.parametrize("cell", _cells())
def test_cell_rehearses_correct(cell):
    out = rehearse(cell)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert out["device"]["platform"] == "cpu"
    assert list(out)[-1] == "checks"
    names = {m["name"] for m in bench_run.metrics_for(
        json.loads((BENCH.parent / "BENCHMARK.json").read_text()), cell, False)}
    assert set(out["metrics"]) == names
    for m in out["metrics"].values():
        assert m["value"] > 0


def test_traced_run_prints_no_device_metric_on_cpu():
    out = rehearse("regional.hotspot", trace=True)
    assert out["correct"]
    assert not any(k.startswith("device.") for k in out["metrics"])
    assert "busy_s" not in out["device"]
    assert out["metrics"]["jit.compiles_in_window"]["unit"] == "count"


def test_schedule_is_fixed_by_the_seed():
    import fleets
    import schedule

    spec, cell, config, traffic = bench_run.load_cell("regional.hotspot")
    fleet = fleets.build(dict(config, args=dict(config["args"], **SMALL["regional.hotspot"])), SEED)
    a = schedule.build(fleet, traffic, SEED, 20.0)
    b = schedule.build(fleet, traffic, SEED, 20.0)
    c = schedule.build(fleet, traffic, SEED + 1, 20.0)
    assert np.array_equal(a.due, b.due)
    assert all(np.array_equal(x["demand"], y["demand"]) for x, y in zip(a.payloads, b.payloads))
    # Another seed: the same number of events of the same sizes.
    assert [x["app_ids"].size for x in a.payloads] == [x["app_ids"].size for x in c.payloads]


def test_processes_compose():
    """A traffic file with both a hot arc and surges keeps both: each
    process's changes fall due, and an app's multiplier is the product of
    the processes'."""
    import fleets
    import schedule

    _, _, config, hot = bench_run.load_cell("regional.hotspot")
    _, _, _, surge = bench_run.load_cell("regional.surge")
    fleet = fleets.build(dict(config, args=dict(config["args"], **SMALL["regional.hotspot"])), SEED)
    procs = {p["kind"]: p for p in hot["processes"] + surge["processes"]}
    both = dict(hot, processes=[procs["hot_arc"], procs["surge"], procs["background"]])
    sched = schedule.build(fleet, both, SEED, 40.0)
    arc_s, surge_s = procs["hot_arc"]["move_every_s"], procs["surge"]["every_s"]
    changes = set(np.arange(0.0, 40.0, arc_s)) | set(np.arange(0.0, 40.0, surge_s))
    assert changes <= set(sched.due.tolist())
    b = schedule.Builder(fleet, SEED, 0.0)
    b.set_factor("a", np.array([1, 2]), 2.0)
    b.set_factor("b", np.array([2, 3]), 3.0)
    assert b.mult[[0, 1, 2, 3]].tolist() == [1.0, 2.0, 6.0, 3.0]


def test_no_chip_is_refused():
    with pytest.raises(bench_run.NoChip):
        bench_run.run("regional.hotspot", SEED, 1.0, False)


# -- faults planted under the timed path ------------------------------------

def _shadow_unchanged(monkeypatch):
    from repro.service.shadow import FleetShadow

    monkeypatch.setattr(FleetShadow, "_apply_telemetry", lambda self, ev, seq: None)


def _half_the_batch(monkeypatch):
    from repro.service.shadow import FleetShadow

    original = FleetShadow._apply_telemetry

    def half(self, ev, seq):
        k = len(ev.app_ids) // 2
        original(self, dataclasses.replace(ev, app_ids=ev.app_ids[:k],
                                           demand=ev.demand[:k], tasks=ev.tasks[:k]), seq)
    monkeypatch.setattr(FleetShadow, "_apply_telemetry", half)


def _answer_altered(monkeypatch):
    import jax.numpy as jnp
    from repro.core.controller import BalanceController

    original = BalanceController._actuate_phase

    def altered(self, inp, plan):
        res = original(self, inp, plan)
        if res.applied:
            p = self.cluster.problem
            x = np.asarray(p.assignment0).copy()
            x[:: max(1, x.size // 50)] = (x[:: max(1, x.size // 50)] + 1) % p.num_tiers
            self.cluster = dataclasses.replace(
                self.cluster, problem=p.with_assignment0(jnp.asarray(x)))
        return res
    monkeypatch.setattr(BalanceController, "_actuate_phase", altered)


@pytest.mark.parametrize("fault", [_shadow_unchanged, _half_the_batch, _answer_altered],
                         ids=["state_unchanged", "half_batch", "answer_altered"])
def test_faults(monkeypatch, fault):
    fault(monkeypatch)
    out = rehearse("regional.hotspot", seconds=8.0)
    assert not out["correct"], out["checks"]


# -- the cooperation bus's lower levels bypassed ------------------------------

def _strong_surges():
    """Surges strong enough that a small fleet runs full passes of the bus."""
    _, _, _, traffic = bench_run.load_cell("regional.surge")
    traffic = copy.deepcopy(traffic)
    traffic["processes"][0].update(factor=3.0, app_frac=0.15, every_s=4.0)
    traffic["warm_up"]["full_at_s"] = [1.0, 5.0]
    return traffic


def _region_bypassed(monkeypatch):
    from repro.core.hierarchy import RegionScheduler

    monkeypatch.setattr(RegionScheduler, "premask", lambda self, problem: np.zeros(
        (problem.num_apps, problem.num_tiers), bool))
    monkeypatch.setattr(RegionScheduler, "vet", lambda self, proposal: np.zeros(0, np.int64))


def _host_rejections_ignored(monkeypatch):
    from repro.core.hierarchy import HostScheduler

    original = HostScheduler.vet

    def ignored(self, proposal):
        original(self, proposal)          # packs, as the level does, then accepts all
        return np.zeros(0, np.int64)
    monkeypatch.setattr(HostScheduler, "vet", ignored)


@pytest.mark.parametrize("fault", [None, _region_bypassed, _host_rejections_ignored],
                         ids=["sound", "region_bypassed", "host_rejections_ignored"])
def test_bus_levels(monkeypatch, fault):
    if fault is not None:
        fault(monkeypatch)
    out = rehearse("regional.surge", seconds=8.0, traffic=_strong_surges())
    assert out["checks"]["applied_passes"]["value"] >= 1
    if fault is None:
        assert out["correct"], out["checks"]
    else:
        assert not out["correct"] and out["checks"]["rule_breaks"]["value"] > 0, out["checks"]


def test_control():
    out = rehearse("regional.hotspot", seconds=8.0, control=True)
    assert not out["correct"]
    gap = out["checks"]["obj_gap"]["value"]
    assert gap > bench_run.OBJ_GAP_LIMIT
