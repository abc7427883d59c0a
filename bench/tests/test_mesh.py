"""A cell of several chips and a sharded controller, rehearsed on the CPU.

The four-device rehearsal runs in a child process whose CPU backend has
four virtual devices (``--xla_force_host_platform_device_count=4``): a cell
of ``chips: 4`` with ``controller_args {"shards": 8}`` runs under a mesh
over the four with nothing compiled inside its window, every batched shard
solve's result is split over all four devices, a quarter of its rows on
each, the warm-up and a delta pass from its placement are the same bit for
bit with and without the mesh, and the same cell on one chip is correct
too.

In this process, on one device: a configuration that names ``shards`` has
every full pass judged as a sharded pass that solved every shard, so a
full pass that solved fewer, or a pass of the bus, fails ``correct``; such
a configuration may not state a region latency budget, which a sharded
pass does not keep; and ``controller_args`` takes only scalar fields of
``ControllerConfig``.

These cases lie outside tier-1's ``testpaths``: run them with
``python -m pytest bench/tests``.
"""

import copy
import dataclasses
import json
import os
import pathlib
import subprocess
import sys
import textwrap

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import run as bench_run  # noqa: E402

SIZES = {"num_apps": 2000, "num_tiers": 16}
SEED = 3_141_592_653   # above 2**31
SHARDS = {"shards": 8}


def _sharded(config):
    """``config`` with sharded full passes: ``controller_args`` names the
    shards and, as a sharded pass runs no region level, no region budget."""
    out = {k: v for k, v in config.items() if k != "region_latency_budget_ms"}
    return dict(out, controller_args=dict(SHARDS))


def _sharded_cell(chips):
    _, cell, config, _ = bench_run.load_cell("regional.hotspot")
    return dict(cell, chips=chips), _sharded(config)


FOUR_DEVICES = textwrap.dedent("""
    import dataclasses, json, sys
    import numpy as np
    import jax
    sys.path.insert(0, {bench!r})
    sys.path.insert(0, {src!r})
    import run as bench_run
    import fleets, schedule
    import repro.shard.solve as shard_solve
    from repro.core.controller import BalanceController, TickInput
    from repro.service import ServiceConfig

    solve_layouts = []      # (devices, replicated, rows, rows on each device) per solve
    original = shard_solve._batched_solver

    def recorded(config):
        fn = original(config)

        def solve(*a):
            out = fn(*a)
            x = out[0]
            solve_layouts.append((len(x.sharding.device_set), x.sharding.is_fully_replicated,
                                  x.shape[0], sorted(s.data.shape[0] for s in x.addressable_shards)))
            return out
        return solve
    shard_solve._batched_solver = recorded
    compiles_in_window = []
    summary = bench_run._summary

    def recorded_summary(data, *a):
        compiles_in_window.append(data.compiles_in_window)
        summary(data, *a)
    bench_run._summary = recorded_summary

    sizes, seed, args = {sizes!r}, {seed!r}, {args!r}
    _, cell, config, traffic = bench_run.load_cell("regional.hotspot")
    config = dict({{k: v for k, v in config.items() if k != "region_latency_budget_ms"}},
                  controller_args=args)
    four = bench_run.run("regional.hotspot", seed, 6.0, False, rehearse=True, sizes=sizes,
                         cell=dict(cell, chips=4), config=config)
    n_four = len(solve_layouts)
    one = bench_run.run("regional.hotspot", seed, 6.0, False, rehearse=True, sizes=sizes,
                        cell=dict(cell, chips=1), config=config)

    # The warm-up's start, and one delta pass from it on the demand the
    # schedule reports by 6 s, with and without the mesh.
    fleet = fleets.build(dict(config, args=dict(config["args"], **sizes)), seed)
    sched = schedule.build(fleet, traffic, seed, 6.0)

    service = ServiceConfig(num_shards=config["service"]["num_shards"])
    cfg = bench_run.controller_config(args)

    def warm_and_delta():
        loop, _ = bench_run.warm_up(fleet, sched, traffic["warm_up"], cfg, service)
        start = loop.controller.cluster
        ctl = BalanceController(start, dataclasses.replace(cfg, trigger_d2b=-1.0,
                                                           cooldown_rounds=0))
        drifted = bench_run._reported_by(fleet, sched, start, 6.0)
        ctl.step(TickInput(cluster=drifted, now=1, dirty_shards=tuple(range(loop.num_shards)),
                           num_shards=loop.num_shards))
        return (np.asarray(start.problem.assignment0),
                np.asarray(ctl.cluster.problem.assignment0))

    off = warm_and_delta()
    with jax.set_mesh(jax.make_mesh((4,), ("data",))):
        on = warm_and_delta()
    print(json.dumps({{
        "four": four, "one": one, "solve_layouts": solve_layouts[:n_four],
        "compiles_in_window": compiles_in_window,
        "start_equal": bool(np.array_equal(off[0], on[0])),
        "delta_equal": bool(np.array_equal(off[1], on[1])),
        "delta_moved": int(np.sum(off[1] != off[0])),
    }}))
""")


@pytest.fixture(scope="module")
def four_devices():
    prog = FOUR_DEVICES.format(bench=str(BENCH), src=str(BENCH.parent / "src"),
                               sizes=SIZES, seed=SEED, args=SHARDS)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    done = subprocess.run([sys.executable, "-c", prog], capture_output=True, text=True,
                          env=env, timeout=900)
    assert done.returncode == 0, done.stderr[-4000:]
    return json.loads(done.stdout.strip().splitlines()[-1])


def test_four_chip_cell_runs_under_a_mesh_and_is_correct(four_devices):
    out = four_devices["four"]
    assert out["correct"], out["checks"]
    assert out["device"]["chips"] == 4 and out["device"]["count"] == 4
    assert out["checks"]["applied_passes"]["value"] >= 1
    # the warm-up compiled, under the mesh, everything the window runs
    assert four_devices["compiles_in_window"][0] == 0


def test_sharded_results_span_every_device(four_devices):
    # every batched shard solve of the four-chip run (warm-up and window)
    # left its result split over all four devices, a quarter of the shards
    # on each: not replicated, every chip solving every shard
    layouts = four_devices["solve_layouts"]
    assert layouts
    for devices, replicated, rows, per_device in layouts:
        assert devices == 4 and not replicated
        assert per_device == [rows // 4] * 4 and rows % 4 == 0


def test_warm_up_and_a_delta_pass_do_not_depend_on_the_mesh(four_devices):
    assert four_devices["start_equal"]
    assert four_devices["delta_equal"]
    assert four_devices["delta_moved"] > 0


def test_same_cell_on_one_chip_is_correct(four_devices):
    out = four_devices["one"]
    assert out["correct"], out["checks"]
    assert out["device"]["chips"] == 1


# -- the full-pass rule by engine, on one device ----------------------------

def _strong_surges():
    """Surges strong enough that a small fleet runs full passes."""
    _, _, _, traffic = bench_run.load_cell("regional.surge")
    traffic = copy.deepcopy(traffic)
    traffic["processes"][0].update(factor=3.0, app_frac=0.15, every_s=4.0)
    traffic["warm_up"]["full_at_s"] = [1.0, 5.0]
    return traffic


def _fewer_shards(monkeypatch):
    """A full pass solves only shard 0."""
    import repro.shard.fleet as shard_fleet

    original = shard_fleet.solve_fleet

    def fewer(cluster, cfg, *a, dirty_shards=None, **k):
        return original(cluster, cfg, *a, dirty_shards=(0,) if dirty_shards is None
                        else dirty_shards, **k)
    monkeypatch.setattr(shard_fleet, "solve_fleet", fewer)


def _bus_instead(monkeypatch):
    """The controller ignores the configured shards: full passes run the bus."""
    from repro.core.controller import BalanceController

    original = BalanceController.__init__

    def bus(self, cluster, config=None, *a, **k):
        original(self, cluster, dataclasses.replace(config, shards=None), *a, **k)
    monkeypatch.setattr(BalanceController, "__init__", bus)


@pytest.mark.parametrize("fault", [None, _fewer_shards, _bus_instead],
                         ids=["sound", "fewer_shards", "bus_pass"])
def test_full_pass_is_judged_by_the_configured_engine(monkeypatch, fault):
    if fault is not None:
        fault(monkeypatch)
    seen = []
    original = bench_run.check

    def check(fleet, config, sched, steps, control):
        seen.extend(steps)
        return original(fleet, config, sched, steps, control)
    monkeypatch.setattr(bench_run, "check", check)
    cell, config = _sharded_cell(1)
    out = bench_run.run("regional.hotspot", SEED, 8.0, False, rehearse=True, sizes=SIZES,
                        traffic=_strong_surges(), cell=cell, config=config)
    full = [r for r in seen if r["applied"] and r["action"] == "full" and not r["delta"]]
    assert full, "no full pass applied in the window"
    if fault is None:
        assert out["correct"], out["checks"]
        assert all(r["solved_shards"] == r["num_shards"] and "levels" not in r for r in full)
    else:
        assert not out["correct"] and out["checks"]["rule_breaks"]["value"] > 0, out["checks"]


@pytest.mark.parametrize("args", [{"shard": 8}, {"coop": None}, {"shards": "8"},
                                  {"shards": True}, {"trigger_d2b": [0.1]}])
def test_controller_args_take_only_scalar_fields(args):
    with pytest.raises(ValueError):
        bench_run.controller_config(args)


def test_controller_args_set_the_controller():
    cfg = bench_run.controller_config({"shards": 8, "trigger_d2b": 1, "cooldown_rounds": 0})
    assert (cfg.shards, cfg.trigger_d2b, cfg.cooldown_rounds) == (8, 1, 0)
    assert bench_run.controller_config({"movement_cost_budget": None}).movement_cost_budget is None


def test_a_sharded_configuration_states_no_region_budget():
    # a sharded full pass runs no region or host level: a configuration
    # that names shards and states a region budget claims what it lacks
    _, cell, config, _ = bench_run.load_cell("regional.hotspot")
    assert bench_run.sharded_full_passes(_sharded(config))
    assert not bench_run.sharded_full_passes(config)
    claims = dict(config, controller_args=dict(SHARDS))
    with pytest.raises(ValueError, match="region_latency_budget_ms"):
        bench_run.sharded_full_passes(claims)
    with pytest.raises(ValueError, match="region_latency_budget_ms"):
        bench_run.run("regional.hotspot", SEED, 1.0, False, rehearse=True, sizes=SIZES,
                      cell=cell, config=claims)
