"""The shadow's per-tier sums: one pass per step, bit for bit the scatter-add.

``FleetShadow`` derives tier loads, ``d2b`` and ``over_ideal`` (the drift
detector's inputs) and the arrival placement from one pass of
``np.bincount`` over the live apps.  The oracle here is the scatter-add
form (``np.add.at``, one per quantity), kept as the reference: every
quantity must equal it exactly, not approximately, because ``d2b`` and
``over_ideal`` feed the drift gates.
"""

import numpy as np
import pytest

from repro.core import generate_cluster
from repro.core.hierarchy import RegionScheduler
from repro.service import (NOOP, AppArrival, AppDeparture, CapacityUpdate,
                           FleetShadow, ServiceLoop, TelemetryDelta)


# -- the scatter-add oracle ---------------------------------------------------

def _oracle_sums(sh, live):
    util = np.zeros_like(sh._capacity, np.float64)
    tsk = np.zeros(sh._capacity.shape[0], np.float64)
    np.add.at(util, sh._x0[live], sh._demand[live])
    np.add.at(tsk, sh._x0[live], sh._tasks[live])
    return util, tsk


def _oracle_tier_loads(sh):
    util, _ = _oracle_sums(sh, sh._valid)
    return (util / np.maximum(sh._capacity, 1e-9)).max(axis=1)


def _oracle_over_ideal(sh):
    cap = np.maximum(sh._capacity, 1e-9)
    lim = np.maximum(sh._task_limit, 1e-9)
    util, tsk = _oracle_sums(sh, sh._valid)
    over = float((util / cap - sh._ideal).max())
    return max(over, float((tsk / lim - sh._ideal_t).max()))


def _oracle_d2b(sh):
    live = sh._valid
    cap = np.maximum(sh._capacity, 1e-9)
    lim = np.maximum(sh._task_limit, 1e-9)
    util, tsk = _oracle_sums(sh, live)
    util_frac = util / cap
    task_frac = tsk / lim
    total_frac = sh._demand[live].sum(axis=0) / cap.sum(axis=0)
    total_task = sh._tasks[live].sum() / lim.sum()
    worst = float(np.abs(util_frac - total_frac[None, :]).max())
    return max(worst, float(np.abs(task_frac - total_task).max()))


def _oracle_place(sh, n):
    T = sh._capacity.shape[0]
    live = sh._valid.copy()
    live[n] = False
    util, tsk = _oracle_sums(sh, live)
    ok = sh._slo_allowed[:, sh._slo[n]]
    region_ok = RegionScheduler(sh.view()).feasibility_matrix()[n]
    if (ok & region_ok).any():
        ok = ok & region_ok
    if not ok.any():
        ok = np.ones(T, bool)
    frac = np.maximum(
        ((util + sh._demand[n]) / np.maximum(sh._capacity, 1e-9)).max(axis=1),
        (tsk + sh._tasks[n]) / np.maximum(sh._task_limit, 1e-9),
    )
    return int(np.argmin(np.where(ok, frac, np.inf)))


def _assert_exact(sh):
    loads = sh.tier_loads()
    assert loads.dtype == np.float64
    assert np.array_equal(loads, _oracle_tier_loads(sh))
    assert sh.d2b() == _oracle_d2b(sh)
    assert sh.over_ideal() == _oracle_over_ideal(sh)
    c_loads, c_d2b, c_over = sh.drift_inputs()
    assert np.array_equal(c_loads, loads)
    assert c_d2b == sh.d2b() and c_over == sh.over_ideal()


def _shadow(seed, tiers, num_apps=300):
    return FleetShadow(generate_cluster(num_apps=num_apps, num_tiers=tiers,
                                        seed=seed))


# -- bit identity ---------------------------------------------------------------

@pytest.mark.parametrize("tiers", [5, 64])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_sums_bit_identical_to_scatter_add(seed, tiers):
    sh = _shadow(seed, tiers)
    rng = np.random.default_rng(seed + 100)
    _assert_exact(sh)
    seq = 0
    # Departures leave invalid rows (their x0 still points at a tier).
    gone = rng.choice(np.flatnonzero(sh._valid), size=30, replace=False)
    for n in gone:
        sh.apply(AppDeparture(app_id=int(n)), seq=seq)
        seq += 1
    _assert_exact(sh)
    # Telemetry over a live subset.
    ids = rng.choice(np.flatnonzero(sh._valid), size=60, replace=False)
    dem = sh._demand[ids] * rng.uniform(0.5, 2.0, (ids.size, 1))
    sh.apply(TelemetryDelta(app_ids=tuple(int(i) for i in ids),
                            demand=dem.astype(np.float32),
                            tasks=sh._tasks[ids] + 1.0, collected_at=1),
             seq=seq)
    seq += 1
    _assert_exact(sh)
    # Arrivals into the departed slots, placed by the shadow's greedy rule;
    # every chosen tier equals the oracle's on the state _place saw.
    for n in gone[:10]:
        old_tier = sh._x0[n]
        sh.apply(AppArrival(app_id=int(n),
                            demand=rng.uniform(0.001, 0.05, 2),
                            tasks=float(rng.integers(1, 20)),
                            slo=int(rng.integers(0, 4)), tier=-1), seq=seq)
        seq += 1
        chosen = sh._x0[n]
        sh._x0[n] = old_tier
        assert chosen == _oracle_place(sh, n)
        sh._x0[n] = chosen
        _assert_exact(sh)
    # A capacity update moves the denominators, not the sums.
    sh.apply(CapacityUpdate(capacity=sh._capacity * 0.85,
                            task_limit=sh._task_limit * 1.1), seq=seq)
    _assert_exact(sh)
    # A new incumbent moves the sums.
    x = sh._x0.copy()
    x[rng.choice(x.size, 40, replace=False)] = rng.integers(0, tiers, 40)
    sh.adopt_assignment(x)
    _assert_exact(sh)


def test_sums_of_an_empty_fleet_match():
    sh = _shadow(0, 5, num_apps=20)
    for n in np.flatnonzero(sh._valid):
        sh.apply(AppDeparture(app_id=int(n)), seq=int(n))
    assert not sh._valid.any()
    _assert_exact(sh)


# -- one pass per step ----------------------------------------------------------

def test_loop_computes_the_sums_once_per_quiet_step_twice_per_solve(
        monkeypatch):
    loop = ServiceLoop(generate_cluster(num_apps=64, seed=0))
    calls = []
    sums = loop.shadow._tier_sums

    def counted(*args, **kwargs):
        calls.append(1)
        return sums(*args, **kwargs)

    monkeypatch.setattr(loop.shadow, "_tier_sums", counted)
    # The generated fleet starts imbalanced: the first step applies a full
    # pass (drift, then commit after the new incumbent is adopted).
    first = loop.step(0)
    assert first.applied
    assert len(calls) == 2
    for t in range(1, 4):
        calls.clear()
        out = loop.step(t)
        assert out.action == NOOP, out.reason
        assert len(calls) == 1
