"""Continuous controller: trigger and apply, cooldown and hysteresis,
dry run."""
import numpy as np

from repro.core import generate_cluster
from repro.core.controller import (BalanceController, ControllerConfig,
                                   TickInput)


# ---------------------------------------------------------------------------
# continuous controller
# ---------------------------------------------------------------------------

def test_controller_triggers_and_applies():
    cluster = generate_cluster(num_apps=200, seed=5)
    ctl = BalanceController(cluster, ControllerConfig(cooldown_rounds=2))
    ev = ctl.step(TickInput()).event
    assert ev.triggered                      # tier 3 is hot by construction
    assert ev.applied
    assert ev.d2b_after < ev.d2b_before


def test_controller_cooldown_and_hysteresis():
    cluster = generate_cluster(num_apps=200, seed=5)
    ctl = BalanceController(cluster, ControllerConfig(cooldown_rounds=5))
    ev1 = ctl.step(TickInput()).event
    assert ev1.applied
    ev2 = ctl.step(TickInput()).event                         # inside cooldown
    assert not ev2.triggered and "cooldown" in ev2.reason
    audit = ctl.audit()
    assert audit["rebalances"] == 1
    assert audit["mean_improvement"] > 0


def test_controller_dry_run_does_not_mutate():
    cluster = generate_cluster(num_apps=150, seed=6)
    before = np.asarray(cluster.problem.assignment0).copy()
    ctl = BalanceController(cluster,
                            ControllerConfig(dry_run=True))
    ev = ctl.step(TickInput()).event
    assert ev.triggered and not ev.applied
    np.testing.assert_array_equal(
        np.asarray(ctl.cluster.problem.assignment0), before)
