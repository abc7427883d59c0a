"""Fleets: the data a configuration file names, made from the seed.

A configuration file (``bench/configs/<name>.json``) names its builder
under ``"builder"`` (a module ``bench/builders/<builder>.py`` with a
``build(**args, seed)`` function), the builder's keyword arguments under
``"args"`` and the seed that fixes the fleet under ``"fleet_seed"``.

A builder returns ``FleetArrays``, plain numpy arrays of one fleet, so the
reference (``bench/reference.py``) works from data the program never
touched.  ``to_cluster`` wraps the arrays in the program's own
``ClusterState``, which is what the system under test is handed.
"""

from __future__ import annotations

import dataclasses

import numpy as np

import plugins


@dataclasses.dataclass
class FleetArrays:
    demand: np.ndarray        # f32[N, R]
    tasks: np.ndarray         # f32[N]
    slo: np.ndarray           # i32[N]
    criticality: np.ndarray   # f32[N]
    assignment0: np.ndarray   # i32[N]
    capacity: np.ndarray      # f32[T, R]
    task_limit: np.ndarray    # f32[T]
    slo_allowed: np.ndarray   # bool[T, S]
    app_region: np.ndarray    # i32[N]
    tier_regions: np.ndarray  # bool[T, G]
    region_latency: np.ndarray  # f32[G, G]
    hosts_per_tier: np.ndarray  # i32[T]
    host_capacity: np.ndarray   # f32[R]
    move_frac: float = 0.10
    ideal_frac: float = 0.70
    ideal_task_frac: float = 0.80

    @property
    def num_apps(self) -> int:
        return int(self.demand.shape[0])

    @property
    def num_tiers(self) -> int:
        return int(self.capacity.shape[0])


APP_FIELDS = ("demand", "tasks", "slo", "criticality", "assignment0", "app_region")


def build(config: dict, seed: int) -> FleetArrays:
    """The fleet a configuration file describes, for run seed ``seed``.

    The configuration's ``fleet_seed`` fixes the fleet: its tiers, and the
    set of apps with their sizes, SLO classes and placements.  ``seed``
    permutes the app rows, so every run seed gets the same set of apps in
    another order (and its own traffic, ``bench/schedule.py``): the work is
    the same from seed to seed, the inputs are not."""
    builder = plugins.load("builders", config["builder"])
    fleet = builder.build(**config["args"], seed=int(config["fleet_seed"]))
    perm = np.random.default_rng(np.random.SeedSequence([int(seed), 7])).permutation(
        fleet.num_apps)
    return dataclasses.replace(fleet, **{f: getattr(fleet, f)[perm] for f in APP_FIELDS})


def to_cluster(fleet: FleetArrays):
    """The program's ``ClusterState`` over copies of the arrays."""
    from repro.core.problem import make_problem
    from repro.core.telemetry import ClusterState

    problem = make_problem(
        demand=fleet.demand.copy(), tasks=fleet.tasks.copy(), slo=fleet.slo.copy(),
        criticality=fleet.criticality.copy(), assignment0=fleet.assignment0.copy(),
        capacity=fleet.capacity.copy(), task_limit=fleet.task_limit.copy(),
        slo_allowed=fleet.slo_allowed.copy(), ideal_frac=fleet.ideal_frac,
        ideal_task_frac=fleet.ideal_task_frac, move_frac=fleet.move_frac)
    N, T = fleet.num_apps, fleet.num_tiers
    return ClusterState(
        problem=problem,
        app_names=[f"app_{i:07d}" for i in range(N)],
        tier_names=[f"tier_{t + 1}" for t in range(T)],
        app_region=fleet.app_region.copy(),
        tier_regions=fleet.tier_regions.copy(),
        region_latency=fleet.region_latency.copy(),
        hosts_per_tier=fleet.hosts_per_tier.copy(),
        host_capacity=fleet.host_capacity.copy(),
    )
