"""A regional fleet whose tiers repeat the source paper's SLO table.

The source paper's section 4 set-up is five tiers with the SLO mapping
SLO1: tiers 1-3; SLO2: tiers 1-3; SLO3: tiers 1-5; SLO4: tiers 4-5.  Here
that five-tier pattern repeats around a ring of ``num_tiers`` tiers (tier
``t`` takes row ``t mod 5`` of ``slo_table``), and apps draw their SLO
class from ``slo_mix``.  The rest is the arithmetic of the repository's
vectorized fleet builder (``repro.shard.synthetic_fleet``), copied so that
a later change there cannot move this yardstick: lognormal cpu and memory
demand (the collected p99), Poisson tasks, tiers on contiguous arcs of 2-4
regions of a latency ring, each app's data region drawn from its home
tier's arc, capacity sized so that each tier's worst resource sits near
``util_target``, 40-120 hosts per tier.
"""

from __future__ import annotations

import numpy as np

NUM_RESOURCES = 2


def build(num_apps: int, num_tiers: int, num_regions: int, *, seed: int,
          slo_table, slo_mix, util_target: float, move_frac: float):
    from fleets import FleetArrays

    rng = np.random.default_rng(seed)
    N, T, G, R = int(num_apps), int(num_tiers), int(num_regions), NUM_RESOURCES
    pattern = np.asarray(slo_table, bool)
    slo_allowed = pattern[np.arange(T) % pattern.shape[0]]
    n_slo = slo_allowed.shape[1]
    demand = np.empty((N, R), np.float32)
    demand[:, 0] = rng.lognormal(1.2, 0.9, N)
    demand[:, 1] = rng.lognormal(1.8, 0.9, N)
    tasks = (1.0 + rng.poisson(6.0, N)).astype(np.float32)
    slo = rng.choice(n_slo, size=N, p=np.asarray(slo_mix, np.float64)).astype(np.int32)
    criticality = rng.beta(2.0, 5.0, N).astype(np.float32)
    assignment0 = np.zeros(N, np.int32)
    for c in range(n_slo):
        apps = np.where(slo == c)[0]
        ok = np.where(slo_allowed[:, c])[0]
        assignment0[apps] = rng.choice(ok, size=apps.size)
    tier_regions = np.zeros((T, G), bool)
    for t in range(T):
        start = int(round(t * G / T)) % G
        arc = int(rng.integers(2, min(4, G) + 1))
        tier_regions[t, (start + np.arange(arc)) % G] = True
    app_region = np.zeros(N, np.int32)
    for t in range(T):
        apps = np.where(assignment0 == t)[0]
        if apps.size:
            app_region[apps] = rng.choice(np.where(tier_regions[t])[0], size=apps.size)
    util = np.zeros((T, R), np.float64)
    np.add.at(util, assignment0, demand)
    tier_tasks = np.zeros(T, np.float64)
    np.add.at(tier_tasks, assignment0, tasks)
    capacity = np.maximum(util / util_target, demand.max() * 1.5).astype(np.float32)
    task_limit = np.maximum(tier_tasks / util_target, tasks.max() * 2).astype(np.float32)
    ring = np.abs(np.arange(G)[:, None] - np.arange(G)[None, :])
    ring = np.minimum(ring, G - ring)
    region_latency = (4.0 + 14.0 * ring + rng.uniform(0, 3, (G, G))).astype(np.float32)
    region_latency = ((region_latency + region_latency.T) / 2).astype(np.float32)
    np.fill_diagonal(region_latency, 0.0)
    hosts_per_tier = rng.integers(40, 120, T).astype(np.int32)
    host_capacity = (capacity.sum(axis=0) / hosts_per_tier.sum() * 1.6).astype(np.float32)
    return FleetArrays(demand, tasks, slo, criticality, assignment0, capacity,
                       task_limit, slo_allowed, app_region, tier_regions,
                       region_latency, hosts_per_tier, host_capacity,
                       move_frac=float(move_frac))
