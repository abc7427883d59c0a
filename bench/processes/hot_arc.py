"""A hot arc of tiers that moves around the tier ring.

Every ``move_every_s`` the arc (the tiers within ``T // halfwidth_div`` of
a centre drawn on the ring) takes a rise: ``app_frac`` of the apps, drawn
by the fleet as built with Zipf ``zipf_s`` weights by ring distance, are
scaled so that the centre tier's load rises by ``rise`` of its capacity.
When the arc moves on they return to base.  The apps touched report at
once, in records of ``record_apps``.
"""

import numpy as np

ORDER = 0  # multiplier changes: before the readings due at the same instant


def timeline(b, spec: dict, seconds: float) -> list:
    f = b.fleet
    T, N, x0 = f.num_tiers, f.num_apps, f.assignment0
    util = np.zeros((T, f.capacity.shape[1]))
    np.add.at(util, x0, f.demand.astype(np.float64))
    half = max(1, T // int(spec["halfwidth_div"]))
    k = max(1, int(spec["app_frac"] * N))
    steps = []
    for i in range(int(np.ceil(seconds / spec["move_every_s"]))):
        centre = int(b.rng.integers(T))
        dist = np.abs(np.arange(T) - centre)
        dist = np.minimum(dist, T - dist)
        weight = np.where(dist < half, 1.0 / (1.0 + dist) ** spec["zipf_s"], 0.0)
        apps = b.zipf_draw(weight[x0], k)
        r = int(np.argmax(util[centre] / f.capacity[centre]))
        on_centre = apps[x0[apps] == centre]
        share = f.demand[on_centre, r].sum() / f.capacity[centre, r]
        factor = float(np.clip(1.0 + spec["rise"] / max(share, 1e-9), 1.05, 3.0))
        steps.append((i * spec["move_every_s"], apps, factor))
    return b.step_changes(id(spec), steps, int(spec["record_apps"]))
