"""Fleet-wide surges on Zipf-ranked tiers.

Every ``every_s``, ``app_frac`` of the apps, drawn by the fleet as built
with Zipf ``zipf_s`` weights over a ranking of the tiers (a new random
ranking each time), take ``factor`` times their demand, and the previous
surge's apps return to base.  The apps touched report at once, in records
of ``record_apps``.
"""

import numpy as np

ORDER = 0  # multiplier changes: before the readings due at the same instant


def timeline(b, spec: dict, seconds: float) -> list:
    f = b.fleet
    T, N, x0 = f.num_tiers, f.num_apps, f.assignment0
    k = max(1, int(spec["app_frac"] * N))
    steps = []
    for i in range(int(np.ceil(seconds / spec["every_s"]))):
        rank = np.empty(T)
        rank[b.rng.permutation(T)] = np.arange(T)
        weight = 1.0 / (1.0 + rank) ** spec["zipf_s"]
        steps.append((i * spec["every_s"], b.zipf_draw(weight[x0], k), float(spec["factor"])))
    return b.step_changes(id(spec), steps, int(spec["record_apps"]))
