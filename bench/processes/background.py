"""Background telemetry: every app reports once per ``report_period_s``.

The apps are dealt, in an order drawn once from the seed, into records of
``record_apps`` apps spread evenly over the period; each reading reports
the app's current true demand with the mix's jitter.
"""

import numpy as np

ORDER = 1  # readings: after any multiplier change due at the same instant


def timeline(b, spec: dict, seconds: float) -> list:
    N = b.fleet.num_apps
    K = int(spec["record_apps"])
    period = float(spec["report_period_s"])
    perm = b.rng.permutation(N)
    out = []
    for p in range(int(np.ceil(seconds / period))):
        for r in range(int(np.ceil(N / K))):
            t = p * period + r * K * period / N
            ids = perm[r * K:(r + 1) * K]
            out.append((t, lambda b, t=t, ids=ids: b.report(t, ids, len(ids))))
    return out
