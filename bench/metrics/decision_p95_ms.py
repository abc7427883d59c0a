"""95th percentile decision latency (ms) over every event due in the window."""

import numpy as np


def read(run):
    lat = run.latencies_ms[~np.isnan(run.latencies_ms)]
    return float(np.percentile(lat, 95)) if lat.size else None
