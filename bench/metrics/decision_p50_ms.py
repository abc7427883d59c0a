"""Median decision latency (ms): due time -> end of the step that drained the event."""

import numpy as np


def read(run):
    lat = run.latencies_ms[~np.isnan(run.latencies_ms)]
    return float(np.percentile(lat, 50)) if lat.size else None
