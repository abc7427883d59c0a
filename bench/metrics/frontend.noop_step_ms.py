"""Mean host wall time (ms) of the steps whose drift decision was noop:
the service frontend alone (drain, shadow, drift decision)."""

import numpy as np


def read(run):
    walls = [r["t1"] - r["t0"] for r in run.steps if r["action"] == "noop"]
    return float(np.mean(walls) * 1e3) if walls else None
