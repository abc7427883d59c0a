"""Mean host wall time (ms) of the steps that ran a full cooperation pass."""

import numpy as np


def read(run):
    walls = [r["t1"] - r["t0"] for r in run.steps
             if r["action"] == "full" and r["ran"]]
    return float(np.mean(walls) * 1e3) if walls else None
