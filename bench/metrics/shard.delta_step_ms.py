"""Mean host wall time (ms) of the steps that ran a delta pass (the batched
shard solve over the dirty shards)."""

import numpy as np


def read(run):
    walls = [r["t1"] - r["t0"] for r in run.steps
             if r["action"] == "delta" and r["ran"]]
    return float(np.mean(walls) * 1e3) if walls else None
