"""Mean of the program's ``solve_s`` span of ``solve_fleet`` (ms) per delta
pass: the batched shard solve, dispatch to result."""

import numpy as np


def read(run):
    spans = [r["solve_s"] for r in run.steps
             if r["action"] == "delta" and r["ran"] and "solve_s" in r]
    return float(np.mean(spans) * 1e3) if spans else None
