"""Mean of the program's ``CoopTimings.solve_s`` span (ms) per full pass:
the global LocalSearch solves inside the cooperation bus."""

import numpy as np


def read(run):
    spans = [r["coop_solve_s"] for r in run.steps
             if r["action"] == "full" and r["ran"] and "coop_solve_s" in r]
    return float(np.mean(spans) * 1e3) if spans else None
