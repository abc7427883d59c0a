"""Device time (ms) of the LocalSearch executables per solve: the global
solver (``_solve_local_jit``) and the batched shard solver (``batched``),
summed from the trace's XLA module events, over the passes that ran."""

SOLVER_MODULES = ("jit__solve_local_jit", "jit_batched")


def read(run):
    if run.trace is None:
        return None
    solves = sum(1 for r in run.steps if r["ran"])
    mods = run.trace["modules_s"]
    found = [v for k, v in mods.items() if k in SOLVER_MODULES]
    if not solves or not found:
        return None
    return sum(found) * 1e3 / solves
