"""The LocalSearch sweep reads each app's current-tier rows without a gather.

``move_delta_cost`` looks up the source tier's capacity, load fraction and
ideal share with ``delta.at_tier``, a select over the tier axis, so the
``while`` body of the solver holds no gather with an app-sized result
(inside the loop XLA neither fuses nor hoists one).  These tests pin:

* the sweep's values, bit for bit, against the same sweep with gathers;
* the trajectories of the global and the batched shard solve, recorded
  before the change (assignment digest, sweeps, committed moves, objective);
* the structure: the lowered ``while`` body has no app-axis gather.
"""

import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import LocalSearchConfig, solve_local
from repro.core import delta
from repro.core.problem import pad_problem, tier_loads
from repro.core.solver_local import _solve_local_jit, _weights_vector
from repro.shard import partition_problem, plan_shards, solve_shards, synthetic_fleet
from repro.shard.solve import ShardSolveConfig, _batched_solver


def _bits(a):
    """Exact bit pattern, so -0.0 != +0.0 and NaN == NaN."""
    return np.asarray(a, np.float32).view(np.uint32)


def _digest(x):
    return hashlib.sha256(np.asarray(x, np.int32).tobytes()).hexdigest()[:16]


def _sweep_args(T, seed):
    """A padded problem whose current assignment puts apps on every tier."""
    p = pad_problem(synthetic_fleet(200, T, seed=seed).problem, 256)
    rng = np.random.default_rng(seed)
    x = rng.permutation(np.arange(200) % T)
    x = jnp.asarray(np.concatenate([x, np.zeros(56, np.int64)]), jnp.int32)
    util, tasks = tier_loads(p, x)
    return (p.demand, p.tasks, p.criticality, x, p.assignment0, p.capacity,
            p.task_limit, p.ideal_frac, p.ideal_task_frac, util, tasks,
            _weights_vector(p))


@pytest.mark.parametrize("jit", [False, True], ids=["eager", "jit"])
@pytest.mark.parametrize("T", [8, 64])
def test_move_delta_cost_bitwise_equals_gather_reference(monkeypatch, T, jit):
    args = _sweep_args(T, seed=T)
    assert np.all(np.bincount(np.asarray(args[3])[:200], minlength=T) > 0)

    def run():
        fn = lambda *a: delta.move_delta_cost(*a)  # noqa: E731  fresh trace
        return np.asarray((jax.jit(fn) if jit else fn)(*args))

    got = run()
    monkeypatch.setattr(delta, "at_tier", lambda table, src: table[src])
    want = run()
    assert got.shape == (256, T)
    np.testing.assert_array_equal(_bits(got), _bits(want))


@pytest.mark.parametrize("shape", [(8,), (8, 2), (64,), (64, 2)])
def test_at_tier_is_the_gather(shape):
    rng = np.random.default_rng(len(shape) * shape[0])
    table = rng.standard_normal(shape).astype(np.float32)
    table[1] = np.inf
    src = jnp.asarray(rng.integers(0, shape[0], 300), jnp.int32)
    got = delta.at_tier(jnp.asarray(table), src)
    np.testing.assert_array_equal(_bits(got), _bits(table[np.asarray(src)]))


# Recorded before the sweep lost its gathers: the same work in the same order.
_GLOBAL = ("3df8db647891d18f", 71, 122, 1.84987473487854)
_SHARDS = ("fec7ab0edba120b2", [12, 8, 10], [12, 7, 10],
           [1546.8468017578125, 1555.2239990234375, 1449.447509765625])
_SAMPLED = ("bc52706f690949de", 26, 25, 2470.775390625)


def _global_problem():
    p = synthetic_fleet(3000, 64, seed=7).problem
    rng = np.random.default_rng(11)
    return p.with_avoid(jnp.asarray(rng.random((p.num_apps, p.num_tiers)) < 0.05))


def _shard_batch():
    fleet = synthetic_fleet(1200, 24, seed=0, util_target=0.8)
    return partition_problem(fleet.problem, plan_shards(fleet, 3))


@pytest.mark.parametrize("case", ["global", "shards", "sampled"])
def test_solve_trajectory_unchanged(case):
    if case == "global":
        r = solve_local(_global_problem(), LocalSearchConfig())
        got = (_digest(r.assignment), r.extra["sweeps"],
               r.extra["committed_moves"], r.objective)
        assert got == _GLOBAL
    elif case == "shards":
        sp = _shard_batch()
        assert sp.problems.capacity.shape == (3, 8, 2)   # S = 3, Tb = 8
        r = solve_shards(sp)
        got = (_digest(r.x), r.iterations.tolist(), r.committed.tolist(),
               [float(o) for o in r.objective])
        assert got == _SHARDS
    else:
        p = synthetic_fleet(600, 16, seed=2, util_target=0.8).problem
        r = solve_local(p, LocalSearchConfig(temperature=0.05, max_iters=64, seed=3))
        got = (_digest(r.assignment), r.extra["sweeps"],
               r.extra["committed_moves"], r.objective)
        assert got == _SAMPLED


# -- structure of the lowered loop ---------------------------------------------

_CALL = re.compile(r"call @([\w.$-]+)\(")
_GATHER_RESULT = re.compile(r'"stablehlo\.gather".*->\s*tensor<([0-9x]*)x?[a-z]\w*>')


def _block(lines, start):
    """Lines from ``start`` through the close of the braces it opens."""
    depth, out = 0, []
    for line in lines[start:]:
        out.append(line)
        depth += line.count("{") - line.count("}")
        if depth == 0 and len(out) > 1:
            return out
    raise AssertionError("unbalanced braces in the lowered module")


def _first_while(funcs, name):
    """The first while loop reached from function ``name``, in call order."""
    body = funcs[name]
    for i, line in enumerate(body):
        if "stablehlo.while" in line:
            return _block(body, i)
        for callee in _CALL.findall(line):
            loop = _first_while(funcs, callee)
            if loop is not None:
                return loop
    return None


def _while_body(text):
    """The lines of the solver's outermost while loop, with those of the
    functions it calls."""
    lines = text.splitlines()
    funcs = {}
    for i, line in enumerate(lines):
        m = re.search(r"func\.func \w+ @([\w.$-]+)\(", line)
        if m:
            funcs[m.group(1)] = _block(lines, i)
    todo, seen, body = [_first_while(funcs, "main")], set(), []
    while todo:
        block = todo.pop()
        body += block
        for line in block:
            for name in _CALL.findall(line):
                if name not in seen:
                    seen.add(name)
                    todo.append(funcs[name])
    return body


def _lowered(case):
    if case in ("global", "sampled"):
        p = synthetic_fleet(300, 16, seed=0).problem
        n_apps, n_tiers = p.num_apps, p.num_tiers
        text = _solve_local_jit.lower(
            p, jax.random.PRNGKey(0), p.assignment0, max_iters=8,
            temperature=0.05 if case == "sampled" else 0.0, tol=1e-7,
            batch_moves=16, batch_quality=0.9,
        ).as_text()
    else:
        sp = _shard_batch()
        n_apps, n_tiers = sp.problems.demand.shape[1], sp.problems.capacity.shape[1]
        keys = jax.random.split(jax.random.PRNGKey(0), sp.num_shards)
        text = _batched_solver(ShardSolveConfig()).lower(
            sp.problems, keys, sp.problems.assignment0).as_text()
    return n_apps, n_tiers, text


@pytest.mark.parametrize("case", ["global", "shards", "sampled"])
def test_sweep_loop_has_no_app_axis_gather(case):
    n_apps, n_tiers, text = _lowered(case)
    body = _while_body(text)
    # The [N, T] sweep lies in what the walk collected: it reached the body.
    assert any(f"{n_apps}x{n_tiers}xf32>" in line for line in body)
    shapes = [tuple(int(d) for d in m.group(1).split("x") if d)
              for m in map(_GATHER_RESULT.search, body) if m]
    assert all(n_apps not in s for s in shapes), (n_apps, shapes)
