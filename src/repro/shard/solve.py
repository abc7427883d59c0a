"""Batched shard solving: S subproblems under one vmapped LocalSearch.

One executable solves every shard: the stacked ``ShardedProblem`` (uniform
(Nb, Tb) shapes from ``shard.partition``) runs through
``vmap(_solve_local_jit)`` under a single outer ``jit``, so a fleet of any
size costs one compilation per (S, Nb, Tb) shape triple — the same
shape-bucketed caching contract as the global solver, observable through
``shard_batch_trace_count``.

At ``temperature=0`` the batched top-k LocalSearch never consumes its PRNG
key, so the batched pass is deterministic and bit-reproducible per shard
regardless of the split.  Device placement of the stacked batch goes
through ``distributed.place_shard_batch`` under ``jax.set_mesh`` (a no-op
off-mesh).
"""

from __future__ import annotations

import dataclasses
import time

import jax
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core.solver_local import _solve_local_jit
from repro.distributed.sharding import dp_axes, place_shard_batch
from repro.shard.partition import ShardedProblem

_TRACE_COUNTS = {"shard_batch": 0}
_CACHE: dict = {}


def shard_batch_trace_count() -> int:
    """How many times the batched shard solver has been (re)traced."""
    return _TRACE_COUNTS["shard_batch"]


@dataclasses.dataclass(frozen=True)
class ShardSolveConfig:
    """Knobs for the batched pass (mirrors ``LocalSearchConfig``)."""

    max_iters: int = 256
    tol: float = 1e-7
    batch_moves: int = 16
    batch_quality: float = 0.9
    seed: int = 0


@dataclasses.dataclass
class ShardSolveResult:
    """Per-shard outputs of one batched pass (leading [S] axis).

    Under a ``dirty`` mask (delta solve) the unsolved shards report their
    incumbent assignment with 0 iterations / 0 committed moves and a NaN
    objective; ``solved`` records which shards actually ran.
    """

    x: jax.Array  # i32[S, Nb] local assignments
    iterations: np.ndarray  # i32[S]
    converged: np.ndarray  # bool[S]
    committed: np.ndarray  # i32[S] committed moves per shard
    objective: np.ndarray  # f32[S] final per-shard objective
    solve_time_s: float
    trace_count: int
    solved: np.ndarray = dataclasses.field(
        default_factory=lambda: np.zeros(0, bool)
    )  # bool[S]


def _batched_solver(config: ShardSolveConfig):
    """jit(vmap(LocalSearch-core)), cached per static-knob tuple.

    The jit cache keys executables by the (S, Nb, Tb) leaf shapes on top of
    this per-knob cache, so drifting shard counts reuse compilations the
    same way drifting app counts reuse app buckets.
    """
    key = (config.max_iters, config.tol, config.batch_moves, config.batch_quality)
    fn = _CACHE.get(key)
    if fn is None:

        def one(p, k, x0):
            return _solve_local_jit(
                p,
                k,
                x0,
                max_iters=config.max_iters,
                temperature=0.0,
                tol=config.tol,
                batch_moves=config.batch_moves,
                batch_quality=config.batch_quality,
            )

        def batched(problems, keys, x0):
            _TRACE_COUNTS["shard_batch"] += 1
            mesh = jax.sharding.get_abstract_mesh()
            if mesh.empty or mesh.size == 1:
                return jax.vmap(one)(problems, keys, x0)
            # Under a multi-device mesh every device solves its own slice
            # of the shards: the vmap runs per device inside shard_map, so
            # no shard's search ever spans devices and the body has no
            # collectives (hence no varying-axis bookkeeping to check).
            spec = P(dp_axes(mesh))
            return jax.shard_map(
                jax.vmap(one), in_specs=spec, out_specs=spec, check_vma=False
            )(problems, keys, x0)

        fn = jax.jit(batched)
        _CACHE[key] = fn
    return fn


def solve_shards(
    sharded: ShardedProblem,
    config: ShardSolveConfig | None = None,
    *,
    dirty=None,
) -> ShardSolveResult:
    """Solve all shards (or only the ``dirty`` ones) as one batched pass.

    ``dirty`` is an optional bool[S] mask (or iterable of shard indices):
    the *delta-solve* path.  The dirty subproblems are gathered out of the
    stacked pytree with an index select — every leaf keeps the exact values
    it holds in the full stack, and the per-shard PRNG keys are gathered
    from the same ``split`` the full pass uses — so an all-dirty delta
    solve runs the identical executable on identical inputs and is
    bit-identical to the full solve (property-tested in
    tests/test_service.py).  A strict subset pays one extra compilation per
    new (S', Nb, Tb) shape triple and leaves unsolved shards at their
    incumbent assignment.
    """
    cfg = config if config is not None else ShardSolveConfig()
    S = sharded.num_shards
    problems = sharded.problems
    keys = jax.random.split(jax.random.PRNGKey(cfg.seed), S)
    x0 = problems.assignment0
    fn = _batched_solver(cfg)

    if dirty is None:
        idx = np.arange(S)
    else:
        mask = np.asarray(dirty)
        idx = (
            np.where(mask)[0]
            if mask.dtype == bool
            else np.unique(mask.astype(np.int64))
        )
    solved = np.zeros(S, bool)
    solved[idx] = True
    if idx.size == 0:
        return ShardSolveResult(
            x=x0,
            iterations=np.zeros(S, np.int32),
            converged=np.ones(S, bool),
            committed=np.zeros(S, np.int32),
            objective=np.full(S, np.nan, np.float32),
            solve_time_s=0.0,
            trace_count=shard_batch_trace_count(),
            solved=solved,
        )

    sub_problems = jax.tree_util.tree_map(lambda a: a[idx], problems)
    # Under a multi-device mesh the batch spreads over the batch axes
    # (padded to a multiple of their size); the padding rows are dropped.
    batch, n = place_shard_batch((sub_problems, keys[idx], x0[idx]))
    t0 = time.perf_counter()
    out = fn(*batch)
    if out[0].shape[0] != n:
        out = tuple(np.asarray(o)[:n] for o in out)
    x_sub, it, done, committed, obj = out
    x_sub = jax.block_until_ready(x_sub)
    if idx.size == S:
        return ShardSolveResult(
            x=x_sub,
            iterations=np.asarray(it),
            converged=np.asarray(done),
            committed=np.asarray(committed),
            objective=np.asarray(obj),
            solve_time_s=time.perf_counter() - t0,
            trace_count=shard_batch_trace_count(),
            solved=solved,
        )
    # Scatter the solved shards back; the rest keep their incumbents.
    x = np.asarray(x0).copy()
    x[idx] = np.asarray(x_sub)
    iterations = np.zeros(S, np.int32)
    iterations[idx] = np.asarray(it)
    converged = np.ones(S, bool)
    converged[idx] = np.asarray(done)
    committed_full = np.zeros(S, np.int32)
    committed_full[idx] = np.asarray(committed)
    objective = np.full(S, np.nan, np.float32)
    objective[idx] = np.asarray(obj)
    return ShardSolveResult(
        x=x,
        iterations=iterations,
        converged=converged,
        committed=committed_full,
        objective=objective,
        solve_time_s=time.perf_counter() - t0,
        trace_count=shard_batch_trace_count(),
        solved=solved,
    )
