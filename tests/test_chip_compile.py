"""Ahead-of-time compiles of the main path for one TPU v5e chip.

The TPU compiler is installed with JAX and compiles for a chip that is
described, not attached: each test lowers one program of the streaming
control loop at the regional-fleet size (100,000 apps x 64 tiers) with
shapes only, and compiles it for one chip of a described ``v5e:2x2`` host
(the sharded pass also for all four chips of it).
A compile that passes is not a chip run; what it catches is what the chip's
compiler would refuse (layouts, memory) before any chip time is spent.

The topology is described inside a module-scoped fixture, never at import:
only one process may hold the TPU library, and the test workers all import
this file.  Keep every such compile in this one file.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from repro.core.problem import bucket_size, pad_problem
from repro.core.solver_local import LocalSearchConfig, _solve_local_jit
from repro.core.sptlb import TIMEOUT_BUDGETS
from repro.kernels.pack import pack_ffd_tiers
from repro.shard import partition_problem, plan_shards, synthetic_fleet
from repro.shard.solve import ShardSolveConfig, _batched_solver

NUM_APPS, NUM_TIERS, NUM_REGIONS = 100_000, 64, 8
V5E_HBM_BYTES = 16 * 2**30
MAX_ITERS = TIMEOUT_BUDGETS[30]   # the controller's default engine budget


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # An entry compiled for a described chip cannot be read back without
    # one; keep these compiles out of any persistent cache, and give the
    # worker's setting back to the tests that follow.
    was_enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        try:
            desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc
    finally:
        jax.config.update("jax_enable_compilation_cache", was_enabled)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def fleet():
    return synthetic_fleet(NUM_APPS, num_tiers=NUM_TIERS, num_regions=NUM_REGIONS, seed=0)


def _shapes(tree, sharding):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), jnp.asarray(a).dtype, sharding=sharding),
        tree,
    )


def _fits_one_chip(compiled):
    mem = compiled.memory_analysis()
    used = mem.temp_size_in_bytes + mem.argument_size_in_bytes + mem.output_size_in_bytes
    assert 0 < used < V5E_HBM_BYTES, used


def test_global_local_search_compiles_for_v5e(fleet, one_chip):
    """The full pass's solver at the padded app bucket the controller uses."""
    problem = pad_problem(fleet.problem, bucket_size(NUM_APPS))
    cfg = LocalSearchConfig(max_iters=MAX_ITERS)
    compiled = _solve_local_jit.lower(
        _shapes(problem, one_chip),
        jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip),
        jax.ShapeDtypeStruct((problem.num_apps,), jnp.int32, sharding=one_chip),
        max_iters=cfg.max_iters,
        temperature=cfg.temperature,
        tol=cfg.tol,
        batch_moves=cfg.batch_moves,
        batch_quality=cfg.batch_quality,
    ).compile()
    _fits_one_chip(compiled)


def test_batched_shard_solver_compiles_for_v5e(fleet, one_chip):
    """The delta pass's vmapped solver over S=8 shards of the fleet."""
    sharded = partition_problem(fleet.problem, plan_shards(fleet, 8))
    S, Nb = sharded.app_ids.shape
    fn = _batched_solver(ShardSolveConfig(max_iters=MAX_ITERS))
    compiled = fn.lower(
        _shapes(sharded.problems, one_chip),
        jax.ShapeDtypeStruct((S, 2), jnp.uint32, sharding=one_chip),
        jax.ShapeDtypeStruct((S, Nb), jnp.int32, sharding=one_chip),
    ).compile()
    _fits_one_chip(compiled)


def test_pack_ffd_tiers_compiles_for_v5e(fleet, one_chip):
    """Host packing of every tier at once, [T, M, R] as the host level builds it."""
    counts = np.bincount(np.asarray(fleet.problem.assignment0), minlength=NUM_TIERS)
    M = bucket_size(int(counts.max()), minimum=128)
    R = fleet.problem.num_resources
    hosts_pad = bucket_size(int(fleet.hosts_per_tier.max()), minimum=16)
    compiled = pack_ffd_tiers.lower(
        jax.ShapeDtypeStruct((NUM_TIERS, M, R), jnp.float32, sharding=one_chip),
        jax.ShapeDtypeStruct((R,), jnp.float32, sharding=one_chip),
        jax.ShapeDtypeStruct((NUM_TIERS,), jnp.int32, sharding=one_chip),
        num_hosts_pad=hosts_pad,
    ).compile()
    _fits_one_chip(compiled)


def test_batched_shard_solver_compiles_for_a_v5e_2x2_mesh(fleet, topo):
    """The S=16 pass of ``chip_smoke.py --chips 4``: the shard batch split
    over the four chips of the host, each solving its own shards."""
    mesh = Mesh(np.asarray(topo.devices).reshape(4), ("data",))
    batch = NamedSharding(mesh, P("data"))
    sharded = partition_problem(fleet.problem, plan_shards(fleet, 16))
    S, Nb = sharded.app_ids.shape
    fn = _batched_solver(ShardSolveConfig(max_iters=MAX_ITERS))
    with jax.set_mesh(mesh):
        compiled = fn.lower(
            _shapes(sharded.problems, batch),
            jax.ShapeDtypeStruct((S, 2), jnp.uint32, sharding=batch),
            jax.ShapeDtypeStruct((S, Nb), jnp.int32, sharding=batch),
        ).compile()
    _fits_one_chip(compiled)                 # the analysis is per device
    assert "all-gather" not in compiled.as_text()   # no shard spans devices
