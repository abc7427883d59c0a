"""Placement of the sharded fleet solver's shard batch on a device mesh.

The fleet solver stacks S shard subproblems on a leading axis and vmaps
over it.  Under a multi-device mesh (``jax.set_mesh``) that leading axis
shards over the mesh's ``"data"`` axis, so each device solves its own
slice of the shards; off-mesh, or on one device, nothing is placed.
"""
from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def dp_axes(mesh: Mesh):
    """The batch-parallel axes the shard batch splits over: ("data",)."""
    return ("data",)


def _ambient_mesh() -> Optional[Mesh]:
    """The concrete mesh set with ``jax.set_mesh``, or None off-mesh.

    Only the installed JAX's own API is consulted: the abstract mesh says
    whether a mesh is in scope, and ``jax.sharding.get_mesh`` gives its
    devices.  Call outside ``jit`` (``get_mesh`` refuses a trace).
    """
    if jax.sharding.get_abstract_mesh().empty:
        return None
    mesh = jax.sharding.get_mesh()
    return None if mesh.empty else mesh


def place_shard_batch(tree: Any) -> tuple[Any, int]:
    """Place a stacked [S, ...] shard batch over the mesh's ``"data"`` axis.

    The sharded fleet solver stacks S subproblems on a leading axis and
    vmaps over it — embarrassingly parallel, so the leading axis shards
    over ``"data"`` and each device solves its slice of the shards.
    Returns ``(placed, S)``: under a multi-device mesh the leading axis is
    padded to a multiple of the axis size by repeating the last shard, so
    the caller keeps the first ``S`` rows of every result.  Off-mesh, or on
    a one-device mesh, the tree passes through untouched.  A placement the
    mesh cannot take raises; nothing is silently replicated.
    """
    leaves = [x for x in jax.tree.leaves(tree) if getattr(x, "ndim", 0) >= 1]
    if not leaves:
        raise ValueError("place_shard_batch needs at least one [S, ...] leaf")
    S = int(leaves[0].shape[0])
    mesh = _ambient_mesh()
    if mesh is None or mesh.size == 1:
        return tree, S
    dp = dp_axes(mesh)
    missing = [a for a in dp if a not in mesh.axis_names]
    if missing:
        raise ValueError(f"mesh {mesh.axis_names} has no batch axis {missing}")
    width = int(np.prod([mesh.shape[a] for a in dp]))
    padded = -(-S // width) * width

    def one(leaf):
        if getattr(leaf, "ndim", 0) < 1 or leaf.shape[0] != S:
            raise ValueError(
                f"every shard-batch leaf needs leading axis {S}, got "
                f"{getattr(leaf, 'shape', None)}")
        if padded != S:
            fill = jnp.repeat(leaf[-1:], padded - S, axis=0)
            leaf = jnp.concatenate([leaf, fill], axis=0)
        spec = P(dp, *([None] * (leaf.ndim - 1)))
        return jax.device_put(leaf, NamedSharding(mesh, spec))
    return jax.tree.map(one, tree), S
