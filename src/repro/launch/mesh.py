"""Production mesh construction.

Single pod : (16, 16)    axes ("data", "model")   = 256 chips
Multi-pod  : (2, 16, 16) axes ("pod", "data", "model") = 512 chips

A FUNCTION, not a module-level constant — importing this module must never
touch jax device state (the dry-run sets XLA_FLAGS before first jax init).
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def _auto_mesh(shape, axes):
    """A mesh whose axes keep compiler-chosen (GSPMD) propagation under
    ``jax.set_mesh`` — the models constrain activations, they do not type
    every array's sharding."""
    auto = (jax.sharding.AxisType.Auto,) * len(axes)
    return jax.make_mesh(shape, axes, axis_types=auto)


def make_host_mesh(*, data: int = 1, model: int = 1):
    """Small mesh over the locally available devices (tests, examples)."""
    n = len(jax.devices())
    assert data * model <= n, (data, model, n)
    return _auto_mesh((data, model), ("data", "model"))
