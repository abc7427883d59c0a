"""First-fit-decreasing bin packing as compiled scans (host-scheduler core).

The hierarchy's host scheduler answers "does every app mapped to this tier
still fit after packing?" by first-fit packing the tier's demand (sorted
decreasing) into identical host bins.  Two entry points:

  * ``pack_ffd``       — one tier.  The host axis is padded to a static
                         power-of-two ``num_hosts_pad`` with -inf-capacity
                         bins (they can never accept an item), and the live
                         host count arrives as a *traced* scalar — so tiers
                         with different host counts share one compiled
                         executable instead of retracing per distinct
                         ``hosts_per_tier`` value.
  * ``pack_ffd_tiers`` — every tier of a cluster at once: a vmap of the same
                         scan over a ``[T, M, R]`` demand tensor.  One device
                         dispatch replaces the per-tier Python loop inside a
                         cooperation feedback round.

Both run the seed scan's exact arithmetic: the same f32 subtractions in the
same order over the pre-sorted demand, first fit == lowest live host index;
padded bins sit *after* the live bins so they never perturb ``argmax``.
Zero-demand padding rows fit host 0 and consume nothing, so app-axis bucket
padding never changes the packing either.  Accept/reject is therefore
bit-identical across both entry points for any given item order — and
bit-identical to the seed per-tier loop whenever max demands are tie-free
(the callers canonicalize tie order by ascending app id, where the seed
packed in caller order with an unstable sort).

These are XLA ``lax.scan`` kernels, not Pallas: FFD is a strict sequential
dependence over items (each placement changes the bins the next item sees),
so there is no intra-tier parallelism for a Pallas grid to exploit — the win
is batching tiers and caching executables, which XLA already gives us.

Retrace counters (``pack_trace_count``) increment at *trace* time only, like
``solver_local.local_search_trace_count``: a delta of 0 across a call means
the jit cache was hit.  ``DispatchStats`` wraps a compiled call with the
wall-clock / dispatch / retrace bookkeeping every caller of these kernels
wants (the host scheduler level reports it through the cooperation bus's
per-level ``counters()`` hook).
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.spans import span

_TRACE_COUNTS = {"pack_ffd": 0, "pack_ffd_tiers": 0}


def pack_trace_count() -> int:
    """Total (re)traces of the packing executables across both entry points."""
    return _TRACE_COUNTS["pack_ffd"] + _TRACE_COUNTS["pack_ffd_tiers"]


@dataclasses.dataclass
class DispatchStats:
    """Device-dispatch bookkeeping for the packing kernels.

    ``run`` executes one compiled call synchronously (``np.asarray`` blocks
    on the device) and accumulates wall-clock seconds, dispatch count, and
    the retrace delta observed across the call — the counters the
    cooperation bus folds into ``CoopTimings.levels["host"]`` and
    ``host_side_frac`` classification (dispatch time counts device-side).
    """

    seconds: float = 0.0
    dispatches: int = 0
    retraces: int = 0

    def run(self, fn, *args, **kw) -> np.ndarray:
        with span("bus.pack", into=self, key="seconds"):
            before = pack_trace_count()
            out = np.asarray(fn(*args, **kw))      # asarray syncs the device
            self.retraces += pack_trace_count() - before
            self.dispatches += 1
        return out


def _ffd_scan(demand_sorted: jax.Array, capacity: jax.Array,
              num_hosts: jax.Array, num_hosts_pad: int) -> jax.Array:
    """First-fit scan of pre-sorted items into ``num_hosts`` live bins.

    ``num_hosts`` is traced; ``num_hosts_pad`` is the static padded bin
    count.  Dead bins get -inf capacity: ``-inf >= d`` is False for every
    d >= 0 (including the zero padding rows), so they never accept an item
    and never shift the first-fit index.  Returns rejected bool[M].
    """
    live = jnp.arange(num_hosts_pad) < num_hosts
    hosts0 = jnp.where(live[:, None], capacity[None, :], -jnp.inf)

    def step(hosts, d):
        fit = jnp.all(hosts >= d[None, :], axis=1)
        any_fit = jnp.any(fit)
        h = jnp.argmax(fit)                                 # first fit
        hosts = hosts.at[h].add(jnp.where(any_fit, -d, 0.0))
        return hosts, ~any_fit

    _, rejected = jax.lax.scan(step, hosts0, demand_sorted)
    return rejected


@partial(jax.jit, static_argnames=("num_hosts_pad",))
def pack_ffd(demand_sorted: jax.Array, capacity: jax.Array,
             num_hosts: jax.Array, *, num_hosts_pad: int) -> jax.Array:
    """Single-tier FFD: rejected bool[M] for ``demand_sorted`` [M, R]."""
    _TRACE_COUNTS["pack_ffd"] += 1          # trace-time side effect only
    return _ffd_scan(demand_sorted, capacity, num_hosts, num_hosts_pad)


@partial(jax.jit, static_argnames=("num_hosts_pad",))
def pack_ffd_tiers(demand_sorted: jax.Array, capacity: jax.Array,
                   hosts_per_tier: jax.Array, *,
                   num_hosts_pad: int) -> jax.Array:
    """All-tier FFD: rejected bool[T, M] for ``demand_sorted`` [T, M, R].

    Row t is tier t's demand, sorted decreasing and zero-padded to M; the
    vmapped scan packs every tier in one dispatch with per-tier live host
    counts from ``hosts_per_tier`` (i32[T]).
    """
    _TRACE_COUNTS["pack_ffd_tiers"] += 1    # trace-time side effect only
    return jax.vmap(
        lambda d, nh: _ffd_scan(d, capacity, nh, num_hosts_pad)
    )(demand_sorted, hosts_per_tier)
