"""Mixture-of-Experts layer (granite-moe, deepseek-v2-lite).

Top-k routing with capacity-bounded, sort-based dispatch (GShard-style but
scatter/gather instead of one-hot einsums, so HLO FLOPs stay proportional to
*active* compute — important for an honest roofline).  Expert weights are
stacked [E, ...] so expert parallelism is a PartitionSpec on axis 0.

Deepseek-v2 specifics supported: shared experts (always-on), top-k softmax
renormalization, first-k-dense layers (handled by the caller's block pattern).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import layers as L


def moe_init(cfg, key, dtype):
    E, d, f = cfg.num_experts, cfg.d_model, cfg.d_ff_expert
    ks = jax.random.split(key, 5)

    def expert_stack(k, d_in, d_out, scale=1.0):
        keys = jax.random.split(k, E)
        return jnp.stack([L.dense_init(keys[e], d_in, d_out, dtype, scale)
                          for e in range(E)])

    p = {
        "router": L.dense_init(ks[0], d, E, jnp.float32),
        "w_gate": expert_stack(ks[1], d, f),
        "w_up": expert_stack(ks[2], d, f),
        "w_down": expert_stack(ks[3], f, d, scale=0.5),
    }
    if cfg.num_shared_experts:
        p["shared"] = L.mlp_init(ks[4], d, f * cfg.num_shared_experts, dtype)
    return p


def _routing(cfg, params, xf):
    """Shared routing math: -> (gates [T,k], idx [T,k], aux loss)."""
    E, k = cfg.num_experts, cfg.top_k
    T = xf.shape[0]
    logits = L.linear(xf.astype(jnp.float32), params["router"])   # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, k)                          # [T, k]
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    me = jnp.mean(probs, axis=0)
    ce = jnp.zeros(E).at[idx.reshape(-1)].add(jnp.ones(T * k) / (T * k))
    aux = cfg.router_aux_coef * E * jnp.sum(me * ce)
    return gates, idx, aux


def moe_apply(cfg, params, x):
    if cfg.moe_impl == "ep":
        y_aux = _moe_apply_ep(cfg, params, x)
        if y_aux is not None:
            return y_aux
        # no mesh in scope (single-device tests): fall through to global
    return _moe_apply_global(cfg, params, x)


def _moe_apply_global(cfg, params, x):
    """x: [B, S, d] -> (y, aux_loss)."""
    B, S, d = x.shape
    E, k = cfg.num_experts, cfg.top_k
    T = B * S
    xf = x.reshape(T, d)

    logits = L.linear(xf.astype(jnp.float32), params["router"])   # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, k)                          # [T, k]
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)

    # Load-balancing aux loss (Switch-style): E * sum_e f_e * p_e.
    me = jnp.mean(probs, axis=0)
    ce = jnp.zeros(E).at[idx.reshape(-1)].add(
        jnp.ones(T * k) / (T * k))
    aux = cfg.router_aux_coef * E * jnp.sum(me * ce)

    # Decode (S == 1) is dropless — capacity drops would silently corrupt
    # generation; T*k is tiny there.  Train/prefill use the configured
    # capacity factor (drops are the standard TPU MoE trade-off).
    if S == 1:
        capacity = T * k
    else:
        capacity = max(int(T * k / E * cfg.capacity_factor), k)
    capacity = min(capacity, T * k)

    # --- sort-based dispatch ---
    e_flat = idx.reshape(-1)                                      # [T*k]
    g_flat = gates.reshape(-1)
    tok_flat = jnp.arange(T * k, dtype=jnp.int32) // k
    order = jnp.argsort(e_flat, stable=True)
    e_sorted = e_flat[order]
    tok_sorted = tok_flat[order]
    g_sorted = g_flat[order]
    start = jnp.searchsorted(e_sorted, jnp.arange(E, dtype=e_sorted.dtype))
    rank = jnp.arange(T * k, dtype=jnp.int32) - start[e_sorted].astype(jnp.int32)
    keep = rank < capacity
    slot = jnp.clip(rank, 0, capacity - 1)

    buf = jnp.zeros((E, capacity, d), xf.dtype)
    contrib = jnp.where(keep[:, None], xf[tok_sorted], 0)
    buf = buf.at[e_sorted, slot].add(contrib)

    # --- expert FFN on [E, capacity, d] (vmapped over the expert axis) ---
    act = L.act_fn(cfg.activation)
    def expert_ffn(b, wg, wu, wd):
        h = act(L.linear(b, wg)) * L.linear(b, wu)
        return L.linear(h.astype(b.dtype), wd)
    h = jax.vmap(expert_ffn)(buf, params["w_gate"], params["w_up"],
                             params["w_down"])                    # [E, cap, d]

    # --- combine ---
    y_slot = (h[e_sorted, slot].astype(jnp.float32)
              * jnp.where(keep, g_sorted, 0.0)[:, None])
    y = jnp.zeros((T, d), jnp.float32).at[tok_sorted].add(y_slot)

    if cfg.num_shared_experts:
        y = y + L.mlp_apply(params["shared"], xf, cfg.activation)
    return y.reshape(B, S, d).astype(x.dtype), aux


# ---------------------------------------------------------------------------
# expert-parallel implementation (§Perf B): shard_map over (data x model)
# ---------------------------------------------------------------------------

def _get_mesh():
    """The mesh set with ``jax.set_mesh`` (abstract inside a trace), or None."""
    mesh = jax.sharding.get_abstract_mesh()
    return None if mesh.empty else mesh


def _moe_apply_ep(cfg, params, x):
    """Expert parallelism via shard_map.

    The baseline ("global") dispatch sorts/scatters over the *globally
    sharded* token axis, which XLA can only implement by gathering tokens
    across the mesh — measured at ~1.6e13 collective bytes/step for
    deepseek train_4k.  Here instead:

      * routing + capacity dispatch run per data-shard (local tokens only),
      * each model rank scatters/computes only its E/ep experts,
      * partial expert outputs combine with one bf16 psum over "model"
        (the same wire pattern as a row-parallel matmul),
      * aux loss is pmean'd over the whole mesh (exact replication).

    Returns None when no (data, model) mesh is in scope (single-device
    tests fall back to the global path).
    """
    import numpy as np
    from jax.sharding import PartitionSpec as P

    mesh = _get_mesh()
    if mesh is None or "model" not in mesh.axis_names:
        return None
    try:
        from jax import shard_map as _shard_map

        def shard_map_fn(f, in_specs, out_specs):
            return _shard_map(f, mesh=mesh, in_specs=in_specs,
                              out_specs=out_specs, check_vma=False)
    except (ImportError, TypeError):
        from jax.experimental.shard_map import shard_map as _shard_map_legacy

        def shard_map_fn(f, in_specs, out_specs):
            return _shard_map_legacy(f, mesh=mesh, in_specs=in_specs,
                                     out_specs=out_specs, check_rep=False)

    sizes = dict(zip(mesh.axis_names, mesh.axis_sizes))
    ep = sizes["model"]
    E, k = cfg.num_experts, cfg.top_k
    B, S, d = x.shape
    dp_ax = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    dp = int(np.prod([sizes[a] for a in dp_ax])) if dp_ax else 1
    if E % ep != 0 or B % max(dp, 1) != 0:
        return None
    E_loc = E // ep
    T_loc = (B // dp) * S
    capacity = max(int(T_loc * k / E * cfg.capacity_factor), k)
    if S == 1:
        capacity = T_loc * k
    capacity = min(capacity, T_loc * k)
    all_axes = dp_ax + ("model",)

    def shard_fn(xb, router_w, wg, wu, wd):
        r = jax.lax.axis_index("model")
        Tl = xb.shape[0] * xb.shape[1]
        xf = xb.reshape(Tl, d)
        logits = L.linear(xf.astype(jnp.float32), router_w)
        probs = jax.nn.softmax(logits, axis=-1)
        gates, idx = jax.lax.top_k(probs, k)
        gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
        me = jnp.mean(probs, axis=0)
        ce = jnp.zeros(E).at[idx.reshape(-1)].add(jnp.ones(Tl * k) / (Tl * k))
        aux = cfg.router_aux_coef * E * jnp.sum(me * ce)
        aux = jax.lax.pmean(aux, all_axes)

        # local dispatch; keep only this model rank's expert payloads
        e_flat = idx.reshape(-1)
        g_flat = gates.reshape(-1)
        tok_flat = jnp.arange(Tl * k, dtype=jnp.int32) // k
        order = jnp.argsort(e_flat, stable=True)
        e_sorted = e_flat[order]
        tok_sorted = tok_flat[order]
        g_sorted = g_flat[order]
        start = jnp.searchsorted(e_sorted, jnp.arange(E, dtype=e_sorted.dtype))
        rank_in_e = (jnp.arange(Tl * k, dtype=jnp.int32)
                     - start[e_sorted].astype(jnp.int32))
        e_local = e_sorted.astype(jnp.int32) - r * E_loc
        mine = (e_local >= 0) & (e_local < E_loc) & (rank_in_e < capacity)
        slot = jnp.clip(rank_in_e, 0, capacity - 1)
        e_idx = jnp.clip(e_local, 0, E_loc - 1)

        buf = jnp.zeros((E_loc, capacity, d), xf.dtype)
        buf = buf.at[e_idx, slot].add(
            jnp.where(mine[:, None], xf[tok_sorted], 0))

        act = L.act_fn(cfg.activation)

        def ffn(b, g_, u_, d_):
            h = act(L.linear(b, g_)) * L.linear(b, u_)
            return L.linear(h.astype(b.dtype), d_)

        h = jax.vmap(ffn)(buf, wg, wu, wd)                  # [E_loc, cap, d]
        y_slot = (h[e_idx, slot].astype(jnp.float32)
                  * jnp.where(mine, g_sorted, 0.0)[:, None])
        y = jnp.zeros((Tl, d), jnp.float32).at[tok_sorted].add(y_slot)
        # bf16 partial-output combine — same wire pattern as row-parallel TP
        y = jax.lax.psum(y.astype(xb.dtype), "model")
        return y.reshape(xb.shape), aux

    in_specs = (P(dp_ax if dp_ax else None, None, None), P(None, None),
                P("model", None, None), P("model", None, None),
                P("model", None, None))
    out_specs = (P(dp_ax if dp_ax else None, None, None), P())
    f = shard_map_fn(shard_fn, in_specs, out_specs)
    y, aux = f(x, params["router"], params["w_gate"], params["w_up"],
               params["w_down"])
    if cfg.num_shared_experts:
        y = y + L.mlp_apply(params["shared"], x, cfg.activation)
    return y.astype(x.dtype), aux
