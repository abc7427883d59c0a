"""PartitionSpec rules: params, optimizer state, batches, caches.

2D "megatron" layout on the ("data", "model") mesh, with an optional leading
"pod" axis that composes with "data" for batch/gradient parallelism:
  * column-parallel up-projections  (d_model -> hidden): shard out-dim
  * row-parallel   down-projections (hidden -> d_model): shard in-dim
  * embeddings / lm_head: vocab-sharded
  * MoE expert stacks: expert-parallel on axis 0 (the "model" axis)
  * everything else (norms, biases, scalars): replicated

Rules are *name-based* with a divisibility sanitizer: if a proposed sharded
dim is not divisible by the mesh axis size (e.g. kv-head counts smaller than
the model axis, odd vocab sizes), the axis is dropped for that dim —
correctness first, and the dry-run/roofline shows the cost honestly.
"""
from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def dp_axes(mesh: Mesh):
    """Batch-parallel axes: ("pod", "data") on multi-pod, else ("data",)."""
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


# Name-based rules: (names, core_rank, spec-for-the-core-dims).  A leaf may
# carry extra *leading* stack dims (scanned layer stacks, zamba's
# per-application out_proj stack); they are padded with None by rank, which
# makes the rules independent of whether a family stacks its layers.
_RULES: tuple[tuple[tuple[str, ...], int, tuple], ...] = (
    # MoE expert stacks [E, d, f] — expert-parallel on the model axis
    (("moe::w_gate", "moe::w_up", "moe::w_down"), 3, ("model", None, None)),
    # embeddings [V, d] — vocab-sharded
    (("embed",), 2, ("model", None)),
    # xlstm block-diagonal recurrent mats [H, Dh, Dh]
    (("r_z", "r_i", "r_f", "r_o"), 3, (None, "model", None)),
    # row-parallel (hidden -> d_model)
    (("wo", "w_down", "out_proj"), 2, ("model", None)),
    # column-parallel (d_model -> hidden)
    (("wq", "wk", "wv", "w_gate", "w_up", "in_proj", "wk_up", "wv_up",
      "wkv_down", "w_gate_up", "w_in", "w_if", "wk_rope", "head", "lm_head",
      "conv_w", "pos_conv_w"), 2, (None, "model")),
    # replicated small projections
    (("router",), 2, (None, None)),
    # hidden-dim vectors (sharded with their producing projection)
    (("bq", "bk", "bv", "conv_b", "gate_norm"), 1, ("model",)),
    # per-head / d_model vectors and norms — replicated
    (("A_log", "D", "dt_bias", "kv_norm", "mask_embed", "norm", "ln1", "ln2",
      "ln1_post", "ln2_post", "final_norm", "out_norm", "scale", "bias",
      "ffn"), 1, (None,)),
)


def _match(path: str, last: str, names: tuple[str, ...]) -> bool:
    for name in names:
        if "::" in name:                 # context::leafname
            ctx, leafname = name.split("::")
            if ctx in path and last == leafname and "shared" not in path:
                return True
        elif last == name or (len(name) > 2 and name in last):
            return True
    return False


def param_spec(path_parts: tuple, leaf) -> P:
    path = "/".join(str(p) for p in path_parts)
    last = str(path_parts[-1]) if path_parts else ""
    ndim = leaf.ndim
    for names, core_rank, spec in _RULES:
        if _match(path, last, names):
            if ndim < core_rank:         # scalarized / degenerate leaf
                return P(*((None,) * ndim))
            lead = ndim - core_rank
            return P(*((None,) * lead + tuple(spec)))
    return P(*((None,) * ndim))


def sanitize(spec: P, shape: tuple, mesh: Mesh) -> P:
    """Drop mesh axes that do not divide the corresponding dim."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    out = []
    for i, axis in enumerate(spec):
        if axis is None:
            out.append(None)
            continue
        axes = axis if isinstance(axis, tuple) else (axis,)
        total = int(np.prod([sizes[a] for a in axes]))
        if i < len(shape) and shape[i] % total == 0:
            out.append(axis)
        else:
            out.append(None)
    return P(*out)


def params_shardings(mesh: Mesh, params_shape) -> Any:
    """NamedShardings for a params pytree (of ShapeDtypeStructs or arrays)."""
    def one(path, leaf):
        spec = param_spec(tuple(p.key if hasattr(p, "key") else
                                getattr(p, "idx", p) for p in path), leaf)
        spec = sanitize(spec, leaf.shape, mesh)
        return NamedSharding(mesh, spec)
    return jax.tree_util.tree_map_with_path(one, params_shape)


def opt_state_shardings(mesh: Mesh, opt_shape, *, zero1: bool = False) -> Any:
    """Optimizer state mirrors the params tree (count is replicated).

    ``zero1``: additionally shard each moment tensor over the data axis
    (ZeRO-1).  The optimizer math then runs data-sharded and XLA inserts a
    reduce-scatter(grads) / all-gather(updates) pair — trading a little
    wire for an 8x cut in f32 moment memory.  See EXPERIMENTS.md §Perf B3.
    """
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    data_size = sizes.get("data", 1)

    def one(path, leaf):
        keys = tuple(p.key if hasattr(p, "key") else getattr(p, "idx", p)
                     for p in path)
        if leaf.ndim == 0:
            return NamedSharding(mesh, P())
        spec = param_spec(keys, leaf)
        spec = sanitize(spec, leaf.shape, mesh)
        if zero1 and data_size > 1:
            entries = list(spec) + [None] * (leaf.ndim - len(spec))
            # shard the largest still-unsharded dim over "data"
            cands = [(leaf.shape[i], i) for i, a in enumerate(entries)
                     if a is None and leaf.shape[i] % data_size == 0]
            if cands:
                _, i = max(cands)
                entries[i] = "data"
                spec = P(*entries)
        return NamedSharding(mesh, spec)
    return jax.tree_util.tree_map_with_path(one, opt_shape)


def batch_shardings(mesh: Mesh, batch_shape) -> Any:
    """Model inputs: batch dim over ("pod","data"), rest replicated."""
    dp = dp_axes(mesh)

    def one(leaf):
        spec = P(dp, *([None] * (leaf.ndim - 1)))
        return NamedSharding(mesh, sanitize(spec, leaf.shape, mesh))
    return jax.tree.map(one, batch_shape)


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
    """Place a stacked [S, ...] shard batch over the mesh's batch axes.

    The sharded fleet solver stacks S subproblems on a leading axis and
    vmaps over it — embarrassingly parallel, so the leading axis shards
    over ("pod","data") exactly like a model input batch and each device
    solves its slice of the shards.  Returns ``(placed, S)``: under a
    multi-device mesh the leading axis is padded to a multiple of the batch
    axes' size by repeating the last shard, so the caller keeps the first
    ``S`` rows of every result.  Off-mesh, or on a one-device mesh, the
    tree passes through untouched.  A placement the mesh cannot take
    raises; nothing is silently replicated.
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


def cache_shardings(mesh: Mesh, cache_shape, *, kv_shard: str = "heads") -> Any:
    """KV/state caches: batch over dp axes, heads/feature over "model".

    Handles the layouts used by the models:
      [L, B, S, KV, D] stacked attention kv, [B, S, KV, D] unstacked,
      [B, S, lora] MLA, [L, B, H, P, N] mamba states, xlstm states, scalars.

    ``kv_shard``:
      "heads" — kv-head dim on "model" (baseline; silently replicates when
                the head count does not divide the axis),
      "seq"   — sequence dim on "model" (flash-decoding style: every chip
                owns a slice of the context; softmax combines via small
                partial reductions).  See EXPERIMENTS.md §Perf A.
      "auto"  — heads when the kv-head count divides the model axis
                (measured best there), else seq (11-12x better when it
                doesn't).  The production default for launch/serve paths.
    """
    dp = dp_axes(mesh)
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    model_size = sizes.get("model", 1)

    def one(path, leaf):
        path_s = "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                          for p in path)
        nd = leaf.ndim
        if nd == 0:
            spec = P()
        elif "pos" in path_s:
            spec = P()
        else:
            # Identify the batch dim: stacked caches have it second.
            stacked = ("layers" in path_s or "mamba" in path_s
                       or "attn_k" in path_s or "attn_v" in path_s)
            spec_list: list = [None] * nd
            b_dim = 1 if (stacked and nd >= 2) else 0
            spec_list[b_dim] = dp
            # Shard the "model"-parallel dim where one exists.
            is_attn_kv = (("k" in path_s.split("/")[-1]
                           or "v" in path_s.split("/")[-1])
                          and nd >= 4 and "ssm" not in path_s
                          and "conv" not in path_s)
            if "c_kv" in path_s:
                spec_list[-1] = "model"              # MLA latent dim
            elif "k_pe" in path_s:
                pass                                 # tiny; replicate
            elif "ssm" in path_s and nd >= 3:
                spec_list[b_dim + 1] = "model"       # mamba heads
            elif is_attn_kv and (
                    kv_shard == "seq"
                    or (kv_shard == "auto"
                        and leaf.shape[nd - 2] % model_size != 0)):
                spec_list[b_dim + 1] = "model"       # sequence slice
            elif nd >= 4:
                spec_list[nd - 2] = "model"          # kv heads (baseline)
            spec = P(*spec_list)
        return NamedSharding(mesh, sanitize(spec, leaf.shape, mesh))
    return jax.tree_util.tree_map_with_path(one, cache_shape)


def logits_sharding(mesh: Mesh, shape: Optional[tuple] = None,
                    ndim: int = 3) -> NamedSharding:
    dp = dp_axes(mesh)
    spec = P(dp, *([None] * (ndim - 2)), "model")
    if shape is not None:
        spec = sanitize(spec, shape, mesh)
    return NamedSharding(mesh, spec)


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


# ---------------------------------------------------------------------------
# in-model activation constraints (MaxText-style explicit activation sharding)
# ---------------------------------------------------------------------------

def constrain(x, dims: tuple):
    """with_sharding_constraint using logical dims, safe without a mesh.

    dims entries: "dp" (batch axes), "model", or None.  Axes that do not
    exist in the ambient mesh, or that do not divide the dim, are dropped —
    the same correctness-first policy as ``sanitize``.
    """
    import jax

    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return x
    names = set(mesh.axis_names)
    sizes = dict(zip(mesh.axis_names, mesh.axis_sizes))
    dp = tuple(a for a in ("pod", "data") if a in names)

    spec = []
    for d, dim in zip(dims, x.shape):
        if d is None:
            spec.append(None)
            continue
        if d == "dp":
            axes = dp
        elif d == "dpm":                   # batch over data AND model axes
            axes = dp + (("model",) if "model" in names else ())
        else:
            axes = (d,) if d in names else ()
        total = int(np.prod([sizes[a] for a in axes])) if axes else 1
        if axes and dim % total == 0:
            spec.append(axes if len(axes) > 1 else axes[0])
        else:
            spec.append(None)
    try:
        return jax.lax.with_sharding_constraint(x, P(*spec))
    except Exception:
        return x
