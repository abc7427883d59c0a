"""Perf-variant code paths (EXPERIMENTS.md §Perf): numerics must be
preserved by every optimization flag.

Multi-device checks (EP MoE, batch-sharded attention) run in a subprocess
with 8 host devices so the main test process keeps its single-device jax.
"""
import dataclasses
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np

from repro.configs import get_config
from repro.models import build_model, reduce_for_smoke

KEY = jax.random.PRNGKey(0)


# ---------------------------------------------------------------------------
# ring cache (§Perf A4) — single device
# ---------------------------------------------------------------------------

def test_ring_cache_matches_full_cache_across_wrap():
    cfg = dataclasses.replace(reduce_for_smoke(get_config("gemma2-9b")),
                              ring_cache=True)
    model = build_model(cfg)
    params = model.init(KEY)
    B, S = 2, 24                       # reduced window = 16 < 24 -> wraps
    toks = jax.random.randint(KEY, (B, S), 0, cfg.vocab_size)
    full, _ = jax.jit(model.forward_train)(params, {"tokens": toks})

    cache = model.init_cache(B, 64)
    # local (ring) cache is window-sized; global cache is full-sized
    assert cache["layers"][0]["k"].shape[2] == cfg.window
    assert cache["layers"][1]["k"].shape[2] == 64

    _, cache = jax.jit(model.prefill)(params, {"tokens": toks[:, :20]}, cache)
    dec = jax.jit(model.decode_step)
    for t in range(20, S):
        logits, cache = dec(params, toks[:, t:t + 1], cache)
        np.testing.assert_allclose(np.asarray(logits[:, 0]),
                                   np.asarray(full[:, t]),
                                   atol=5e-3, rtol=5e-3)


def test_ring_cache_prefill_shorter_than_window():
    cfg = dataclasses.replace(reduce_for_smoke(get_config("gemma2-9b")),
                              ring_cache=True)
    model = build_model(cfg)
    params = model.init(KEY)
    B, S = 2, 10                        # < window (16): no wrap
    toks = jax.random.randint(KEY, (B, S), 0, cfg.vocab_size)
    full, _ = jax.jit(model.forward_train)(params, {"tokens": toks})
    cache = model.init_cache(B, 32)
    _, cache = jax.jit(model.prefill)(params, {"tokens": toks[:, :S - 1]},
                                      cache)
    logits, _ = jax.jit(model.decode_step)(params, toks[:, S - 1:S], cache)
    np.testing.assert_allclose(np.asarray(logits[:, 0]),
                               np.asarray(full[:, -1]), atol=5e-3, rtol=5e-3)


# ---------------------------------------------------------------------------
# microbatching via scan-xs (§Perf B2) — grad equivalence
# ---------------------------------------------------------------------------

def test_unrolled_microbatches_match_scanned():
    from repro.train.train_step import init_train_state, make_train_step
    cfg = dataclasses.replace(reduce_for_smoke(get_config("olmo-1b")),
                              remat=False)
    model = build_model(cfg)
    batch = {
        "tokens": jax.random.randint(KEY, (4, 16), 0, cfg.vocab_size),
        "targets": jax.random.randint(KEY, (4, 16), 0, cfg.vocab_size),
    }
    s1 = init_train_state(model, KEY)
    s2 = init_train_state(model, KEY)
    scanned = make_train_step(model, microbatches=2)(s1, batch)
    unrolled = make_train_step(model, microbatches=2, unroll=True)(s2, batch)
    np.testing.assert_allclose(float(scanned[1]["loss"]),
                               float(unrolled[1]["loss"]), rtol=1e-5)
    for a, b in zip(jax.tree.leaves(scanned[0].params),
                    jax.tree.leaves(unrolled[0].params)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   atol=1e-5, rtol=1e-4)


# ---------------------------------------------------------------------------
# multi-device numerics (subprocess: 8 host devices)
# ---------------------------------------------------------------------------

_SUBPROCESS_PROG = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import dataclasses, jax, jax.numpy as jnp, numpy as np
    from repro.configs import get_config
    from repro.models import build_model, reduce_for_smoke
    from repro.models import moe as MOE

    mesh = jax.make_mesh((2, 4), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    key = jax.random.PRNGKey(0)

    # --- EP MoE == global MoE ---
    cfg = dataclasses.replace(reduce_for_smoke(get_config("granite-moe-1b-a400m")),
                              param_dtype="float32")
    params = MOE.moe_init(cfg, key, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, cfg.d_model))
    with jax.set_mesh(mesh):
        y_g, _ = jax.jit(lambda p, x: MOE._moe_apply_global(cfg, p, x))(params, x)
        cfg_ep = dataclasses.replace(cfg, moe_impl="ep")
        y_e, _ = jax.jit(lambda p, x: MOE.moe_apply(cfg_ep, p, x))(params, x)
    assert float(jnp.max(jnp.abs(y_g - y_e))) < 1e-4, "EP mismatch"

    # --- batch-sharded attention == baseline ---
    cfg = dataclasses.replace(reduce_for_smoke(get_config("smollm-360m")),
                              param_dtype="float32")
    model = build_model(cfg)
    p = model.init(key)
    batch = {"tokens": jax.random.randint(key, (8, 16), 0, cfg.vocab_size)}
    with jax.set_mesh(mesh):
        base, _ = jax.jit(model.forward_train)(p, batch)
        cfg_b = dataclasses.replace(cfg, attn_batch_shard=True,
                                    activation_sharding=True)
        model_b = build_model(cfg_b)
        opt, _ = jax.jit(model_b.forward_train)(p, batch)
    assert float(jnp.max(jnp.abs(base - opt))) < 1e-4, "abshard mismatch"
    print("SUBPROCESS_OK")
""")


def test_multidevice_variant_numerics():
    res = subprocess.run(
        [sys.executable, "-c", _SUBPROCESS_PROG],
        capture_output=True, text=True, timeout=420,
        # JAX_PLATFORMS must survive the env replacement: without it jax
        # probes for accelerator plugins in the child and can hang forever.
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
             "JAX_PLATFORMS": os.environ.get("JAX_PLATFORMS", "cpu")},
        cwd=str(__import__("pathlib").Path(__file__).parent.parent))
    assert "SUBPROCESS_OK" in res.stdout, res.stdout + res.stderr


# ---------------------------------------------------------------------------
# sequence-sharded KV + ZeRO-1 sharding specs build for the affected trees
# ---------------------------------------------------------------------------

def test_kvseq_and_zero1_specs():
    from repro.distributed import sharding as SH
    from repro.launch.mesh import make_host_mesh
    from repro.train.optimizer import adamw_init

    mesh = make_host_mesh(data=1, model=1)
    cfg = get_config("gemma2-9b")
    model = build_model(cfg)
    cache_abs = jax.eval_shape(lambda: model.init_cache(8, 1024))
    sh_heads = SH.cache_shardings(mesh, cache_abs, kv_shard="heads")
    sh_seq = SH.cache_shardings(mesh, cache_abs, kv_shard="seq")
    assert len(jax.tree.leaves(sh_heads)) == len(jax.tree.leaves(sh_seq))

    cfg_s = reduce_for_smoke(cfg)
    model_s = build_model(cfg_s)
    params = jax.eval_shape(model_s.init, KEY)
    opt = jax.eval_shape(lambda: adamw_init(params))
    sh = SH.opt_state_shardings(mesh, opt.m, zero1=True)
    assert len(jax.tree.leaves(sh)) == len(jax.tree.leaves(opt.m))
