"""Per-kernel allclose sweeps vs the pure-jnp oracles (interpret mode).

Shapes / dtypes / feature flags swept per kernel, as required for (c).
"""
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.common import random_problem_arrays
from repro.kernels import ops

RNG = np.random.default_rng(0)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,S,H,KV,D", [
    (1, 128, 4, 4, 64),       # MHA
    (2, 256, 8, 2, 64),       # GQA 4:1
    (1, 200, 4, 2, 80),       # ragged seq, zamba head_dim
    (1, 256, 16, 8, 128),     # gemma2-like ratio
    (2, 64, 15, 5, 64),       # smollm heads (non-pow2)
])
def test_flash_attention_shapes(B, S, H, KV, D):
    q = jnp.asarray(RNG.normal(0, 1, (B, S, H, D)), jnp.float32)
    k = jnp.asarray(RNG.normal(0, 1, (B, S, KV, D)), jnp.float32)
    v = jnp.asarray(RNG.normal(0, 1, (B, S, KV, D)), jnp.float32)
    o_ref = ops.flash_attention(q, k, v, impl="xla")
    o_pal = ops.flash_attention(q, k, v, impl="pallas", interpret=True)
    np.testing.assert_allclose(o_pal, o_ref, atol=3e-5, rtol=3e-5)


@pytest.mark.parametrize("window,softcap,causal", [
    (None, None, True),
    (64, None, True),
    (None, 50.0, True),
    (64, 50.0, True),
    (None, None, False),
])
def test_flash_attention_features(window, softcap, causal):
    B, S, H, KV, D = 1, 256, 4, 2, 64
    q = jnp.asarray(RNG.normal(0, 1, (B, S, H, D)), jnp.float32)
    k = jnp.asarray(RNG.normal(0, 1, (B, S, KV, D)), jnp.float32)
    v = jnp.asarray(RNG.normal(0, 1, (B, S, KV, D)), jnp.float32)
    kw = dict(causal=causal, window=window, softcap=softcap)
    o_ref = ops.flash_attention(q, k, v, impl="xla", **kw)
    o_pal = ops.flash_attention(q, k, v, impl="pallas", interpret=True, **kw)
    np.testing.assert_allclose(o_pal, o_ref, atol=3e-5, rtol=3e-5)


def test_flash_attention_bf16():
    B, S, H, KV, D = 1, 128, 4, 2, 64
    q = jnp.asarray(RNG.normal(0, 1, (B, S, H, D)), jnp.bfloat16)
    k = jnp.asarray(RNG.normal(0, 1, (B, S, KV, D)), jnp.bfloat16)
    v = jnp.asarray(RNG.normal(0, 1, (B, S, KV, D)), jnp.bfloat16)
    o_ref = ops.flash_attention(q, k, v, impl="xla")
    o_pal = ops.flash_attention(q, k, v, impl="pallas", interpret=True)
    np.testing.assert_allclose(np.asarray(o_pal, np.float32),
                               np.asarray(o_ref, np.float32),
                               atol=3e-2, rtol=3e-2)


# ---------------------------------------------------------------------------
# mamba SSD chunk scan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,S,H,P,N", [
    (1, 128, 2, 64, 64),
    (2, 256, 4, 64, 64),
    (1, 384, 8, 32, 16),      # reduced-config dims
])
def test_mamba_scan_shapes(B, S, H, P, N):
    x = jnp.asarray(RNG.normal(0, 1, (B, S, H, P)), jnp.float32)
    dt = jnp.asarray(RNG.uniform(1e-3, 0.1, (B, S, H)), jnp.float32)
    A = -jnp.asarray(RNG.uniform(0.5, 2.0, H), jnp.float32)
    Bm = jnp.asarray(RNG.normal(0, 1, (B, S, N)), jnp.float32)
    Cm = jnp.asarray(RNG.normal(0, 1, (B, S, N)), jnp.float32)
    D = jnp.asarray(RNG.uniform(0.5, 1.5, H), jnp.float32)
    y_ref, h_ref = ops.mamba_scan(x, dt, A, Bm, Cm, D, impl="xla")
    y_pal, h_pal = ops.mamba_scan(x, dt, A, Bm, Cm, D, impl="pallas", interpret=True)
    np.testing.assert_allclose(y_pal, y_ref, atol=5e-5, rtol=5e-5)
    np.testing.assert_allclose(h_pal, h_ref, atol=5e-5, rtol=5e-5)


def test_mamba_scan_carry_state():
    """Chunked scan with a carried-in state h0 matches the reference."""
    B, S, H, P, N = 1, 256, 2, 64, 64
    x = jnp.asarray(RNG.normal(0, 1, (B, S, H, P)), jnp.float32)
    dt = jnp.asarray(RNG.uniform(1e-3, 0.1, (B, S, H)), jnp.float32)
    A = -jnp.asarray(RNG.uniform(0.5, 2.0, H), jnp.float32)
    Bm = jnp.asarray(RNG.normal(0, 1, (B, S, N)), jnp.float32)
    Cm = jnp.asarray(RNG.normal(0, 1, (B, S, N)), jnp.float32)
    D = jnp.asarray(RNG.uniform(0.5, 1.5, H), jnp.float32)
    h0 = jnp.asarray(RNG.normal(0, 0.3, (B, H, P, N)), jnp.float32)
    y_ref, h_ref = ops.mamba_scan(x, dt, A, Bm, Cm, D, h0, impl="xla")
    y_pal, h_pal = ops.mamba_scan(x, dt, A, Bm, Cm, D, h0, impl="pallas", interpret=True)
    np.testing.assert_allclose(y_pal, y_ref, atol=5e-5, rtol=5e-5)
    np.testing.assert_allclose(h_pal, h_ref, atol=5e-5, rtol=5e-5)


def test_mamba_chunked_matches_recurrent():
    """The chunked algorithm equals the step-by-step recurrence."""
    from repro.models.mamba2 import ssd_step
    B, S, H, P, N = 1, 128, 2, 16, 16
    x = jnp.asarray(RNG.normal(0, 1, (B, S, H, P)), jnp.float32)
    dt = jnp.asarray(RNG.uniform(1e-3, 0.1, (B, S, H)), jnp.float32)
    A = -jnp.asarray(RNG.uniform(0.5, 2.0, H), jnp.float32)
    Bm = jnp.asarray(RNG.normal(0, 1, (B, S, N)), jnp.float32)
    Cm = jnp.asarray(RNG.normal(0, 1, (B, S, N)), jnp.float32)
    D = jnp.asarray(RNG.uniform(0.5, 1.5, H), jnp.float32)
    y_chunk, h_chunk = ops.mamba_scan(x, dt, A, Bm, Cm, D, impl="xla")

    h = jnp.zeros((B, H, P, N), jnp.float32)
    ys = []
    for t in range(S):
        y_t, h = ssd_step(h, x[:, t], dt[:, t], A, Bm[:, t], Cm[:, t], D)
        ys.append(y_t)
    y_rec = jnp.stack(ys, axis=1)
    np.testing.assert_allclose(y_chunk, y_rec, atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(h_chunk, h, atol=2e-4, rtol=2e-4)


# ---------------------------------------------------------------------------
# move_eval
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("N,T", [(64, 5), (300, 5), (500, 17), (1000, 128)])
def test_move_eval_matches_ref(N, T):
    args = random_problem_arrays(N, T, seed=N + T)
    d_ref = ops.move_eval(*args, impl="xla")
    d_pal = ops.move_eval(*args, impl="pallas", interpret=True)
    scale = float(jnp.max(jnp.abs(d_ref))) + 1e-9
    np.testing.assert_allclose(d_pal / scale, d_ref / scale, atol=1e-5)


def test_move_eval_delta_is_exact():
    """delta[n, t] must equal objective(after move) - objective(before)."""
    from repro.core import generate_cluster, objective
    from repro.core.delta import move_delta_cost
    from repro.core.solver_local import _weights_vector
    from repro.core.problem import tier_loads

    cluster = generate_cluster(num_apps=40, seed=2)
    p = cluster.problem
    x = p.assignment0
    util, tasks = tier_loads(p, x)
    delta = move_delta_cost(p.demand, p.tasks, p.criticality, x,
                            p.assignment0, p.capacity, p.task_limit,
                            p.ideal_frac, p.ideal_task_frac, util, tasks,
                            _weights_vector(p))
    base = float(objective(p, x))
    rng = np.random.default_rng(0)
    for _ in range(20):
        n = int(rng.integers(p.num_apps))
        t = int(rng.integers(p.num_tiers))
        moved = x.at[n].set(t)
        true_delta = float(objective(p, moved)) - base
        assert abs(float(delta[n, t]) - true_delta) < 1e-3 * max(
            1.0, abs(true_delta)), (n, t)


@pytest.mark.parametrize("N,T,moves_left", [(300, 5, 5), (500, 17, 0)])
def test_move_eval_best_matches_ref(N, T, moves_left):
    """Fused sweep+mask+argmin kernel vs the core.delta oracle."""
    args = random_problem_arrays(N, T, seed=N + T)
    rng = np.random.default_rng(N)
    feas = jnp.asarray(rng.random((N, T)) > 0.2)
    ml = jnp.int32(moves_left)
    s_ref, t_ref = ops.move_eval_best(*args, feas, ml, impl="xla")
    s_pal, t_pal = ops.move_eval_best(*args, feas, ml, impl="pallas", interpret=True)
    finite = np.isfinite(np.asarray(s_ref))
    # same apps marked infeasible (+inf)
    assert np.array_equal(np.isfinite(np.asarray(s_pal)), finite)
    scale = float(jnp.max(jnp.abs(jnp.where(finite, s_ref, 0.0)))) + 1e-9
    np.testing.assert_allclose(np.asarray(s_pal)[finite] / scale,
                               np.asarray(s_ref)[finite] / scale, atol=1e-5)
    assert np.array_equal(np.asarray(t_pal)[finite], np.asarray(t_ref)[finite])


def test_solver_with_fused_best_pallas(cluster300):
    """Batched LocalSearch end-to-end on the fused-best kernel path."""
    import functools
    from repro.core import LocalSearchConfig, solve_local, validate
    from repro.kernels.move_eval import move_eval_best_pallas

    p = cluster300.problem
    res = solve_local(
        p, LocalSearchConfig(max_iters=8, batch_moves=8),
        move_best_fn=functools.partial(move_eval_best_pallas, interpret=True))
    assert validate(p, res.assignment).ok
    res_ref = solve_local(p, LocalSearchConfig(max_iters=8, batch_moves=8))
    assert np.array_equal(np.asarray(res.assignment),
                          np.asarray(res_ref.assignment))


def test_solver_with_pallas_move_eval(cluster300):
    """LocalSearch runs end-to-end on the Pallas kernel (interpret mode)."""
    import functools
    from repro.core import LocalSearchConfig, solve_local, validate
    from repro.kernels.move_eval import move_eval_pallas

    p = cluster300.problem
    res = solve_local(p, LocalSearchConfig(max_iters=8),
                      move_eval_fn=functools.partial(move_eval_pallas,
                                                     interpret=True))
    assert validate(p, res.assignment).ok


# ---------------------------------------------------------------------------
# flash decode
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,Smax,H,KV,D,kv_len,softcap", [
    (2, 512, 4, 2, 64, 300, None),
    (1, 1024, 8, 8, 128, 1024, None),     # MHA, cache full
    (2, 640, 16, 8, 80, 17, 50.0),        # nearly-empty cache + softcap
    (1, 512, 15, 5, 64, 400, None),       # smollm head counts
])
def test_flash_decode_matches_ref(B, Smax, H, KV, D, kv_len, softcap):
    rng = np.random.default_rng(B * Smax + kv_len)
    q = jnp.asarray(rng.normal(0, 1, (B, 1, H, D)), jnp.float32)
    k = jnp.asarray(rng.normal(0, 1, (B, Smax, KV, D)), jnp.float32)
    v = jnp.asarray(rng.normal(0, 1, (B, Smax, KV, D)), jnp.float32)
    o_ref = ops.flash_decode(q, k, v, kv_len, softcap=softcap, impl="xla")
    o_pal = ops.flash_decode(q, k, v, kv_len, softcap=softcap, impl="pallas", interpret=True)
    np.testing.assert_allclose(np.asarray(o_pal), np.asarray(o_ref),
                               atol=3e-5, rtol=3e-5)


def test_flash_decode_bf16():
    rng = np.random.default_rng(7)
    B, Smax, H, KV, D = 2, 512, 4, 2, 64
    q = jnp.asarray(rng.normal(0, 1, (B, 1, H, D)), jnp.bfloat16)
    k = jnp.asarray(rng.normal(0, 1, (B, Smax, KV, D)), jnp.bfloat16)
    v = jnp.asarray(rng.normal(0, 1, (B, Smax, KV, D)), jnp.bfloat16)
    o_ref = ops.flash_decode(q, k, v, 200, impl="xla")
    o_pal = ops.flash_decode(q, k, v, 200, impl="pallas", interpret=True)
    np.testing.assert_allclose(np.asarray(o_pal, np.float32),
                               np.asarray(o_ref, np.float32),
                               atol=3e-2, rtol=3e-2)
