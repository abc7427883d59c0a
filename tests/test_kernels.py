"""Per-kernel allclose sweeps vs the pure-jnp oracles (interpret mode).

Shapes / dtypes / feature flags swept per kernel, as required for (c).
"""
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.common import random_problem_arrays
from repro.kernels import ops


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
