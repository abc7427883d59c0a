"""What the chip bring-up relies on, checked on the CPU.

* importing the package starts no JAX backend (a process that imported
  ``repro`` has not claimed the chip);
* a Pallas kernel never drops into interpret mode on its own;
* the compile cache goes where ``JAX_COMPILATION_CACHE_DIR`` says, else to
  ``<checkout>/.jax_cache``;
* ``place_shard_batch`` leaves a shard batch alone off-mesh and on one
  device, spreads it over a ``jax.set_mesh`` mesh of four (virtual)
  devices, and the sharded solve gives the same answer as off-mesh;
* ``chip_smoke.py`` refuses a machine without a TPU, fails without the
  package beside it, and passes its CPU rehearsal at a small size.

The multi-device and fresh-interpreter checks run in child processes with
``JAX_PLATFORMS=cpu``.
"""

import json
import os
import pathlib
import shutil
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import pytest

from repro.distributed.sharding import place_shard_batch
from repro.kernels import ops

ROOT = pathlib.Path(__file__).resolve().parent.parent


def _run(args, *, env_extra=None, cwd=ROOT, timeout=600):
    env = {
        "PYTHONPATH": str(ROOT / "src"),
        "PATH": os.environ.get("PATH", "/usr/bin:/bin"),
        # Without it JAX probes for accelerator plugins in the child.
        "JAX_PLATFORMS": "cpu",
        "HOME": os.environ.get("HOME", str(cwd)),
    }
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, *args], capture_output=True, text=True,
        timeout=timeout, env=env, cwd=str(cwd), stdin=subprocess.DEVNULL)


def test_importing_repro_starts_no_backend():
    prog = textwrap.dedent("""
        import importlib, pkgutil
        import jax._src.xla_bridge as xb
        import repro
        for m in pkgutil.walk_packages(repro.__path__, "repro."):
            importlib.import_module(m.name)
            assert not xb.backends_are_initialized(), m.name
        print("NO_BACKEND_OK")
    """)
    res = _run(["-c", prog])
    assert "NO_BACKEND_OK" in res.stdout, res.stdout + res.stderr


def test_pallas_impl_off_tpu_needs_explicit_interpret():
    from benchmarks.common import random_problem_arrays

    args = random_problem_arrays(16, 4, seed=0)
    with pytest.raises(RuntimeError, match="interpret=True"):
        ops.move_eval(*args, impl="pallas")
    ref = ops.move_eval(*args, impl="xla")
    got = ops.move_eval(*args, impl="pallas", interpret=True)
    assert float(jnp.max(jnp.abs(ref - got))) <= 1e-4 * (float(jnp.max(jnp.abs(ref))) + 1.0)


@pytest.mark.parametrize("env_dir", [None, "custom"])
def test_compile_cache_directory(tmp_path, env_dir):
    prog = textwrap.dedent("""
        import jax
        from repro.compile_cache import enable_compile_cache
        print(enable_compile_cache())
        print(jax.config.jax_compilation_cache_dir)
    """)
    extra = {}
    if env_dir is not None:
        extra["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / env_dir)
    res = _run(["-c", prog], env_extra=extra)
    used, configured = res.stdout.split()[-2:]
    if env_dir is None:
        assert used == configured == str(ROOT / ".jax_cache")
    else:
        assert used == configured == str(tmp_path / env_dir)


@pytest.mark.parametrize("case", ["off_mesh", "one_device_mesh", "no_batch_leaf"])
def test_place_shard_batch_on_one_device(case):
    """In-process, on the one CPU device: off-mesh and under a one-device
    ``data`` mesh the tree and ``S`` pass through untouched; a tree with no
    ``[S, ...]`` leaf is refused."""
    tree = {"x": jnp.arange(12.0).reshape(3, 4), "k": jnp.arange(3)}
    if case == "no_batch_leaf":
        with pytest.raises(ValueError, match=r"\[S, \.\.\.\] leaf"):
            place_shard_batch({"scalar": jnp.float32(1.0), "meta": None})
        return
    if case == "off_mesh":
        placed, S = place_shard_batch(tree)
    else:
        with jax.set_mesh(jax.make_mesh((1,), ("data",))):
            placed, S = place_shard_batch(tree)
    assert placed is tree and S == 3


def test_shard_batch_spans_a_four_device_mesh():
    prog = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import jax, numpy as np
        from repro.distributed.sharding import place_shard_batch
        from repro.shard import partition_problem, plan_shards, solve_shards, synthetic_fleet
        from repro.shard.solve import ShardSolveConfig

        cluster = synthetic_fleet(600, num_tiers=16, seed=4)
        sharded = partition_problem(cluster.problem, plan_shards(cluster, 6))
        cfg = ShardSolveConfig(max_iters=32)
        off = solve_shards(sharded, cfg)
        off_dirty = solve_shards(sharded, cfg, dirty=[1, 4])
        # Off-mesh placement is a no-op.
        same, n = place_shard_batch(sharded.problems)
        assert same is sharded.problems and n == 6

        mesh = jax.make_mesh((4,), ("data",))
        with jax.set_mesh(mesh):
            placed, n = place_shard_batch(sharded.problems)
            on = solve_shards(sharded, cfg)
            on_dirty = solve_shards(sharded, cfg, dirty=[1, 4])
        devs = set(mesh.devices.flat)
        assert n == 6
        for leaf in jax.tree.leaves(placed):
            assert leaf.shape[0] == 8, leaf.shape          # padded to 2 x 4
            assert leaf.sharding.device_set == devs
        assert np.array_equal(np.asarray(on.x), np.asarray(off.x))
        assert np.array_equal(on.iterations, off.iterations)
        assert on.committed.sum() > 0
        assert np.array_equal(np.asarray(on_dirty.x), np.asarray(off_dirty.x))

        # A mesh without a batch axis is refused, not silently replicated.
        with jax.set_mesh(jax.make_mesh((4,), ("model",))):
            try:
                place_shard_batch(sharded.problems)
            except ValueError:
                pass
            else:
                raise AssertionError("placed a batch on a mesh with no batch axis")
        print("MESH_OK")
    """)
    res = _run(["-c", prog])
    assert "MESH_OK" in res.stdout, res.stdout + res.stderr


def _last_json(stdout: str):
    lines = [ln for ln in stdout.strip().splitlines() if ln.strip()]
    try:
        return json.loads(lines[-1]) if lines else None
    except json.JSONDecodeError:
        return None


def test_chip_smoke_refuses_a_machine_without_tpu():
    res = _run(["chip_smoke.py"])
    assert res.returncode == 2, res.stdout + res.stderr
    assert "no TPU found" in res.stderr
    assert _last_json(res.stdout) is None


def test_chip_smoke_fails_without_the_package(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path / "chip_smoke.py")
    res = _run(["chip_smoke.py", "--rehearse-on-cpu", "--apps", "2000", "--tiers", "16"],
               cwd=tmp_path, env_extra={"PYTHONPATH": ""})
    assert res.returncode != 0, res.stdout + res.stderr
    assert _last_json(res.stdout) is None


@pytest.mark.parametrize("chips", [1, 4])
def test_chip_smoke_cpu_rehearsal(tmp_path, chips):
    """The script end to end at 2,000 apps x 16 tiers (at 64 tiers a fleet
    this small leaves ~31 apps per tier and the cooperation bus can apply a
    mapping worse than the incumbent, which the reference check catches)."""
    res = _run(["chip_smoke.py", "--rehearse-on-cpu", "--chips", str(chips),
                "--apps", "2000", "--tiers", "16"],
               env_extra={"JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache")})
    assert res.returncode == 0, res.stdout[-4000:] + res.stderr[-4000:]
    last = _last_json(res.stdout)
    assert last == {"ok": True, "device": {"platform": "cpu", "kind": "cpu", "count": chips}}
    if chips == 1:
        assert "passes: full=" in res.stdout
        assert "retraces across the measured run (0 expected): " \
               "{'local_search': 0, 'shard_batch': 0, 'pack': 0}" in res.stdout
    else:
        assert "merged assignments equal: True" in res.stdout
