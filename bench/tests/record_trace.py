"""Record the small profiler trace that ``test_trace_reduce.py`` reads.

    python3 bench/tests/record_trace.py <out_dir>

Runs a few jitted calls between the harness's own host spans
(``bench.window``, ``bench.step``, ``bench.sleep``) on the default device and
writes the ``.xplane.pb`` to ``<out_dir>/small.xplane.pb``.  Recorded once on
a TPU v5e and committed as ``bench/tests/data/small.xplane.pb``.
"""

import pathlib
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp


def main(out_dir: str) -> None:
    solve = jax.jit(lambda x: jnp.tanh(x @ x.T).sum(axis=1))
    x = jnp.ones((512, 512), jnp.float32)
    solve(x).block_until_ready()
    tmp = tempfile.mkdtemp(prefix="bench_record_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(tmp, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.step"):
                solve(x).block_until_ready()
            with jax.profiler.TraceAnnotation("bench.sleep"):
                time.sleep(0.01)
    jax.profiler.stop_trace()
    out = pathlib.Path(out_dir)
    out.mkdir(parents=True, exist_ok=True)
    shutil.copy(sorted(pathlib.Path(tmp).rglob("*.xplane.pb"))[-1], out / "small.xplane.pb")
    shutil.rmtree(tmp, ignore_errors=True)
    print(f"wrote {out / 'small.xplane.pb'} on {jax.devices()[0].device_kind}")


if __name__ == "__main__":
    main(sys.argv[1])
