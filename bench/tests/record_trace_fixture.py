"""Records `fixtures/cpu_window.xplane.pb`, the small trace that
`test_bench_trace.py` reduces: on the CPU backend, inside one
`bench.window` span, three rounds of two jitted programs `sample` and
`exchange`, each round followed by a 30 ms `bench.sleep` span.

    JAX_PLATFORMS=cpu python3 bench/tests/record_trace_fixture.py
"""
import glob
import os
import shutil
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from bench import trace  # noqa: E402

ROUNDS = 3
SLEEP_S = 0.03


@jax.jit
def sample(x):
    return jnp.sin(x) @ x


@jax.jit
def exchange(x):
    return jnp.cumsum(x, axis=0)


def main():
    x = jnp.ones((512, 512))
    sample(x).block_until_ready()
    exchange(x).block_until_ready()
    out = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(out, profiler_options=opts)
    with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
        for _ in range(ROUNDS):
            with jax.profiler.TraceAnnotation("bench.job"):
                sample(x).block_until_ready()
                exchange(x).block_until_ready()
            with jax.profiler.TraceAnnotation("bench.sleep"):
                time.sleep(SLEEP_S)
    jax.profiler.stop_trace()
    (src,) = glob.glob(os.path.join(out, "**", "*.xplane.pb"),
                       recursive=True)
    dst = os.path.join(HERE, "fixtures", "cpu_window.xplane.pb")
    os.makedirs(os.path.dirname(dst), exist_ok=True)
    shutil.copy(src, dst)
    shutil.rmtree(out)
    print(dst, os.path.getsize(dst))


if __name__ == "__main__":
    main()
