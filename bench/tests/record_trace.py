"""Record the small CPU trace that test_trace_reduce.py reads:

    JAX_PLATFORMS=cpu python bench/tests/record_trace.py

Three calls of one jitted program inside a ``bench.window`` span, the
second after a 50 ms ``bench.idle`` sleep; written to
``bench/tests/data/cpu_trace.xplane.pb``.
"""
import glob
import os
import shutil
import tempfile
import time

import jax
import jax.numpy as jnp

# the trace names source files without their directories
jax.config.update("jax_hlo_source_file_canonicalization_regex", ".*/")

OUT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data", "cpu_trace.xplane.pb")


@jax.jit
def step(x):
    return jnp.tanh(x @ x).sum()


def main():
    x = jnp.ones((256, 256), jnp.float32)
    step(x).block_until_ready()
    tmp = tempfile.mkdtemp()
    jax.profiler.start_trace(tmp)
    with jax.profiler.TraceAnnotation("bench.window"):
        step(x).block_until_ready()
        with jax.profiler.TraceAnnotation("bench.idle"):
            time.sleep(0.05)
        for _ in range(2):
            with jax.profiler.TraceAnnotation("bench.step"):
                step(x).block_until_ready()
    jax.profiler.stop_trace()
    shutil.copy(glob.glob(os.path.join(tmp, "plugins", "profile", "*", "*.xplane.pb"))[0], OUT)
    shutil.rmtree(tmp)


if __name__ == "__main__":
    main()
