"""Record the small CPU trace that tests/bench/test_trace_reduce.py reads.

    JAX_PLATFORMS=cpu python tests/bench/data/record_trace.py

Three annotated steps of a jitted ``step`` (a matrix product and a
tanh), each followed by a host sleep that leaves the device idle, the
way `bench/run.py` annotates the fused serve step.
"""

import glob
import os
import shutil
import tempfile
import time

import jax
import jax.numpy as jnp

HERE = os.path.dirname(os.path.abspath(__file__))


def main():
    @jax.jit
    def step(x):
        return jnp.tanh(x @ x)

    x = jnp.ones((384, 384), jnp.float32)
    step(x).block_until_ready()
    tmp = tempfile.mkdtemp()
    jax.profiler.start_trace(tmp)
    for _ in range(3):
        with jax.profiler.TraceAnnotation("bench_step"):
            step(x).block_until_ready()
        time.sleep(0.004)
    with jax.profiler.TraceAnnotation("bench_step"):
        step(x).block_until_ready()
    jax.profiler.stop_trace()
    src = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)
    shutil.copy(src[0], os.path.join(HERE, "cpu_trace.xplane.pb"))
    shutil.rmtree(tmp)


if __name__ == "__main__":
    main()
