#!/usr/bin/env python3
"""Record the small trace that test_trace.py reduces (run on the chip):

    chiprun -- python3 benchmark/tests/record_trace.py chiprun_out/tiny_tpu.xplane.pb

Three calls of a jitted loop (a `while` with fusions nested in it), 50 ms
of sleep after each, inside one `bench:window` annotation.
"""

import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp

from benchmark.harness import trace


def main(out: str) -> None:
    @jax.jit
    def step(x):
        return jax.lax.fori_loop(0, 4, lambda _, a: jnp.tanh(a @ a) * 0.5, x)

    x = jnp.ones((256, 256), jnp.float32)
    step(x).block_until_ready()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    logdir = tempfile.mkdtemp()
    jax.profiler.start_trace(logdir, profiler_options=options)
    with jax.profiler.TraceAnnotation(trace.WINDOW):
        for _ in range(3):
            step(x).block_until_ready()
            time.sleep(0.05)
    jax.profiler.stop_trace()
    shutil.copy(trace.find_xplane(logdir), out)
    shutil.rmtree(logdir)
    print(jax.devices()[0].device_kind, trace.reduce(trace.read_planes(out)))


if __name__ == "__main__":
    main(sys.argv[1])
