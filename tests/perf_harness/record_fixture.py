#!/usr/bin/env python3
"""Records the small trace the reducer's test reads (run on the chip):

  chiprun -- python3 tests/perf_harness/record_fixture.py chiprun_out/fixture

A tiny program: three steps of a jitted matmul chain with one named Pallas
kernel (`fixture_kernel`), each step under a `bench.step` host span, with a
30 ms sleep between steps (idle gaps), all inside `bench.window`.
"""

import glob
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main(out_dir):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    def double(x_ref, o_ref):
        o_ref[...] = x_ref[...] * 2.0

    @jax.jit
    def step(x):
        y = jnp.tanh(x @ x)
        y = pl.pallas_call(double, name="fixture_kernel",
                           out_shape=jax.ShapeDtypeStruct(y.shape, y.dtype),
                           interpret=jax.default_backend() != "tpu")(y)
        return y @ x

    x = jnp.ones((1024, 1024), jnp.float32) * 1e-3
    step(x).block_until_ready()
    tmp = os.path.join(out_dir, "raw")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(tmp, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.step"):
                step(x).block_until_ready()
            with jax.profiler.TraceAnnotation("bench.sleep"):
                time.sleep(0.03)
    jax.profiler.stop_trace()
    path = sorted(glob.glob(os.path.join(
        tmp, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    shutil.copy(path, os.path.join(out_dir, "fixture.xplane.pb"))
    shutil.rmtree(tmp)
    print(os.path.getsize(os.path.join(out_dir, "fixture.xplane.pb")),
          "bytes", jax.devices()[0].device_kind)


if __name__ == "__main__":
    os.makedirs(sys.argv[1], exist_ok=True)
    main(sys.argv[1])
