"""Record the small trace under ``bench/tests/data/`` on a TPU.

    python bench/tests/record_trace.py <out_dir>

Five rounds of a jitted step (a Pallas kernel named ``wire_probe``, then a
matmul), each dispatched inside a ``bench.dispatch`` span, with a 50 ms
host sleep in a ``bench.batch`` span before each round, all inside one
``bench.window`` span.  The device therefore idles about 50 ms per round,
and the tests check the reduction against that.
"""
import os
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.profiler import TraceAnnotation

ROUNDS, SLEEP_S = 5, 0.05


def _kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...] * 2.0 + 1.0


def wire_probe(x):
    return pl.pallas_call(_kernel, out_shape=jax.ShapeDtypeStruct(x.shape,
                                                                  x.dtype),
                          name="wire_probe")(x)


@jax.jit
def step(x, w):
    return wire_probe(x) @ w


def main(out_dir):
    if jax.devices()[0].platform != "tpu":
        sys.exit("no TPU: nothing recorded")
    x = jnp.ones((1024, 1024), jnp.float32)
    w = jnp.ones((1024, 1024), jnp.float32) / 1024
    step(x, w).block_until_ready()
    tmp = tempfile.mkdtemp()
    jax.profiler.start_trace(tmp)
    with TraceAnnotation("bench.window"):
        for _ in range(ROUNDS):
            with TraceAnnotation("bench.batch"):
                time.sleep(SLEEP_S)
            with TraceAnnotation("bench.dispatch"):
                y = step(x, w)
            y.block_until_ready()
    jax.profiler.stop_trace()
    import glob
    path = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)[0]
    os.makedirs(out_dir, exist_ok=True)
    shutil.copy(path, os.path.join(out_dir, "small.xplane.pb"))
    shutil.rmtree(tmp)


if __name__ == "__main__":
    main(sys.argv[1])
