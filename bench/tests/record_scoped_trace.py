"""Record the scoped trace under ``bench/tests/data/`` on a TPU.

    python bench/tests/record_scoped_trace.py <out_dir>

The rounds of ``record_trace.py`` with the program's span names: the Pallas
kernel ``wire_probe`` runs inside ``laq.wire.quantize`` and the matmul
inside ``laq.fwd_bwd``.  Writes ``scoped.xplane.pb``, the trace, and
``scoped.hlo.txt``, the optimized HLO of the step that ran, from which
``bench/scopes.py`` names each device operation's span.
"""
import glob
import os
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation

from record_trace import ROUNDS, SLEEP_S, wire_probe


def step(x, w):
    with jax.named_scope("laq.wire.quantize"):
        y = wire_probe(x)
    with jax.named_scope("laq.fwd_bwd"):
        return y @ w


def main(out_dir):
    if jax.devices()[0].platform != "tpu":
        sys.exit("no TPU: nothing recorded")
    x = jnp.ones((1024, 1024), jnp.float32)
    w = jnp.ones((1024, 1024), jnp.float32) / 1024
    compiled = jax.jit(step).lower(x, w).compile()
    compiled(x, w).block_until_ready()
    tmp = tempfile.mkdtemp()
    jax.profiler.start_trace(tmp)
    with TraceAnnotation("bench.window"):
        for _ in range(ROUNDS):
            with TraceAnnotation("bench.batch"):
                time.sleep(SLEEP_S)
            with TraceAnnotation("bench.dispatch"):
                y = compiled(x, w)
            y.block_until_ready()
    jax.profiler.stop_trace()
    path = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)[0]
    os.makedirs(out_dir, exist_ok=True)
    # source paths without the checkout's place: relative in the HLO, and
    # blanked to the same length in the trace (its strings are
    # length-prefixed)
    checkout = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))) + os.sep
    with open(path, "rb") as f:
        pb = f.read()
    with open(os.path.join(out_dir, "scoped.xplane.pb"), "wb") as f:
        f.write(pb.replace(checkout.encode(),
                           b"." * (len(checkout) - 1) + b"/"))
    with open(os.path.join(out_dir, "scoped.hlo.txt"), "w") as f:
        f.write(compiled.as_text().replace(checkout, ""))
    shutil.rmtree(tmp)


if __name__ == "__main__":
    main(sys.argv[1])
