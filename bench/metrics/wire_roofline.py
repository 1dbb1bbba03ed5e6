"""wire_roofline: the wire kernels' share of their HBM roofline, in %.

The bytes a round's wire must move on each chip, fixed by the shapes
whatever implements it: per parameter, one read of the gradient (in the
parameter's dtype) and of the float32 ``qhat``, one write of the float32
``q_new`` and of the b-bit code.  Over the wire kernels' device time
(``wire_kernel_ms``) at the chip's peak HBM bandwidth.  The wire does no
matmul, so bandwidth bounds it."""
import numpy as np

import harness
import reference

_kernel = harness.metric_module("wire_kernel_ms")


def required_bytes(config, mix):
    """HBM bytes one worker's wire must move per round."""
    bits = mix["strategy"]["bits"]
    total = 0.0
    for _, shape, dtype, _ in reference.family(config).layout(config):
        n = float(np.prod(shape))
        total += n * (np.dtype(dtype).itemsize + 4 + 4 + bits / 8)
    return total


def read(ctx):
    if ctx["trace"] is None or ctx["peaks"] is None:
        return None
    s = _kernel.kernel_seconds(ctx)
    if s <= 0:
        return None
    need = required_bytes(ctx["config"], ctx["mix"]) * ctx["rounds"]
    return 100.0 * need / (s * ctx["peaks"].hbm)
