"""wire_kernel_ms: device milliseconds per round in the wire's Pallas
kernels (``src/repro/kernels/quant_pack.py``), averaged over the chips used
(profiler trace).

Counted: the events on the device's ``XLA Ops`` line whose HLO text calls
``custom_call_target="tpu_custom_call"``, i.e. every Pallas kernel of the
step (``quantize_pack_fused``, ``quantize_codes_fused``, and the absmax
pass, which lowers as ``pallas_call``).  Every Pallas kernel of the compiled
step is a wire kernel today; one added elsewhere in the step would have to
be left out here by name.  The XLA ops around the kernels (casts, pads,
the packing of codes) are not counted: they are the wire's, but not its
kernels'."""
import re

import traces

WIRE_KERNELS = re.compile(r'custom_call_target="tpu_custom_call"')


def kernel_seconds(ctx):
    """Mean device seconds in wire kernels over the traced window."""
    tr = ctx["trace"]
    t0, t1 = ctx["span"]
    per = []
    for p in ctx["planes"]:
        ops = traces.clip(tr.devices[p], t0, t1)
        per.append(sum((e - s) for s, e, n in zip(ops.start, ops.end, ops.name)
                       if WIRE_KERNELS.search(n)) * 1e-9)
    return sum(per) / len(per)


def read(ctx):
    if ctx["trace"] is None:
        return None
    s = kernel_seconds(ctx)
    if s <= 0:
        return None
    return 1e3 * s / ctx["rounds"]
