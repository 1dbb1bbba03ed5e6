"""Named spans of the sharded LAQ step (``launch/train.py``).

Each span is a ``jax.named_scope``: it writes its name into the
``op_name`` metadata of every HLO instruction traced inside it and adds no
operation, so a compiled step with spans is the same program as one
without.  After XLA fuses, a fusion carries its root's ``op_name``.  An
instruction belongs to the innermost ``laq.*`` name in its ``op_name``:
the wire's quantize pass called from inside ``laq.aggregate`` is
``laq.wire.quantize``.  A profiler trace of the step names each device
operation by its HLO instruction, so the device time of a round splits by
span (``bench/scopes.py``).

Spans and the code each covers:

* ``laq.fwd_bwd`` — the loss and its gradient at every evaluation point:
  the current parameters, each microbatch of the fold, the SVRG anchor and
  its correction, the WK2 stale side.
* ``laq.lazy_rule`` — ``worker_update`` (criterion moments, skip decision,
  clocks, bits), the history of the parameter motion, and the round's
  counters and metrics.
* ``laq.shared_block`` — inside ``laq.fwd_bwd``, a hybrid model's shared
  attention block with its invocation's adapter and linear
  (``models/stack.py shared_block``), forward, recomputation and backward.
* ``laq.wire.quantize`` — the wire backends' radius and quantize
  primitives (``core/wire.py``), the Pallas kernels and their operand
  casts and pads included.
* ``laq.wire.codec`` — the byte layout between the kernels and the leaf.
  On the whole-leaf wire: the flattening and packing of the codes where
  no kernel packs them (``pack_flat``), and the slice and reshape of the
  decoded f32 sum to the leaf.  On the axis codec: the flatten and slice
  of the kernels' code rows (``kernels/``), their reshape to the leaf's
  shape, and ``pack_codes_along_axis`` / ``unpack_codes_along_axis``.
* ``laq.aggregate`` — the exchange, the decode kernel (``dequant_acc``)
  or the dequantize and masked sum of the packed wire, the float wire's
  ``psum``, and the server aggregate's add and store.
* ``laq.optimizer`` — ``optimizer.update`` and the parameter motion
  ``||theta^{k+1} - theta^k||^2`` (XLA fuses its reduction into the update's
  sweep; a fusion carries one span).
"""
from __future__ import annotations

import functools

import jax

FWD_BWD = "laq.fwd_bwd"
SHARED_BLOCK = "laq.shared_block"
LAZY_RULE = "laq.lazy_rule"
WIRE_QUANTIZE = "laq.wire.quantize"
WIRE_CODEC = "laq.wire.codec"
AGGREGATE = "laq.aggregate"
OPTIMIZER = "laq.optimizer"

SPANS = (FWD_BWD, SHARED_BLOCK, LAZY_RULE, WIRE_QUANTIZE, WIRE_CODEC,
         AGGREGATE, OPTIMIZER)


def scoped(name: str):
    """Decorator: the function runs inside span ``name`` (the scope is
    made at each call, not when the function is defined)."""
    def wrap(fn):
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)
        return inner
    return wrap
