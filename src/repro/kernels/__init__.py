"""Pallas TPU kernels for the LAQ wire hot loops (absmax radius reduction;
fused quantize+pack with moment side-outputs — fixed-width and
width-grid-unrolled adaptive variants; the payload-only quantize+pack that
the sharded step's fixed-width wire ships; the unpacked codes+delta sweep
of its adaptive axis codec; sparse-pipeline quantize+pack on gathered
survivors; unpack+dequant+accumulate).
ops.py: jit wrappers; ref.py: pure-jnp oracles."""
from .ops import (absmax, dequant_acc, quantize_codes_adaptive, quantize_pack,
                  quantize_pack_adaptive, quantize_pack_fused,
                  sparse_quantize_pack)
