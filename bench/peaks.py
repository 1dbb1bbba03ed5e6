"""Published per-chip peaks, by JAX's ``device_kind``.

Copied from ``src/repro/launch/roofline.py``.  TPU v5e: Google Cloud
documentation, "TPU v5e": 197 TFLOP/s in bf16, 16 GB of HBM at 819 GB/s.  A
device kind that is not here has no peaks: the harness refuses it.
"""
from typing import NamedTuple


class Peaks(NamedTuple):
    flops: float    # bf16 FLOP/s per chip
    hbm: float      # HBM bytes/s per chip


PEAKS = {
    "TPU v5 lite": Peaks(flops=197e12, hbm=819e9),
}
