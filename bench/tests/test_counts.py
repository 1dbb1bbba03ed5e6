"""The yardstick's operation and byte counts against counts by hand."""
import math

import numpy as np
import pytest

import small  # noqa: F401
import harness
import reference


def _config(name):
    return harness.load_json(harness.BENCH / "configs" / f"{name}.json")


def _params(config):
    return sum(math.prod(s) for _, s, _, _ in
               reference.family(config).layout(config))


def test_parameter_counts():
    assert _params(_config("mamba2-130m")) == 167_610_816
    assert _params(_config("stablelm-2-1.6b.l4.v25k")) == 308_299_776


def test_mamba2_flops_per_token():
    c = _config("mamba2-130m")
    D, di, H, P, N, K, V, Q = 768, 1536, 24, 64, 128, 4, 50288, 256
    per_layer = (D * (2 * di + 2 * N + H) + di * D + K * (di + 2 * N)
                 + Q / 2 * N + Q / 2 * H * P + 2 * H * P * N)
    want = 6 * (24 * per_layer + D * V)
    got = reference.family(c).flops_per_token(c, 2048)
    assert got == pytest.approx(want, rel=1e-12)


def test_dense_flops_per_token():
    c = _config("stablelm-2-1.6b.l4.v25k")
    D, F, V, S = 2048, 5632, 25088, 2048
    per_layer = 4 * D * D + 2 * (S / 2) * D + 3 * D * F
    want = 6 * (4 * per_layer + D * V)
    assert reference.family(c).flops_per_token(c, S) == pytest.approx(want)


def test_wire_bytes_per_round():
    roof = harness.metric_module("wire_roofline")
    mix = {"strategy": {"bits": 4}}
    c = _config("mamba2-130m")
    n = _params(c)
    f32 = sum(math.prod(s) for _, s, d, _ in reference.family(c).layout(c)
              if np.dtype(d).itemsize == 4)
    # bf16 gradient 2 B (f32 leaves 4 B), qhat 4 B, q_new 4 B, code 0.5 B
    assert roof.required_bytes(c, mix) == pytest.approx(10.5 * n + 2 * f32)
