"""Pure-jnp oracles for the LAQ wire kernels (the source of truth in tests).

Semantics mirror core/quantize.py exactly, specialized to flat float32
vectors (the kernels operate post-flattening, one leaf at a time).  Covers
both kernel passes: the absmax radius reduction and the fused
quantize+pack+moments sweep, plus the accumulating receive side.
"""
from __future__ import annotations

import jax.numpy as jnp


def absmax_ref(grad: jnp.ndarray, qhat: jnp.ndarray) -> jnp.ndarray:
    """R = ||grad - qhat||_inf, f32 scalar (pass-1 oracle)."""
    d = grad.astype(jnp.float32) - qhat.astype(jnp.float32)
    return jnp.max(jnp.abs(d)).astype(jnp.float32)


def quantize_pack_ref(diff: jnp.ndarray, R: jnp.ndarray, bits: int):
    """diff = grad - qhat, flat f32 [n] (n a multiple of 8/bits).

    Returns (packed uint8 [n*bits/8], q_new_delta f32 [n]) where
    q_new_delta = dequantize(codes) (the innovation actually applied).
    """
    assert bits in (1, 2, 4, 8)
    t = 1.0 / (2.0 ** bits - 1.0)
    levels = 2 ** bits - 1
    denom = jnp.where(R > 0, 2.0 * t * R, 1.0)
    q = jnp.floor((diff + R) / denom + 0.5)
    q = jnp.clip(q, 0, levels)
    q = jnp.where(R > 0, q, (levels + 1) // 2 * jnp.ones_like(q)).astype(jnp.uint8)
    delta = 2.0 * t * R * q.astype(jnp.float32) - R
    delta = jnp.where(R > 0, delta, jnp.zeros_like(delta))
    if bits == 8:
        packed = q
    else:
        cpb = 8 // bits
        packed = q[0::cpb]
        for j in range(1, cpb):
            packed = packed | (q[j::cpb] << (bits * j))
        packed = packed.astype(jnp.uint8)
    return packed, delta


def quantize_pack_fused_ref(grad: jnp.ndarray, qhat: jnp.ndarray,
                            R: jnp.ndarray, bits: int):
    """Oracle for the fused pass-2 kernel on *unpadded* inputs.

    Returns ``(packed, delta, q_new, err_sq, innovation_sq)`` with the same
    association order as the kernel: q_new = qhat + delta, err = grad - q_new.
    """
    g = grad.astype(jnp.float32)
    qh = qhat.astype(jnp.float32)
    n = g.shape[0]
    pad = (-n) % (8 // bits)          # packing needs whole bytes; the pad
    d = g - qh                        # codes are sliced off by the caller
    if pad:
        d = jnp.concatenate([d, jnp.zeros((pad,), jnp.float32)])
    packed, delta = quantize_pack_ref(d, R, bits)
    delta = delta[:n]
    q_new = qh + delta
    err = g - q_new
    return packed, delta, q_new, jnp.sum(err * err), jnp.sum(delta * delta)


def quantize_pack_adaptive_ref(grad: jnp.ndarray, qhat: jnp.ndarray,
                               R: jnp.ndarray, grid: tuple, sel: int):
    """Oracle for the adaptive (width-switched) fused pass-2 kernel on
    *unpadded* inputs: the static-width pipeline at ``bits = grid[sel]``,
    with the payload packed at the provision width ``max(grid)`` (codes
    < 2^b always fit the wider lanes; the sharded wire's static-shape
    provisioning convention).

    Returns ``(packed, delta, q_new, err_sq, innovation_sq)`` exactly like
    :func:`quantize_pack_fused_ref`.
    """
    bits = grid[sel]
    provision = max(grid)
    g = grad.astype(jnp.float32)
    qh = qhat.astype(jnp.float32)
    n = g.shape[0]
    t = 1.0 / (2.0 ** bits - 1.0)
    levels = 2 ** bits - 1
    d = g - qh
    pad = (-n) % (8 // provision)     # provision-width packing needs whole
    if pad:                           # bytes; pad diff is 0 like the kernel's
        d = jnp.concatenate([d, jnp.zeros((pad,), jnp.float32)])
    denom = jnp.where(R > 0, 2.0 * t * R, 1.0)
    q = jnp.floor((d + R) / denom + 0.5)
    q = jnp.clip(q, 0, levels)
    q = jnp.where(R > 0, q, (levels + 1) // 2 * jnp.ones_like(q)).astype(jnp.uint8)
    delta = 2.0 * t * R * q.astype(jnp.float32) - R
    delta = jnp.where(R > 0, delta, jnp.zeros_like(delta))
    if provision == 8:
        packed = q
    else:
        cpb = 8 // provision
        packed = q[0::cpb]
        for j in range(1, cpb):
            packed = packed | (q[j::cpb] << (provision * j))
        packed = packed.astype(jnp.uint8)
    delta = delta[:n]
    q_new = qh + delta
    err = g - q_new
    return packed, delta, q_new, jnp.sum(err * err), jnp.sum(delta * delta)


def dequant_acc_ref(packed: jnp.ndarray, R: jnp.ndarray, keep: jnp.ndarray,
                    bits: int, n: int, acc: jnp.ndarray = None):
    """packed [W, n*bits/8] uint8 (or [W, rows, ROW*bits/8], the payload
    kernel's rows, taken flattened), R [W], keep [W] -> sum_w delta_w,
    f32 [n].

    ``acc`` (optional f32 [n]) is the server aggregate folded into the sum.
    """
    assert bits in (1, 2, 4, 8)
    packed = packed.reshape(packed.shape[0], -1)
    t = 1.0 / (2.0 ** bits - 1.0)
    if bits < 8:
        mask = (1 << bits) - 1
        parts = [((packed >> (bits * j)) & mask).astype(jnp.float32)
                 for j in range(8 // bits)]
        codes = jnp.stack(parts, axis=-1).reshape(packed.shape[0], -1)[:, :n]
    else:
        codes = packed.astype(jnp.float32)[:, :n]
    Rw = R[:, None]
    delta = 2.0 * t * Rw * codes - Rw
    delta = jnp.where(Rw > 0, delta, 0.0) * keep[:, None]
    out = jnp.sum(delta, axis=0)
    return out if acc is None else acc.astype(jnp.float32) + out
