"""Jit'd public wrappers around the Pallas wire kernels.

Handles padding to the kernel block size, flat<->leaf reshaping, and backend
selection: interpret=True on CPU (the validation container), compiled Pallas
on TPU.  Covers the full adaptive-LAQ width grid: b in {2, 4, 8} packs
4 / 2 / 1 codes per byte.

The production entry point is the ``fused`` wire backend in
``repro.core.wire``, which routes the per-worker hot loop through
:func:`absmax` (pass 1) and :func:`quantize_pack_fused` (pass 2) on TPU and
through an op-for-op jnp lowering of the same two-pass algorithm on CPU,
where interpret-mode Pallas would serialize the grid.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from repro.core.spans import WIRE_CODEC
from .quant_pack import (BLOCK, absmax_pallas, dequant_acc_pallas,
                         quantize_codes_adaptive_pallas,
                         quantize_pack_adaptive_pallas, quantize_pack_pallas,
                         quantize_pack_payload_pallas,
                         sparse_quant_pack_pallas)


def _on_cpu() -> bool:
    return jax.default_backend() == "cpu"


def _pad_to_block(flat):
    n = flat.shape[0]
    pad = (-n) % BLOCK
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    return flat, n


def _pad_pair(grad, qhat):
    g = grad.astype(jnp.float32).reshape(-1)
    qh = qhat.astype(jnp.float32).reshape(-1)
    g, n = _pad_to_block(g)
    qh, _ = _pad_to_block(qh)
    return g, qh, n


@functools.partial(jax.jit, static_argnames=("interpret",))
def absmax(grad, qhat, *, interpret: bool | None = None):
    """Pass 1: R = ||grad - qhat||_inf without materializing the diff.

    grad/qhat f32 (any shape, flattened); returns a f32 scalar.  Zero
    padding is harmless (pad diff is 0, abs-max >= 0).
    """
    if interpret is None:
        interpret = _on_cpu()
    g, qh, _ = _pad_pair(grad, qhat)
    partial_max = absmax_pallas(g, qh, interpret=interpret)
    return jnp.max(partial_max)


@functools.partial(jax.jit, static_argnames=("bits", "interpret"))
def quantize_pack_fused(grad, qhat, R, bits: int, *,
                        interpret: bool | None = None):
    """Pass 2: fused quantize+pack with moment side-outputs.

    grad/qhat f32 [n], R scalar.  Returns ``(packed uint8
    [ceil(n/blk)*blk/ROW, ROW*bits/8], delta f32 [n], q_new f32 [n], err_sq,
    innovation_sq)``: the payload in the kernel's rows, as
    :func:`quantize_pack` emits it (:func:`dequant_acc` takes them stacked
    over workers as they are; callers that want flat bytes flatten them),
    and the scalar moments, the block-partial sums of ||grad - q_new||^2
    and ||delta||^2 over the n real elements.
    """
    if interpret is None:
        interpret = _on_cpu()
    g, qh, n = _pad_pair(grad, qhat)
    packed, delta, q_new, err_p, inn_p = quantize_pack_pallas(
        g, qh, R.astype(jnp.float32).reshape(1), bits, n, interpret=interpret)
    return packed, delta[:n], q_new[:n], jnp.sum(err_p), jnp.sum(inn_p)


@functools.partial(jax.jit, static_argnames=("grid", "interpret"))
def quantize_pack_adaptive(grad, qhat, R, onehot, grid: tuple, *,
                           interpret: bool | None = None):
    """Adaptive pass 2: the width-grid-unrolled fused quantize+pack sweep.

    grad/qhat f32 (any shape, flattened), R scalar, ``onehot`` f32 [len(grid)]
    indicator of the selected width (adaptive.select_bits), ``grid`` the
    static ascending width tuple.  Returns ``(packed uint8
    [ceil(n/blk)*blk*max(grid)/8], delta f32 [n], q_new f32 [n], err_sq,
    innovation_sq)`` — the payload is provisioned at max(grid) bits (the
    sharded wire's static-shape convention); a pinned selection reproduces
    :func:`quantize_pack_fused` at that width bit-for-bit (each switch arm
    IS the static-width kernel body).
    """
    if interpret is None:
        interpret = _on_cpu()
    g, qh, n = _pad_pair(grad, qhat)
    sel = jnp.argmax(onehot).astype(jnp.int32).reshape(1)
    packed, delta, q_new, err_p, inn_p = quantize_pack_adaptive_pallas(
        g, qh, R.astype(jnp.float32).reshape(1), sel, grid, n,
        interpret=interpret)
    return packed, delta[:n], q_new[:n], jnp.sum(err_p), jnp.sum(inn_p)


@functools.partial(jax.jit, static_argnames=("grid", "interpret"))
def quantize_codes_adaptive(grad, qhat, R, onehot, grid: tuple, *,
                            interpret: bool | None = None):
    """Pass 2 for the adaptive sharded wire's axis codec: codes + delta in
    one sweep at the width ``onehot`` selects from the static ``grid`` (one
    ``lax.switch`` arm per width), codes left UNPACKED (the axis codec
    packs along the leaf's last dim itself — core/wire.py
    pack_codes_along_axis).

    grad/qhat f32 (any shape, flattened), R scalar.  Returns ``(codes uint8
    [n], delta f32 [n])`` sliced to the real length (callers reshape back
    to the leaf shape).
    """
    if interpret is None:
        interpret = _on_cpu()
    g, qh, n = _pad_pair(grad, qhat)
    sel = jnp.argmax(onehot).astype(jnp.int32).reshape(1)
    codes, delta = quantize_codes_adaptive_pallas(
        g, qh, R.astype(jnp.float32).reshape(1), sel, grid,
        interpret=interpret)
    with jax.named_scope(WIRE_CODEC):
        codes = codes[:n]
    return codes, delta[:n]


@functools.partial(jax.jit, static_argnames=("bits", "interpret"))
def quantize_pack(grad, qhat, R, bits: int, *, interpret: bool | None = None):
    """Flat leaf quantize+pack. grad/qhat (any shape, flattened), R scalar.

    Returns ``(packed uint8 [ceil(n/blk)*blk/ROW, ROW*bits/8], delta f32
    [n])``: the payload in the kernel's rows of ``ROW`` codes, which
    :func:`dequant_acc` takes stacked over workers as they are (their bytes
    in order are the flat little-end-first payload).  The payload-only
    kernel: no q_new/moment outputs, so payload-only callers don't pay
    their VMEM writes (use quantize_pack_fused when the criterion moments
    are wanted too; its payload is these rows, byte for byte).
    """
    if interpret is None:
        interpret = _on_cpu()
    g, qh, n = _pad_pair(grad, qhat)
    packed, delta = quantize_pack_payload_pallas(
        g, qh, R.astype(jnp.float32).reshape(1), bits, interpret=interpret)
    return packed, delta[:n]


@functools.partial(jax.jit, static_argnames=("bits", "interpret"))
def sparse_quantize_pack(vals, lo, hi, bits: int, *,
                         interpret: bool | None = None):
    """Sparse-pipeline quantize+pack on the gathered survivor values.

    vals f32 [k] (any k, padded to the kernel block here), lo/hi the
    sign-magnitude grid-endpoint sidecar scalars.  Returns ``(packed uint8
    [ceil(k/blk)*blk*bits/8], codes uint8 [k], deq f32 [k])`` — codes/deq
    sliced to the k real survivors; the packed buffer keeps the block pad
    (the canonical payload is re-packed from the sliced codes by
    core/wire.py's shared path).
    """
    if interpret is None:
        interpret = _on_cpu()
    v, k = _pad_to_block(vals.astype(jnp.float32).reshape(-1))
    packed, codes, deq = sparse_quant_pack_pallas(
        v, lo.astype(jnp.float32).reshape(1),
        hi.astype(jnp.float32).reshape(1), bits, interpret=interpret)
    return packed, codes[:k], deq[:k]


@functools.partial(jax.jit, static_argnames=("bits", "n", "interpret"))
def dequant_acc(packed, R, keep, bits: int, n: int, acc=None, *,
                interpret: bool | None = None):
    """Server-side unpack+dequant+accumulate over the worker dim.

    ``packed``: uint8 [W, nbytes], or [W, rows, ROW*bits/8] as
    :func:`quantize_pack` and :func:`quantize_pack_fused` emit them,
    BLOCK-padded either way.  Returns
    f32 [n].  ``acc`` (optional f32 [n], e.g. the server aggregate) is
    folded into the same pass: out = acc + sum_w keep_w * delta_w.
    """
    if interpret is None:
        interpret = _on_cpu()
    n_padded = math.prod(packed.shape[1:]) * 8 // bits
    acc_padded = None
    if acc is not None:
        acc_padded, _ = _pad_to_block(acc.astype(jnp.float32).reshape(-1))
        assert acc_padded.shape[0] == n_padded, (acc.shape, n_padded)
    out = dequant_acc_pallas(packed, R.astype(jnp.float32),
                             keep.astype(jnp.float32), bits, n_padded,
                             acc_padded, interpret=interpret)
    with jax.named_scope(WIRE_CODEC):
        return out.reshape(-1)[:n]
