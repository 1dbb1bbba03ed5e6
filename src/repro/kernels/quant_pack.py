"""Pallas TPU kernels for the LAQ wire hot loops — the fused two-pass pipeline.

The per-step elementwise sweep over the full gradient (quantize -> pack on
the send side; unpack -> dequantize -> accumulate over W workers on the
server side) is the paper's compute hot spot — it touches every parameter
every iteration.  On TPU these are VPU (vector-unit) kernels: the win is
fusing the whole send-side pipeline into two VMEM-tiled passes instead of
XLA's multi-kernel materialization of the intermediate diff / code / float
tensors.

Sweep-count accounting (one worker, one round, p-dim gradient):

    reference (core/quantize.py jnp path)       fused (this module)
    1. diff = grad - qhat  (materialized)       1. absmax: R = ||grad-qhat||_inf
    2. R = ||diff||_inf                            (in-kernel diff, no tensor)
    3. codes = quantize(diff, R)                2. quantize_pack: codes+pack,
    4. delta = dequantize(codes, R)                delta, q_new, and per-block
    5. q_new = qhat + delta                        partial sums for
    6. err_sq = ||grad - q_new||^2                 ||grad-q_new||^2 and
    7. innovation_sq = ||delta||^2                 ||delta||^2 — all in one
       (~5-6 full-gradient sweeps, 2+             VMEM pass
       materialized temporaries)

so the skip-criterion inputs (err_sq / innovation_sq) come for free with the
wire payload instead of costing two extra sweeps, and the radius reduction
no longer needs a materialized diff tensor.  The receive side
(``dequant_acc``) additionally takes an optional ``acc`` operand so the
server recursion ``agg^k = agg^{k-1} + sum_m delta_m`` folds into the same
pass instead of a separate p-length add.

Layout (what the TPU's Pallas lowering accepts): every flat vector is viewed
as rows of ``ROW = 1024`` elements, ``[n // ROW, ROW]``, and one grid step
takes ``ROWS = 32`` rows (``BLOCK = 32768`` elements).  A packed row holds
the same 1024 codes in ``ROW * b / 8`` bytes (a multiple of 128 lanes for
every b), so the packed byte order is the flat little-end-first order of
``kernels/ref.py``.  Scalars (the radius ``R``, the adaptive selection, the
sparse grid ends, the per-worker radii and keep bits) ride in SMEM.  The
per-block reductions are written as lane-aligned ``[1, 8, ROW]`` partial
tiles that the caller reduces; every kernel folds a block the same way, so
the moments of the fixed-width and the adaptive kernel agree bitwise.

Packing 8/b consecutive codes into a byte moves codes across lanes, which
the vector unit cannot do with elementwise ops.  The kernels do it on the
MXU instead: one 128*(8/b)-lane chunk of codes times a 0/1-times-2^(b*k)
matrix gives its 128 packed bytes, exactly (codes < 256 and powers of two
are exact in bf16, and the f32 accumulation of at most 8 such terms is
exact).  Unpacking is the transpose: a 0/1 matrix replicates each byte
over its 8/b lanes, and a per-lane shift and mask takes each code out.

The moment side-outputs mask the pad tail (pad codes dequantize to a
*nonzero* midpoint delta, so an unmasked sum would be wrong for
non-BLOCK-multiple lengths).  All shapes are padded upstream in ops.py.

Validated in interpret mode on CPU against kernels/ref.py and against the
pure-jnp fused lowering in core/wire.py (tests sweep shapes x bits x
radii); tests/test_tpu_compile.py compiles every kernel for a described
TPU v5e at real leaf widths.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.spans import WIRE_CODEC

LANES = 128           # vector lanes; a packed chunk is LANES bytes wide
ROW = 1024            # elements per row: 8 codes per byte at b=1 fill LANES
ROWS = 32             # rows per grid step (a uint8 tile is 32 x 128)
BLOCK = ROW * ROWS    # f32 elements per grid step (128 KiB per f32 operand)


def _smem():
    return pl.BlockSpec(memory_space=pltpu.SMEM)


def _rows_spec(width):
    return pl.BlockSpec((ROWS, width), lambda i: (i, 0))


def _part_spec():
    return pl.BlockSpec((1, 8, ROW), lambda i: (i, 0, 0))


def _part_shape(nb):
    return jax.ShapeDtypeStruct((nb, 8, ROW), jnp.float32)


def _fold(x):
    """[ROWS, ROW] -> [1, 8, ROW] partial (sublane-group adds only)."""
    return jnp.sum(x.reshape(ROWS // 8, 8, ROW), axis=0)[None]


def _valid(n_valid):
    """f32 [ROWS, ROW] mask of the real (non-pad) elements of this block."""
    r = jax.lax.broadcasted_iota(jnp.int32, (ROWS, ROW), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (ROWS, ROW), 1)
    idx = r * ROW + c + pl.program_id(0) * BLOCK
    return (idx < n_valid).astype(jnp.float32)


def _quant_codes(diff, R, bits):
    """Integral f32 codes (the core/quantize.py expression)."""
    t = 1.0 / (2.0 ** bits - 1.0)
    levels = 2 ** bits - 1
    denom = jnp.where(R > 0, 2.0 * t * R, 1.0)
    q = jnp.floor((diff + R) / denom + 0.5)
    q = jnp.clip(q, 0, levels)
    return jnp.where(R > 0, q, float((levels + 1) // 2))


def _dequant(qf, R, bits):
    t = 1.0 / (2.0 ** bits - 1.0)
    delta = 2.0 * t * R * qf - R
    return jnp.where(R > 0, delta, jnp.zeros_like(delta))


def _to_u8(qf):
    return qf.astype(jnp.int32).astype(jnp.uint8)


def _pack_rows(qf, bits):
    """Integral f32 codes [R, ROW] -> packed uint8 [R, ROW*bits/8] in the
    little-end-first byte order of kernels/ref.py (see module docstring)."""
    if bits == 8:
        return _to_u8(qf)
    cpb = 8 // bits
    width = LANES * cpb
    r = jax.lax.broadcasted_iota(jnp.int32, (width, LANES), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (width, LANES), 1)
    k = jnp.bitwise_and(r, cpb - 1)
    weight = jnp.zeros((width, LANES), jnp.float32)
    for j in range(cpb):
        weight = jnp.where(k == j, float(1 << (bits * j)), weight)
    weight = jnp.where(jnp.right_shift(r, cpb.bit_length() - 1) == c,
                       weight, 0.0).astype(jnp.bfloat16)
    q16 = qf.astype(jnp.bfloat16)
    chunks = [jnp.dot(q16[:, m * width:(m + 1) * width], weight,
                      preferred_element_type=jnp.float32)
              for m in range(ROW // width)]
    packed = chunks[0] if len(chunks) == 1 else jnp.concatenate(chunks, -1)
    return _to_u8(packed)


def _unpack_rows(pk, bits):
    """Packed uint8 [R, ROW*bits/8] -> f32 codes [R, ROW] (inverse of
    :func:`_pack_rows`)."""
    pf = pk.astype(jnp.int32).astype(jnp.float32)
    if bits == 8:
        return pf
    cpb = 8 // bits
    width = LANES * cpb
    r = jax.lax.broadcasted_iota(jnp.int32, (LANES, width), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (LANES, width), 1)
    spread = jnp.where(jnp.right_shift(c, cpb.bit_length() - 1) == r,
                       1.0, 0.0).astype(jnp.bfloat16)
    lane = jax.lax.broadcasted_iota(jnp.int32, (pk.shape[0], width), 1)
    shift = bits * jnp.bitwise_and(lane, cpb - 1)
    p16 = pf.astype(jnp.bfloat16)
    chunks = []
    for m in range(bits):                    # ROW*bits/8 bytes = bits chunks
        rep = jnp.dot(p16[:, m * LANES:(m + 1) * LANES], spread,
                      preferred_element_type=jnp.float32).astype(jnp.int32)
        code = jnp.bitwise_and(jnp.right_shift(rep, shift), (1 << bits) - 1)
        chunks.append(code.astype(jnp.float32))
    return chunks[0] if len(chunks) == 1 else jnp.concatenate(chunks, -1)


def _call(kernel, n, in_specs, out_specs, out_shape, interpret, *args):
    assert n % BLOCK == 0, n
    return pl.pallas_call(kernel, grid=(n // BLOCK,), in_specs=in_specs,
                          out_specs=out_specs, out_shape=out_shape,
                          interpret=interpret)(*args)


def _rows(flat):
    return flat.reshape(-1, ROW)


# ---------------------------------------------------------------------------
# Pass 1: blockwise |grad - qhat| max reduction (no materialized diff).
# ---------------------------------------------------------------------------

def _absmax_kernel(g_ref, qh_ref, out_ref):
    d = jnp.abs(g_ref[...] - qh_ref[...])
    out_ref[...] = jnp.max(d.reshape(ROWS // 8, 8, ROW), axis=0)[None]


def absmax_pallas(grad, qhat, *, interpret: bool = True):
    """grad, qhat: flat f32 [n] (n % BLOCK == 0).

    Returns per-block partial maxima f32 [n // BLOCK, 8, ROW]; the final
    (tiny) reduction happens in the caller.  Zero-padding is safe: the pad
    diff is 0 and abs-max is >= 0.
    """
    n = grad.shape[0]
    return _call(_absmax_kernel, n, [_rows_spec(ROW), _rows_spec(ROW)],
                 _part_spec(), _part_shape(n // BLOCK), interpret,
                 _rows(grad), _rows(qhat))


# ---------------------------------------------------------------------------
# Pass 2: quantize + pack + dequantized delta + q_new, with per-block moment
# side-outputs (the skip-criterion inputs).
# ---------------------------------------------------------------------------

def _moments(g, qh, delta, valid):
    # same association as the reference: q_new = qhat + delta, err = g - q_new
    qn = qh + delta
    err = (g - qn) * valid
    dv = delta * valid
    return qn, _fold(err * err), _fold(dv * dv)


def _quantize_pack_kernel(bits, n_valid, g_ref, qh_ref, R_ref, packed_ref,
                          delta_ref, qnew_ref, err_ref, inn_ref):
    R = R_ref[0]
    g = g_ref[...]
    qh = qh_ref[...]
    qf = _quant_codes(g - qh, R, bits)
    delta = _dequant(qf, R, bits)
    delta_ref[...] = delta
    qnew_ref[...], err_ref[...], inn_ref[...] = _moments(g, qh, delta,
                                                         _valid(n_valid))
    packed_ref[...] = _pack_rows(qf, bits)


def quantize_pack_pallas(grad, qhat, R, bits: int, n_valid: int, *,
                         interpret: bool = True):
    """grad, qhat: flat f32 [n] (n % BLOCK == 0), R: f32 [1],
    n_valid: static count of real (non-pad) elements.

    Returns ``(packed uint8 [n // ROW, ROW*bits/8], delta f32 [n], q_new
    f32 [n], err_part, inn_part)`` — the payload stays in the kernel's rows
    (on the TPU a 1-D uint8 reshape of them is a relayout of its own; their
    bytes in order are the flat payload); the partials (f32 [n//BLOCK, 8,
    ROW]) are masked to the first ``n_valid`` elements; their sum gives
    ||grad - q_new||^2 and ||delta||^2.
    """
    n = grad.shape[0]
    assert bits in (1, 2, 4, 8), bits
    nb = n // BLOCK
    packed, delta, q_new, err, inn = _call(
        functools.partial(_quantize_pack_kernel, bits, n_valid), n,
        [_rows_spec(ROW), _rows_spec(ROW), _smem()],
        [_rows_spec(ROW * bits // 8), _rows_spec(ROW), _rows_spec(ROW),
         _part_spec(), _part_spec()],
        [jax.ShapeDtypeStruct((n // ROW, ROW * bits // 8), jnp.uint8),
         jax.ShapeDtypeStruct((n // ROW, ROW), jnp.float32),
         jax.ShapeDtypeStruct((n // ROW, ROW), jnp.float32),
         _part_shape(nb), _part_shape(nb)],
        interpret, _rows(grad), _rows(qhat), R)
    return packed, delta.reshape(-1), q_new.reshape(-1), err, inn


def _quantize_pack_payload_kernel(bits, g_ref, qh_ref, R_ref, packed_ref,
                                  delta_ref):
    R = R_ref[0]
    qf = _quant_codes(g_ref[...] - qh_ref[...], R, bits)
    delta_ref[...] = _dequant(qf, R, bits)
    packed_ref[...] = _pack_rows(qf, bits)


def quantize_pack_payload_pallas(grad, qhat, R, bits: int, *,
                                 interpret: bool = True):
    """Payload-only variant of the pass-2 kernel: packed codes + delta, no
    q_new/moment outputs — for callers that only want the wire payload
    (benchmarks, the roundtrip tests: the pinned-width oracle of the fused
    kernel's payload).

    Returns ``(packed uint8 [n // ROW, ROW*bits/8], delta f32 [n])``: the
    payload stays in the kernel's rows, since on the TPU a 1-D uint8
    reshape of them is a relayout of its own.
    """
    n = grad.shape[0]
    assert bits in (1, 2, 4, 8), bits
    packed, delta = _call(
        functools.partial(_quantize_pack_payload_kernel, bits), n,
        [_rows_spec(ROW), _rows_spec(ROW), _smem()],
        [_rows_spec(ROW * bits // 8), _rows_spec(ROW)],
        [jax.ShapeDtypeStruct((n // ROW, ROW * bits // 8), jnp.uint8),
         jax.ShapeDtypeStruct((n // ROW, ROW), jnp.float32)],
        interpret, _rows(grad), _rows(qhat), R)
    return packed, delta.reshape(-1)


# ---------------------------------------------------------------------------
# Adaptive pass 2: width-grid-unrolled fused quantize + pack.  The traced
# per-worker width selection (core/adaptive.py select_bits) cannot
# specialize the kernel at trace time, so the kernel carries one
# ``lax.switch`` arm per grid width — each arm IS the static-width code and
# delta math above, so a pinned selection reproduces the fixed-width kernel
# bit-for-bit.  The packed payload is provisioned at the static width
# max(grid) (codes < 2^b always fit; the sharded wire's provisioning
# convention, docs/wire-format.md), which keeps every arm's output shapes
# identical — the lax.switch requirement.
# ---------------------------------------------------------------------------

def _codes_arm(b, d, R):
    qf = _quant_codes(d, R, b)
    return qf, _dequant(qf, R, b)


def _quantize_pack_adaptive_kernel(grid, provision, n_valid, g_ref, qh_ref,
                                   R_ref, sel_ref, packed_ref, delta_ref,
                                   qnew_ref, err_ref, inn_ref):
    R = R_ref[0]
    g = g_ref[...]
    qh = qh_ref[...]
    arms = [functools.partial(_codes_arm, b) for b in grid]
    qf, delta = jax.lax.switch(sel_ref[0], arms, g - qh, R)
    delta_ref[...] = delta
    qnew_ref[...], err_ref[...], inn_ref[...] = _moments(g, qh, delta,
                                                         _valid(n_valid))
    packed_ref[...] = _pack_rows(qf, provision)


def quantize_pack_adaptive_pallas(grad, qhat, R, sel, grid, n_valid: int, *,
                                  interpret: bool = True):
    """grad, qhat: flat f32 [n] (n % BLOCK == 0), R: f32 [1], sel: int32 [1]
    index into ``grid`` (the ascending static width grid), n_valid: static
    count of real elements.

    Returns ``(packed uint8 [n*max(grid)/8], delta f32 [n], q_new f32 [n],
    err_part, inn_part)`` — the payload is provisioned at max(grid) bits
    (static shape across arms); moments are pad-masked block partials
    exactly like the fixed-width kernel.
    """
    n = grad.shape[0]
    grid = tuple(grid)
    assert all(b in (1, 2, 4, 8) for b in grid), grid
    provision = max(grid)
    nb = n // BLOCK
    packed, delta, q_new, err, inn = _call(
        functools.partial(_quantize_pack_adaptive_kernel, grid, provision,
                          n_valid), n,
        [_rows_spec(ROW), _rows_spec(ROW), _smem(), _smem()],
        [_rows_spec(ROW * provision // 8), _rows_spec(ROW), _rows_spec(ROW),
         _part_spec(), _part_spec()],
        [jax.ShapeDtypeStruct((n // ROW, ROW * provision // 8), jnp.uint8),
         jax.ShapeDtypeStruct((n // ROW, ROW), jnp.float32),
         jax.ShapeDtypeStruct((n // ROW, ROW), jnp.float32),
         _part_shape(nb), _part_shape(nb)],
        interpret, _rows(grad), _rows(qhat), R, sel)
    return (packed.reshape(-1), delta.reshape(-1), q_new.reshape(-1),
            err, inn)


# ---------------------------------------------------------------------------
# Adaptive axis-codec pass 2: quantize at the traced width emitting UNPACKED
# codes + delta in one sweep.  The adaptive sharded wire keeps the axis
# codec, which packs along the leaf's LAST dim (core/wire.py
# pack_codes_along_axis), so the kernel leaves packing to that codec and
# just fuses the code/delta math; the caller reshapes the flat outputs back
# to the leaf shape.  The fixed-width wire ships the payload kernel's
# packed rows instead.
# ---------------------------------------------------------------------------

def _quantize_codes_adaptive_kernel(grid, g_ref, qh_ref, R_ref, sel_ref,
                                    codes_ref, delta_ref):
    arms = [functools.partial(_codes_arm, b) for b in grid]
    qf, delta = jax.lax.switch(sel_ref[0], arms, g_ref[...] - qh_ref[...],
                               R_ref[0])
    codes_ref[...] = _to_u8(qf)
    delta_ref[...] = delta


def quantize_codes_adaptive_pallas(grad, qhat, R, sel, grid, *,
                                   interpret: bool = True):
    """grad, qhat: flat f32 [n] (n % BLOCK == 0), R: f32 [1], sel: int32
    [1] index into ``grid``.

    Returns ``(codes uint8 [n], delta f32 [n])`` at the selected width —
    the adaptive sharded wire's send-side sweep (codes stay unpacked for
    the axis codec)."""
    grid = tuple(grid)
    assert all(b in (1, 2, 4, 8) for b in grid), grid
    n = grad.shape[0]
    codes, delta = _call(
        functools.partial(_quantize_codes_adaptive_kernel, grid), n,
        [_rows_spec(ROW), _rows_spec(ROW), _smem(), _smem()],
        [_rows_spec(ROW), _rows_spec(ROW)],
        [jax.ShapeDtypeStruct((n // ROW, ROW), jnp.uint8),
         jax.ShapeDtypeStruct((n // ROW, ROW), jnp.float32)],
        interpret, _rows(grad), _rows(qhat), R, sel)
    with jax.named_scope(WIRE_CODEC):
        codes = codes.reshape(-1)
    return codes, delta.reshape(-1)


# ---------------------------------------------------------------------------
# Sparse pipeline: quantize + pack the GATHERED survivor values of the
# EF-LAQ compressor (core/compressors.py).  The selection/scatter halves
# are gather-bound and stay in XLA; the elementwise sign-magnitude grid
# math on the k survivors mirrors core/compressors.py's
# reference_sparse_quantize op for op, so the sparse wire content matches
# the reference backend bitwise (core/wire.py sparse_roundtrip contract).
# Covers the full packed grid b in {1, 2, 4, 8} — 1-bit (pure scaled-sign)
# is the EF frontier's headline regime.
# ---------------------------------------------------------------------------

def _sparse_quant_pack_kernel(bits, vals_ref, lo_ref, hi_ref, packed_ref,
                              codes_ref, deq_ref):
    lo = lo_ref[0]
    hi = hi_ref[0]
    v = vals_ref[...]
    L = 2 ** (bits - 1) - 1              # magnitude levels above lo
    a = jnp.abs(v)
    neg = v < 0
    step = (hi - lo) / max(L, 1)
    safe = jnp.where(step > 0, step, 1.0)
    mag = jnp.clip(jnp.floor((a - lo) / safe + 0.5), 0, L)
    mag = jnp.where(step > 0, mag, jnp.zeros_like(mag))
    qf = jnp.where(neg, mag + float(1 << (bits - 1)), mag)
    codes_ref[...] = _to_u8(qf)
    deq_ref[...] = jnp.where(neg, -1.0, 1.0) * (lo + mag * step)
    packed_ref[...] = _pack_rows(qf, bits)


def sparse_quant_pack_pallas(vals, lo, hi, bits: int, *,
                             interpret: bool = True):
    """vals: gathered survivor values, flat f32 [n] (n % BLOCK == 0,
    zero-padded upstream), lo/hi: the grid-endpoint sidecars, f32 [1].

    Returns ``(packed uint8 [n*bits/8], codes uint8 [n], deq f32 [n])``;
    the caller slices the k real entries off (pad values quantize like any
    zero and are discarded — the shared payload packing in core/wire.py
    re-pads canonically).
    """
    n = vals.shape[0]
    assert bits in (1, 2, 4, 8), bits
    packed, codes, deq = _call(
        functools.partial(_sparse_quant_pack_kernel, bits), n,
        [_rows_spec(ROW), _smem(), _smem()],
        [_rows_spec(ROW * bits // 8), _rows_spec(ROW), _rows_spec(ROW)],
        [jax.ShapeDtypeStruct((n // ROW, ROW * bits // 8), jnp.uint8),
         jax.ShapeDtypeStruct((n // ROW, ROW), jnp.uint8),
         jax.ShapeDtypeStruct((n // ROW, ROW), jnp.float32)],
        interpret, _rows(vals), lo, hi)
    return packed.reshape(-1), codes.reshape(-1), deq.reshape(-1)


# ---------------------------------------------------------------------------
# Receive side: unpack + dequant + W-accumulate (+ optional server-aggregate
# fold-in).
# ---------------------------------------------------------------------------

def _dequant_acc_kernel(bits, W, has_acc, *refs):
    if has_acc:
        packed_ref, R_ref, keep_ref, acc_ref, out_ref = refs
        acc = acc_ref[...]
    else:
        packed_ref, R_ref, keep_ref, out_ref = refs
        acc = jnp.zeros(out_ref.shape, jnp.float32)
    for w in range(W):                       # W is static & small (workers/pods)
        delta = _dequant(_unpack_rows(packed_ref[w], bits), R_ref[w], bits)
        acc = acc + delta * keep_ref[w]
    out_ref[...] = acc


def dequant_acc_pallas(packed, R, keep, bits: int, n: int, acc=None, *,
                       interpret: bool = True):
    """packed: uint8 [W, n*bits/8], or the send kernel's rows stacked,
    [W, n // ROW, ROW*bits/8]; R, keep: [W] f32 -> f32 [n // ROW, ROW]
    (summed, in rows).

    ``acc`` (optional f32 [n], e.g. the server aggregate) is folded into the
    same pass: out = acc + sum_w delta_w.
    """
    assert bits in (1, 2, 4, 8), bits
    W = packed.shape[0]
    nbytes = math.prod(packed.shape[1:])
    assert nbytes * 8 // bits == n, (nbytes, bits, n)
    in_specs = [pl.BlockSpec((W, ROWS, ROW * bits // 8),
                             lambda i: (0, i, 0)),
                _smem(), _smem()]
    args = [packed.reshape(W, n // ROW, ROW * bits // 8), R, keep]
    if acc is not None:
        in_specs.append(_rows_spec(ROW))
        args.append(_rows(acc))
    out = _call(functools.partial(_dequant_acc_kernel, bits, W,
                                  acc is not None), n,
                in_specs, _rows_spec(ROW),
                jax.ShapeDtypeStruct((n // ROW, ROW), jnp.float32),
                interpret, *args)
    return out
