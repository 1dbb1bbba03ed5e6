"""Pallas kernel validation: interpret-mode vs the pure-jnp ref oracle,
swept over shapes / bits / dtypes (incl. the fused-pipeline edge cases:
R == 0 blocks, non-BLOCK-multiple lengths through ops.py padding, and
single-worker dequant_acc), plus hypothesis property coverage."""
import hypothesis
import hypothesis.strategies as st
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.quantize import quantize_codes, tau
from repro.kernels import (absmax, dequant_acc, quantize_codes_adaptive,
                           quantize_pack,
                           quantize_pack_adaptive, quantize_pack_fused)
from repro.kernels.quant_pack import BLOCK
from repro.kernels.ref import (absmax_ref, dequant_acc_ref,
                               quantize_pack_adaptive_ref,
                               quantize_pack_fused_ref, quantize_pack_ref)

# non-BLOCK-multiple lengths exercise the ops.py pad + in-kernel moment
# masking; 1 and 3 exercise a single nearly-empty block
EDGE_SHAPES = [1, 3, 128, 5000, BLOCK, BLOCK + 1, 3 * BLOCK + 17]

GRID = (2, 4, 8)       # the bit_schedule width grid the adaptive kernel unrolls


def _onehot(sel):
    return jnp.eye(len(GRID), dtype=jnp.float32)[sel]


def _unpack(packed, bits, n):
    """First n codes from packed bytes, flat or in the kernels' rows
    (little-end-first lanes)."""
    p = np.asarray(packed).reshape(-1)
    if bits == 8:
        return p[:n]
    cpb = 8 // bits
    codes = np.stack([(p >> (bits * j)) & ((1 << bits) - 1)
                      for j in range(cpb)], axis=-1).reshape(-1)
    return codes[:n]


@pytest.mark.parametrize("bits", [2, 4, 8])
@pytest.mark.parametrize("n", [BLOCK, 2 * BLOCK, 3 * BLOCK + 17, 5000, 128])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_quantize_pack_matches_ref(bits, n, dtype):
    key = jax.random.PRNGKey(n * bits)
    g = (jax.random.normal(key, (n,)) * 5).astype(dtype)
    qh = jax.random.normal(jax.random.fold_in(key, 1), (n,)).astype(dtype)
    diff = g.astype(jnp.float32) - qh.astype(jnp.float32)
    R = jnp.max(jnp.abs(diff))
    packed, delta = quantize_pack(g, qh, R, bits)
    pad = (-n) % BLOCK
    dpad = jnp.concatenate([diff, jnp.zeros((pad,))]) if pad else diff
    packed_ref, delta_ref = quantize_pack_ref(dpad, R, bits)
    np.testing.assert_array_equal(np.asarray(packed).reshape(-1),
                                  np.asarray(packed_ref))
    np.testing.assert_allclose(np.asarray(delta), np.asarray(delta_ref[:n]),
                               atol=1e-5)
    # the LAQ error bound holds through the kernel
    tau = 1.0 / (2 ** bits - 1)
    assert float(jnp.max(jnp.abs(diff - delta))) <= float(tau * R) + 1e-5


@pytest.mark.parametrize("bits", [2, 4, 8])
@pytest.mark.parametrize("W", [1, 2, 4, 16])
def test_dequant_acc_matches_ref(bits, W):
    n = 2 * BLOCK
    key = jax.random.PRNGKey(W)
    packed = jax.random.randint(key, (W, n * bits // 8), 0, 256).astype(jnp.uint8)
    R = jax.random.uniform(jax.random.fold_in(key, 1), (W,)) * 3
    keep = (jax.random.uniform(jax.random.fold_in(key, 2), (W,)) > 0.3).astype(jnp.float32)
    out = dequant_acc(packed, R, keep, bits, n)
    ref = dequant_acc_ref(packed, R, keep, bits, n)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4)


def test_roundtrip_wire_identity():
    """send-side kernel -> receive-side kernel == float-mode innovation."""
    n, bits, W = BLOCK, 4, 3
    key = jax.random.PRNGKey(7)
    grads = [jax.random.normal(jax.random.fold_in(key, w), (n,)) for w in range(W)]
    qh = jnp.zeros((n,))
    payloads, Rs, deltas = [], [], []
    for g in grads:
        R = jnp.max(jnp.abs(g - qh))
        pk, dl = quantize_pack(g, qh, R, bits)
        payloads.append(pk)
        Rs.append(R)
        deltas.append(dl)
    acc = dequant_acc(jnp.stack(payloads), jnp.stack(Rs),
                      jnp.ones((W,)), bits, n)
    np.testing.assert_allclose(np.asarray(acc),
                               np.asarray(sum(deltas)), atol=1e-4)


# ---------------------------------------------------------------------------
# Fused-pipeline kernels: pass-1 absmax, pass-2 moment side-outputs, and the
# accumulating receive side.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", EDGE_SHAPES)
def test_absmax_matches_ref(n):
    key = jax.random.PRNGKey(n)
    g = jax.random.normal(key, (n,)) * 7
    qh = jax.random.normal(jax.random.fold_in(key, 1), (n,))
    assert float(absmax(g, qh)) == float(absmax_ref(g, qh))


def test_absmax_zero_innovation():
    g = jnp.full((2 * BLOCK + 5,), 3.25)
    assert float(absmax(g, g)) == 0.0


@pytest.mark.parametrize("bits", [2, 4, 8])
@pytest.mark.parametrize("n", EDGE_SHAPES)
def test_quantize_pack_fused_matches_ref(bits, n):
    """Moment side-outputs must cover exactly the n real elements — the pad
    tail dequantizes to a nonzero midpoint delta, so an unmasked kernel sum
    would be wrong for every non-BLOCK-multiple length here."""
    key = jax.random.PRNGKey(n * bits + 1)
    g = jax.random.normal(key, (n,)) * 4
    qh = jax.random.normal(jax.random.fold_in(key, 1), (n,))
    R = absmax(g, qh)
    packed, delta, q_new, err_sq, inn_sq = quantize_pack_fused(g, qh, R, bits)
    packed_r, delta_r, qn_r, err_r, inn_r = quantize_pack_fused_ref(g, qh, R,
                                                                    bits)
    cpb = 8 // bits
    np.testing.assert_array_equal(np.asarray(packed).reshape(-1)[:n // cpb],
                                  np.asarray(packed_r[:n // cpb]))
    np.testing.assert_allclose(np.asarray(delta), np.asarray(delta_r),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(q_new), np.asarray(qn_r), atol=1e-5)
    np.testing.assert_allclose(float(err_sq), float(err_r), rtol=1e-4,
                               atol=1e-6)
    np.testing.assert_allclose(float(inn_sq), float(inn_r), rtol=1e-4,
                               atol=1e-6)


@pytest.mark.parametrize("bits", [2, 4, 8])
@pytest.mark.parametrize("n", EDGE_SHAPES)
def test_fused_payload_rows_are_the_payload_kernels(bits, n):
    """The whole-leaf wire ships the fused kernel's payload where it once
    ran the payload-only kernel again: for the same ``(g, qh, R)`` the two
    kernels' rows are the same bytes in the same layout, and ``dequant_acc``
    decodes them to the same bits."""
    key = jax.random.PRNGKey(n * bits + 5)
    g = jax.random.normal(key, (n,)) * 4
    qh = jax.random.normal(jax.random.fold_in(key, 1), (n,))
    R = absmax(g, qh)
    fused, *_ = quantize_pack_fused(g, qh, R, bits)
    payload, _ = quantize_pack(g, qh, R, bits)
    assert fused.shape == payload.shape and fused.dtype == payload.dtype
    np.testing.assert_array_equal(np.asarray(fused), np.asarray(payload))
    Rw, keep = jnp.stack([R, 0.5 * R]), jnp.ones((2,))
    got = dequant_acc(jnp.stack([fused, fused[::-1]]), Rw, keep, bits, n)
    want = dequant_acc(jnp.stack([payload, payload[::-1]]), Rw, keep, bits,
                       n)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("bits", [2, 4, 8])
def test_quantize_pack_fused_zero_radius_block(bits):
    """R == 0 (zero innovation): midpoint codes, exactly zero delta and
    moments — the q_new recursion must be a no-op."""
    n = BLOCK + 9
    g = jnp.linspace(-1.0, 1.0, n)
    packed, delta, q_new, err_sq, inn_sq = quantize_pack_fused(
        g, g, jnp.zeros(()), bits)
    assert int(jnp.max(jnp.abs(delta) > 0)) == 0
    np.testing.assert_array_equal(np.asarray(q_new), np.asarray(g))
    assert float(err_sq) == 0.0 and float(inn_sq) == 0.0
    codes = np.asarray(packed).reshape(-1)[: n // (8 // bits)]
    mid = (2 ** bits) // 2
    expect = sum(mid << (bits * j) for j in range(8 // bits))
    assert (codes == expect).all()


# ---------------------------------------------------------------------------
# Adaptive (width-grid-unrolled) pass-2 kernel: one lax.switch arm per grid
# width, payload provisioned at max(grid).  Same edge cases as fixed-width.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("sel", range(len(GRID)))
@pytest.mark.parametrize("n", EDGE_SHAPES)
def test_quantize_pack_adaptive_matches_ref(sel, n):
    """Every grid width, incl. non-BLOCK-multiple lengths through the
    ops.py pad + in-kernel moment masking."""
    key = jax.random.PRNGKey(n * (sel + 1) + 2)
    g = jax.random.normal(key, (n,)) * 4
    qh = jax.random.normal(jax.random.fold_in(key, 1), (n,))
    R = absmax(g, qh)
    out = quantize_pack_adaptive(g, qh, R, _onehot(sel), GRID)
    ref = quantize_pack_adaptive_ref(g, qh, R, GRID, sel)
    packed, delta, q_new, err_sq, inn_sq = out
    packed_r, delta_r, qn_r, err_r, inn_r = ref
    cpb = 8 // max(GRID)
    np.testing.assert_array_equal(np.asarray(packed[:n // cpb]),
                                  np.asarray(packed_r[:n // cpb]))
    np.testing.assert_allclose(np.asarray(delta), np.asarray(delta_r),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(q_new), np.asarray(qn_r), atol=1e-5)
    np.testing.assert_allclose(float(err_sq), float(err_r), rtol=1e-4,
                               atol=1e-6)
    np.testing.assert_allclose(float(inn_sq), float(inn_r), rtol=1e-4,
                               atol=1e-6)


@pytest.mark.parametrize("sel", range(len(GRID)))
@pytest.mark.parametrize("n", [128, 5000, BLOCK + 1])
def test_quantize_pack_adaptive_matches_fixed_kernel(sel, n):
    """BITWISE anchor: the switch arm at a pinned width IS the fixed-width
    kernel pipeline — delta/q_new/moments exactly equal, codes equal after
    unpacking each payload at its own lane width."""
    bits = GRID[sel]
    key = jax.random.PRNGKey(n + sel)
    g = jax.random.normal(key, (n,)) * 4
    qh = jax.random.normal(jax.random.fold_in(key, 1), (n,))
    R = absmax(g, qh)
    packed_a, delta_a, qn_a, err_a, inn_a = quantize_pack_adaptive(
        g, qh, R, _onehot(sel), GRID)
    packed_f, delta_f, qn_f, err_f, inn_f = quantize_pack_fused(g, qh, R, bits)
    np.testing.assert_array_equal(_unpack(packed_a, max(GRID), n),
                                  _unpack(packed_f, bits, n))
    np.testing.assert_array_equal(np.asarray(delta_a), np.asarray(delta_f))
    np.testing.assert_array_equal(np.asarray(qn_a), np.asarray(qn_f))
    assert float(err_a) == float(err_f)
    assert float(inn_a) == float(inn_f)


@pytest.mark.parametrize("sel", range(len(GRID)))
def test_quantize_pack_adaptive_zero_radius_block(sel):
    """R == 0: midpoint codes at the SELECTED width, exactly zero delta and
    moments — the q_new recursion must be a no-op."""
    n = BLOCK + 9
    g = jnp.linspace(-1.0, 1.0, n)
    packed, delta, q_new, err_sq, inn_sq = quantize_pack_adaptive(
        g, g, jnp.zeros(()), _onehot(sel), GRID)
    assert int(jnp.max(jnp.abs(delta) > 0)) == 0
    np.testing.assert_array_equal(np.asarray(q_new), np.asarray(g))
    assert float(err_sq) == 0.0 and float(inn_sq) == 0.0
    mid = (2 ** GRID[sel]) // 2
    assert (_unpack(packed, max(GRID), n) == mid).all()


@pytest.mark.parametrize("sel", range(len(GRID)))
@pytest.mark.parametrize("n", [3, 5000, BLOCK + 1])
def test_quantize_codes_adaptive_matches_fixed(sel, n):
    """The unpacked codes sweep (the adaptive wire's axis codec): adaptive
    == the fixed-width payload kernel at the pinned width, codes and delta
    exactly, and both against the reference expressions."""
    bits = GRID[sel]
    key = jax.random.PRNGKey(n + 11 * sel)
    g = jax.random.normal(key, (n,)) * 3
    qh = jax.random.normal(jax.random.fold_in(key, 1), (n,))
    R = absmax(g, qh)
    codes_a, delta_a = quantize_codes_adaptive(g, qh, R, _onehot(sel), GRID)
    packed_f, delta_f = quantize_pack(g, qh, R, bits)
    np.testing.assert_array_equal(np.asarray(codes_a),
                                  _unpack(np.asarray(packed_f).reshape(-1),
                                          bits, n))
    np.testing.assert_array_equal(np.asarray(delta_a), np.asarray(delta_f))
    # and against the reference expressions
    d = g.astype(jnp.float32) - qh.astype(jnp.float32)
    np.testing.assert_array_equal(np.asarray(codes_a),
                                  np.asarray(quantize_codes(d, R, bits)))
    t = tau(bits)
    delta_ref = jnp.where(R > 0, 2.0 * t * R * codes_a.astype(jnp.float32) - R,
                          0.0)
    np.testing.assert_allclose(np.asarray(delta_a), np.asarray(delta_ref),
                               atol=1e-5)


@pytest.mark.parametrize("bits", [2, 4, 8])
@pytest.mark.parametrize("W", [1, 3])
@pytest.mark.parametrize("n", [5000, 2 * BLOCK])
def test_dequant_acc_with_accumulator(bits, W, n):
    """Optional server-aggregate fold-in (one pass) == separate add; W=1
    covers the single-worker (per-pod) wire."""
    key = jax.random.PRNGKey(W * bits + n)
    npad = ((n + BLOCK - 1) // BLOCK) * BLOCK
    packed = jax.random.randint(key, (W, npad * bits // 8), 0, 256).astype(jnp.uint8)
    R = jax.random.uniform(jax.random.fold_in(key, 1), (W,)) * 2
    keep = (jax.random.uniform(jax.random.fold_in(key, 2), (W,)) > 0.3).astype(jnp.float32)
    acc = jax.random.normal(jax.random.fold_in(key, 3), (n,))
    fused = dequant_acc(packed, R, keep, bits, n, acc)
    ref = dequant_acc_ref(packed, R, keep, bits, n, acc)
    np.testing.assert_allclose(np.asarray(fused), np.asarray(ref), atol=1e-4)
    two_pass = acc + dequant_acc(packed, R, keep, bits, n)
    np.testing.assert_allclose(np.asarray(fused), np.asarray(two_pass),
                               atol=1e-4)


def test_dequant_acc_single_worker_zero_radius():
    """W=1 with R == 0: the worker's payload decodes to exactly zero, so
    the accumulator passes through untouched."""
    n = BLOCK
    packed = jnp.full((1, n // 2), 0x77, jnp.uint8)
    acc = jnp.arange(n, dtype=jnp.float32)
    out = dequant_acc(packed, jnp.zeros((1,)), jnp.ones((1,)), 4, n, acc)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(acc))


@pytest.mark.parametrize("bits", [2, 4, 8])
@pytest.mark.parametrize("W", [1, 2, 4])
@pytest.mark.parametrize("shape", [(7, 13), (3, 5, 33), (BLOCK + 3,)])
def test_row_payload_decodes_like_the_axis_codec(bits, W, shape):
    """The whole-leaf wire's kernel pair: each worker's row payload from
    the send kernel, stacked over workers and decoded by ``dequant_acc``,
    is the axis codec's masked sum (``launch/train.py``) bitwise, at odd
    leaf shapes, with a skipped worker and a worker whose radius is 0."""
    from repro.core.wire import (get_backend, pack_codes_along_axis,
                                 unpack_codes_along_axis)
    ref = get_backend("reference")
    key = jax.random.PRNGKey(W * 100 + bits)
    keep = jnp.array([1.0, 1.0, 0.0, 1.0][:W])
    payloads, Rs, codes = [], [], []
    for w in range(W):
        k1, k2, key = jax.random.split(key, 3)
        qh = jax.random.normal(k1, shape)
        g = qh if w == 1 else qh + jax.random.normal(k2, shape) * 3
        R = ref.leaf_absmax(g, qh)
        pk, _ = quantize_pack(g, qh, R, bits)
        q, _ = ref.leaf_quantize(g, qh, R, bits)
        payloads.append(pk)
        Rs.append(R)
        codes.append(unpack_codes_along_axis(pack_codes_along_axis(q, bits),
                                             bits, q))
    Rw = jnp.stack(Rs)
    n = int(np.prod(shape))
    got = dequant_acc(jnp.stack(payloads), Rw, keep, bits, n).reshape(shape)
    # the axis codec's decode and masked sum over the gathered workers,
    # compiled as the step compiles it
    @jax.jit
    def axis_sum(codes, Rw, keep):
        t = 1.0 / (2.0 ** bits - 1.0)
        Rb = Rw.reshape((W,) + (1,) * len(shape))
        kb = keep.reshape((W,) + (1,) * len(shape))
        delta = 2.0 * t * Rb * codes.astype(jnp.float32) - Rb
        return jnp.sum(jnp.where(Rb > 0, delta, 0.0) * kb, axis=0)

    want = axis_sum(jnp.stack(codes), Rw, keep)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # the reference decode takes the rows as they are too
    ref_sum = jax.jit(dequant_acc_ref, static_argnums=(3, 4))(
        jnp.stack(payloads), Rw, keep, bits, n)
    np.testing.assert_array_equal(np.asarray(ref_sum).reshape(shape),
                                  np.asarray(want))


@hypothesis.given(scale=st.floats(1e-3, 1e3), bits=st.sampled_from([2, 4, 8]))
@hypothesis.settings(max_examples=20, deadline=None)
def test_property_kernel_error_bound(scale, bits):
    key = jax.random.PRNGKey(int(scale * 1000) % 2**31)
    g = jax.random.normal(key, (BLOCK,)) * scale
    qh = jnp.zeros((BLOCK,))
    R = jnp.max(jnp.abs(g))
    _, delta = quantize_pack(g, qh, R, bits)
    tau = 1.0 / (2 ** bits - 1)
    assert float(jnp.max(jnp.abs(g - delta))) <= float(tau * R) * (1 + 1e-5) + 1e-6
