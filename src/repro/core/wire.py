"""Pluggable wire backends: one interface for the quantize/pack/dequant hot
path, with a ``reference`` jnp implementation and a ``fused`` two-pass
implementation.

Every consumer of the quantizer — ``worker_update`` (fixed and adaptive
paths), the simulated runner, the wire microbenchmark, and the packed
sharded wire in ``launch/train.py`` — routes through this interface, so the
kernel-level pipeline can be swapped without touching the LAQ state machine.
Selection is by name via ``StrategyConfig.wire_backend``:

* ``reference`` — the paper-faithful jnp path from :mod:`repro.core.quantize`
  (~5-6 full-gradient sweeps per round: diff+inf-norm, codes, delta, q_new,
  err_sq, innovation_sq as separate elementwise passes).
* ``fused`` — the two-pass pipeline: pass 1 reduces the radius
  ``R = ||grad - qhat||_inf`` blockwise without materializing the diff;
  pass 2 emits codes+payload, delta, q_new AND the per-block partial sums
  for ``||grad - q_new||^2`` / ``||delta||^2`` in a single sweep, so the
  skip-criterion inputs come for free.  Two lowerings of the same
  algorithm: compiled Pallas kernels (:mod:`repro.kernels`) off-CPU, and an
  op-for-op blocked jnp expression on CPU, where interpret-mode Pallas would
  serialize the grid (lowering="auto" picks per ``jax.default_backend()``;
  tests pin "pallas"/"jnp" explicitly).

Equivalence contract (asserted in tests/test_wire_backend.py over
{qgd, laq} x bits {2, 4, 8} x {global, per-leaf} radii): the wire content —
codes, radii, ``delta``, ``q_new`` — is **bit-identical** across backends
(the elementwise expressions are kept identical, down to association order),
and whole simulated LAQ runs reproduce bit-identical trajectories on either
backend.  The scalar moments ``err_sq``/``innovation_sq`` are reduced with
the same tree as the reference on the CPU jnp lowering (usually bit-equal),
but XLA may re-derive a fused producer inside a reduce with a different
mul-add contraction, and the Pallas lowering emits blockwise partial sums —
so moments are only guaranteed to float32 reduction accuracy (~1e-7
relative), which the skip criterion's O(1) threshold margins tolerate.

The byte-level wire layout both backends emit is specified normatively in
``docs/wire-format.md``.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from .adaptive import (dequantize_dynamic, quantize_dynamic, tau_of_selection)
from .compressors import (_flat, _unflat, reference_sparse_quantize,
                          scatter_selection, select_support, sparse_grid)
from .quantize import (dequantize_innovation, innovation, pack_codes,
                       quantize_codes, roundtrip_parts, tau, tree_sq_norm)
from .spans import WIRE_CODEC, WIRE_QUANTIZE, scoped

Pytree = object


def pack_flat(codes, bits: int):
    """The flat payload of one leaf's codes: flattened, padded with
    midpoint codes to whole bytes, packed 8/b per byte
    (``docs/wire-format.md`` section 3)."""
    flat = codes.reshape(-1)
    pad = (-flat.shape[0]) % (8 // bits)
    if pad:
        flat = jnp.concatenate([flat, jnp.full((pad,), (2 ** bits) // 2,
                                               jnp.uint8)])
    return pack_codes(flat, bits)


class WireRoundtrip(NamedTuple):
    """Everything one round's quantize step produces for one worker."""
    q_new: Pytree           # Q_m(theta^k) = qhat + delta
    delta: Pytree           # dequantized innovation deltaQ_m^k
    R_tree: Pytree          # per-leaf radii (global R replicated if not per-leaf)
    R_max: jax.Array        # max leaf radius (paper Fig. 3 diagnostic)
    err_sq: jax.Array       # ||grad - q_new||^2  (criterion eps term)
    innovation_sq: jax.Array  # ||delta||^2       (criterion LHS)
    payload: Optional[list]   # per-leaf packed uint8 codes (with_payload only);
                              # layout is backend-specific (the Pallas payload
                              # is the kernel's BLOCK-padded rows), byte
                              # semantics are shared: the bytes in order are
                              # the flat payload


class WireBackend:
    """Interface: radius reduction, quantize roundtrip, server dequant-acc.

    The per-LEAF primitives (``leaf_absmax`` / ``leaf_quantize`` /
    ``leaf_quantize_adaptive``) are the streamed axis codec's hot loop
    (launch/train.py ``_packed_aggregate`` touches one leaf at a time); the
    whole-leaf wire ships :meth:`roundtrip`'s payload instead.
    Their base-class bodies below ARE the reference expressions — verbatim
    :mod:`repro.core.quantize` / :mod:`repro.core.adaptive` calls — so every
    backend inherits bit-identical wire content by code sharing; subclasses
    override only to swap the *lowering* (the fused backend dispatches the
    Pallas kernels off-CPU).
    """

    name = "?"

    def innovation(self, grad: Pytree, qhat: Pytree, per_leaf: bool = False):
        """``(diff, R_tree, R_max)`` — same contract as quantize.innovation."""
        raise NotImplementedError

    def roundtrip(self, grad: Pytree, qhat: Pytree, bits: int,
                  per_leaf: bool = False,
                  with_payload: bool = False) -> WireRoundtrip:
        raise NotImplementedError

    @scoped(WIRE_QUANTIZE)
    def leaf_absmax(self, g, qh):
        """Scalar ``|| g - qh ||_inf`` for ONE leaf (f32) — the radius
        pre-pass primitive (pass 1 of the two-pass pipeline, per leaf).
        Mirrors ``innovation``/``tree_inf_norm`` exactly; empty leaves
        reduce to 0 like the tree helpers skip them."""
        if g.size == 0:
            return jnp.zeros((), jnp.float32)
        return jnp.max(jnp.abs(g.astype(jnp.float32)
                               - qh.astype(jnp.float32))).astype(jnp.float32)

    @scoped(WIRE_QUANTIZE)
    def leaf_quantize(self, g, qh, R, bits: int):
        """``(codes, delta)`` for one leaf at one static width — the
        send-side pass-2 sweep of the sharded wire's axis codec.  Shape
        preserving (codes uint8, delta f32, both leaf-shaped): the
        axis-packed payload codec downstream packs along the leaf's last
        dim, so the codes must keep the leaf shape."""
        d = g.astype(jnp.float32) - qh.astype(jnp.float32)
        codes = quantize_codes(d, R, bits)
        delta = dequantize_innovation(codes, R, bits)
        return codes, delta

    @scoped(WIRE_QUANTIZE)
    def leaf_quantize_adaptive(self, g, qh, R, grid, onehot, t_sel):
        """Traced-width variant of :meth:`leaf_quantize`: ``onehot``
        selects from the static ascending ``grid``, ``t_sel`` is
        ``tau_of_selection(grid, onehot)`` (computed once per round by the
        caller, not per leaf)."""
        d = g.astype(jnp.float32) - qh.astype(jnp.float32)
        codes = quantize_dynamic(d, R, grid, onehot)
        delta = dequantize_dynamic(codes, R, t_sel)
        return codes, delta

    @scoped(WIRE_QUANTIZE)
    def adaptive_roundtrip(self, grad: Pytree, qhat: Pytree, diff: Pytree,
                           R_tree: Pytree, grid, onehot):
        """Dynamic-width roundtrip ``(q_new, delta, err_sq, innovation_sq)``
        for the width encoded in ``onehot`` over the static ``grid``.

        ``diff``/``R_tree`` come from this backend's own prior
        :meth:`innovation` call — the width selection (adaptive.select_bits)
        needs the radius BEFORE the quantize sweep can run, so the two
        passes cannot be fused across that data dependence.  The base body
        is the reference staged pipeline moved verbatim from
        ``strategy.worker_update`` (bit-compatibility anchor); the fused
        backend overrides with the width-grid-unrolled pass-2 kernel that
        emits delta, q_new and both criterion moments in one sweep.
        """
        codes = quantize_dynamic(diff, R_tree, grid, onehot)
        delta = dequantize_dynamic(codes, R_tree,
                                   tau_of_selection(grid, onehot))
        q_new = jax.tree.map(lambda q, d: q.astype(jnp.float32) + d,
                             qhat, delta)
        err_sq = tree_sq_norm(jax.tree.map(
            lambda g, qn: g.astype(jnp.float32) - qn, grad, q_new))
        innovation_sq = tree_sq_norm(delta)
        return q_new, delta, err_sq, innovation_sq

    def dequant_acc(self, packed, R, keep, bits: int, n: int, acc=None):
        """Server side: ``(acc +) sum_w keep_w * dequant(packed_w, R_w)``."""
        raise NotImplementedError

    def sparse_quantize(self, vals, lo, hi, bits: int):
        """Quantize stage of the sparse pipeline on gathered values:
        ``(codes uint8 [k], deq f32 [k])`` via the sign-magnitude grid on
        [lo, hi] (core/compressors.py).  Everything around it — support
        selection, grid moments, scatter, payload packing — is shared code
        in :func:`sparse_roundtrip`, so backends only differ in this
        elementwise map and must match it bitwise."""
        raise NotImplementedError


class ReferenceWire(WireBackend):
    """The jnp path of core/quantize.py, verbatim (the tests' ground truth)."""

    name = "reference"

    @scoped(WIRE_QUANTIZE)
    def innovation(self, grad, qhat, per_leaf=False):
        return innovation(grad, qhat, per_leaf)

    @scoped(WIRE_QUANTIZE)
    def roundtrip(self, grad, qhat, bits, per_leaf=False, with_payload=False):
        qints, R_tree, delta, q_new, R_max, err_sq = roundtrip_parts(
            grad, qhat, bits, per_leaf)
        innovation_sq = tree_sq_norm(delta)
        payload = None
        if with_payload:
            with jax.named_scope(WIRE_CODEC):
                payload = [pack_flat(q, bits)
                           for q in jax.tree_util.tree_leaves(qints)]
        return WireRoundtrip(q_new, delta, R_tree, R_max, err_sq,
                             innovation_sq, payload)

    def dequant_acc(self, packed, R, keep, bits, n, acc=None):
        from repro.kernels.ref import dequant_acc_ref
        return dequant_acc_ref(packed, R.astype(jnp.float32),
                               keep.astype(jnp.float32), bits, n, acc)

    def sparse_quantize(self, vals, lo, hi, bits):
        return reference_sparse_quantize(vals, lo, hi, bits)


def _fused_leaf_jnp(g, qh, R, bits, with_payload):
    """Op-for-op jnp lowering of the pass-2 kernel, on the dense flat leaf.

    Padding and block tiling belong to the Pallas lowering only: a jnp
    moment reduce fused with a slice-of-padded-array lowers to a masked
    wide reduction whose partial-sum grouping differs from the reference's
    dense reduce at the last ulp — enough to flip near-tie skip decisions.
    Dense flat arrays give both backends the identical elementwise
    expressions AND the identical reduction tree, so wire content and
    moments are bit-identical on CPU.
    """
    gf = g.reshape(-1).astype(jnp.float32)
    qf = qh.reshape(-1).astype(jnp.float32)
    d = gf - qf
    t = tau(bits)
    levels = 2 ** bits - 1
    denom = jnp.where(R > 0, 2.0 * t * R, 1.0)
    q = jnp.clip(jnp.floor((d + R) / denom + 0.5), 0, levels)
    q = jnp.where(R > 0, q, (levels + 1) // 2 * jnp.ones_like(q)).astype(jnp.uint8)
    delta = 2.0 * t * R * q.astype(jnp.float32) - R
    delta = jnp.where(R > 0, delta, jnp.zeros_like(delta))
    qn = qf + delta
    err = gf - qn
    err_sq = jnp.sum(err * err)
    inn_sq = jnp.sum(delta * delta)
    payload = None
    if with_payload:
        with jax.named_scope(WIRE_CODEC):
            payload = pack_flat(q, bits)
    return delta, qn, err_sq, inn_sq, payload


def _fused_leaf_adaptive_jnp(g, qh, R, grid, onehot, t_sel,
                             with_payload=False):
    """Adaptive (traced-width) analogue of :func:`_fused_leaf_jnp`: the
    whole pass-2 sweep — grid-evaluated codes, delta, q_new and both moments
    — as one dense flat per-leaf expression.  The code/delta math is
    expression-for-expression ``quantize_dynamic`` + ``dequantize_dynamic``
    (via the shared ``quantize_codes``), so wire content and moments are
    bit-identical to the reference staged path on CPU.  The payload (wanted
    by the wire microbench's pass framing only) is packed at the provision
    width max(grid), matching the adaptive Pallas kernel."""
    n = g.size
    gf = g.reshape(-1).astype(jnp.float32)
    qf = qh.reshape(-1).astype(jnp.float32)
    d = gf - qf
    q = None
    for i, b in enumerate(grid):
        qi = quantize_codes(d, R, b)
        q = qi if q is None else jnp.where(onehot[i] > 0, qi, q)
    delta = 2.0 * t_sel * R * q.astype(jnp.float32) - R
    delta = jnp.where(R > 0, delta, jnp.zeros_like(delta))
    qn = qf + delta
    err = gf - qn
    payload = None
    if with_payload:
        provision = max(grid)
        pad = (-n) % (8 // provision)
        qp = q
        if pad:
            qp = jnp.concatenate([q, jnp.zeros((pad,), jnp.uint8)])
        payload = pack_codes(qp, provision)
    return delta, qn, jnp.sum(err * err), jnp.sum(delta * delta), payload


class FusedWire(WireBackend):
    """The two-pass fused pipeline (see module docstring).

    ``lowering``: "auto" (Pallas off-CPU, blocked jnp on CPU), "pallas"
    (force the kernels — interpret mode on CPU; the test configuration), or
    "jnp" (force the blocked jnp expression).
    """

    name = "fused"

    def __init__(self, lowering: str = "auto"):
        assert lowering in ("auto", "pallas", "jnp"), lowering
        self.lowering = lowering

    def _use_pallas(self) -> bool:
        if self.lowering == "auto":
            return jax.default_backend() != "cpu"
        return self.lowering == "pallas"

    @scoped(WIRE_QUANTIZE)
    def leaf_absmax(self, g, qh):
        if g.size and self._use_pallas():
            from repro.kernels import absmax
            return absmax(g, qh)
        return super().leaf_absmax(g, qh)

    @scoped(WIRE_QUANTIZE)
    def leaf_quantize_adaptive(self, g, qh, R, grid, onehot, t_sel):
        if g.size and self._use_pallas():
            from repro.kernels import quantize_codes_adaptive
            codes, delta = quantize_codes_adaptive(g, qh, R, onehot,
                                                   tuple(grid))
            with jax.named_scope(WIRE_CODEC):
                codes = codes.reshape(g.shape)
            return codes, delta.reshape(g.shape)
        return super().leaf_quantize_adaptive(g, qh, R, grid, onehot, t_sel)

    def _radii(self, g_leaves, q_leaves, per_leaf):
        maxes = [self.leaf_absmax(g, qh) for g, qh in zip(g_leaves, q_leaves)]
        if per_leaf:
            return maxes, jnp.max(jnp.stack(maxes))
        R = jnp.max(jnp.stack([m for m, g in zip(maxes, g_leaves) if g.size]
                              or [jnp.zeros((), jnp.float32)]))
        return [R for _ in g_leaves], R

    @scoped(WIRE_QUANTIZE)
    def innovation(self, grad, qhat, per_leaf=False):
        """Radius via the pass-1 absmax reduction; the diff itself stays a
        lazy elementwise expression for downstream consumers (the adaptive
        quantizer), so no extra full-gradient sweep is spent on it here."""
        diff = jax.tree.map(
            lambda g, q: g.astype(jnp.float32) - q.astype(jnp.float32),
            grad, qhat)
        g_leaves, treedef = jax.tree_util.tree_flatten(grad)
        q_leaves = jax.tree_util.tree_leaves(qhat)
        R_leaves, R_max = self._radii(g_leaves, q_leaves, per_leaf)
        R_tree = jax.tree_util.tree_unflatten(treedef, R_leaves)
        return diff, R_tree, R_max

    @scoped(WIRE_QUANTIZE)
    def roundtrip(self, grad, qhat, bits, per_leaf=False, with_payload=False):
        assert bits in (1, 2, 4, 8), \
            f"fused wire backend covers the packed-width grid, got bits={bits}"
        g_leaves, treedef = jax.tree_util.tree_flatten(grad)
        q_leaves = jax.tree_util.tree_leaves(qhat)
        R_leaves, R_max = self._radii(g_leaves, q_leaves, per_leaf)
        use_pallas = self._use_pallas()

        delta_leaves, qnew_leaves, payload = [], [], []
        err_parts, inn_parts = [], []
        for g, qh, R in zip(g_leaves, q_leaves, R_leaves):
            if g.size == 0:
                delta_leaves.append(jnp.zeros(g.shape, jnp.float32))
                qnew_leaves.append(jnp.zeros(g.shape, jnp.float32))
                if with_payload:
                    # keep the payload list leaf-aligned (one entry per leaf)
                    payload.append(jnp.zeros((0,), jnp.uint8))
                continue
            if use_pallas:
                from repro.kernels import quantize_pack_fused
                pk, dl, qn, esq, isq = quantize_pack_fused(g, qh, R, bits)
            else:
                dl, qn, esq, isq, pk = _fused_leaf_jnp(g, qh, R, bits,
                                                       with_payload)
            delta_leaves.append(dl.reshape(g.shape))
            qnew_leaves.append(qn.reshape(g.shape))
            err_parts.append(esq)
            inn_parts.append(isq)
            if with_payload:
                payload.append(pk)

        err_sq = (jnp.sum(jnp.stack(err_parts)) if err_parts
                  else jnp.zeros((), jnp.float32))
        inn_sq = (jnp.sum(jnp.stack(inn_parts)) if inn_parts
                  else jnp.zeros((), jnp.float32))
        return WireRoundtrip(
            q_new=jax.tree_util.tree_unflatten(treedef, qnew_leaves),
            delta=jax.tree_util.tree_unflatten(treedef, delta_leaves),
            R_tree=jax.tree_util.tree_unflatten(treedef, R_leaves),
            R_max=R_max, err_sq=err_sq, innovation_sq=inn_sq,
            payload=payload if with_payload else None)

    @scoped(WIRE_QUANTIZE)
    def adaptive_roundtrip(self, grad, qhat, diff, R_tree, grid, onehot):
        """Adaptive pass 2 as ONE sweep: the width-grid-unrolled fused
        kernel (kernels/quant_pack.py — one ``lax.switch`` arm per grid
        width, each arm the static-width pipeline) off-CPU, the dense flat
        jnp expression of the same sweep on CPU.  ``diff`` is deliberately
        unused here: innovation() keeps it a lazy elementwise expression,
        and this path recomputes g - qh inside the sweep instead of
        materializing the tensor."""
        grid = tuple(grid)
        assert all(b in (1, 2, 4, 8) for b in grid), \
            f"fused wire backend covers the packed-width grid, got {grid}"
        t_sel = tau_of_selection(grid, onehot)
        use_pallas = self._use_pallas()
        g_leaves, treedef = jax.tree_util.tree_flatten(grad)
        q_leaves = jax.tree_util.tree_leaves(qhat)
        R_leaves = jax.tree_util.tree_leaves(R_tree)

        delta_leaves, qnew_leaves, err_parts, inn_parts = [], [], [], []
        for g, qh, R in zip(g_leaves, q_leaves, R_leaves):
            if g.size == 0:
                delta_leaves.append(jnp.zeros(g.shape, jnp.float32))
                qnew_leaves.append(jnp.zeros(g.shape, jnp.float32))
                continue
            if use_pallas:
                from repro.kernels import quantize_pack_adaptive
                _, dl, qn, esq, isq = quantize_pack_adaptive(
                    g, qh, R, onehot, grid)
            else:
                dl, qn, esq, isq, _ = _fused_leaf_adaptive_jnp(
                    g, qh, R, grid, onehot, t_sel)
            delta_leaves.append(dl.reshape(g.shape))
            qnew_leaves.append(qn.reshape(g.shape))
            err_parts.append(esq)
            inn_parts.append(isq)

        err_sq = (jnp.sum(jnp.stack(err_parts)) if err_parts
                  else jnp.zeros((), jnp.float32))
        inn_sq = (jnp.sum(jnp.stack(inn_parts)) if inn_parts
                  else jnp.zeros((), jnp.float32))
        return (jax.tree_util.tree_unflatten(treedef, qnew_leaves),
                jax.tree_util.tree_unflatten(treedef, delta_leaves),
                err_sq, inn_sq)

    def dequant_acc(self, packed, R, keep, bits, n, acc=None):
        if n and self._use_pallas():
            from repro.kernels import dequant_acc
            return dequant_acc(packed, R, keep, bits, n, acc)
        from repro.kernels.ref import dequant_acc_ref
        return dequant_acc_ref(packed, R.astype(jnp.float32),
                               keep.astype(jnp.float32), bits, n, acc)

    def sparse_quantize(self, vals, lo, hi, bits):
        if vals.size == 0:
            return (jnp.zeros((0,), jnp.uint8), jnp.zeros((0,), jnp.float32))
        if self._use_pallas():
            from repro.kernels import sparse_quantize_pack
            _, codes, deq = sparse_quantize_pack(vals, lo, hi, bits)
            return codes, deq
        # blocked-jnp lowering: the gathered values vector is dense and
        # flat, so the op-for-op expressions ARE the reference's — wire
        # content is bit-identical on CPU by construction
        return reference_sparse_quantize(vals, lo, hi, bits)


_BACKENDS = {
    "reference": ReferenceWire(),
    "fused": FusedWire(),
}


def get_backend(name) -> WireBackend:
    """Resolve a backend by name (or pass a WireBackend instance through —
    tests use that to pin the fused lowering)."""
    if isinstance(name, WireBackend):
        return name
    try:
        return _BACKENDS[name]
    except KeyError:
        raise ValueError(
            f"unknown wire backend {name!r}; have {sorted(_BACKENDS)}") from None


# ---------------------------------------------------------------------------
# Sparse wire roundtrip — the EF-LAQ compressor pipeline's integration
# point (core/compressors.py supplies the stages; worker_update calls
# this).  Selection, scatter, moments and payload packing are SHARED code;
# only the quantize stage's elementwise map routes through the backend, so
# the bit-identity contract of the dense wire extends to the sparse one.
# ---------------------------------------------------------------------------

class SparseRoundtrip(NamedTuple):
    """One worker's sparse quantize step (mirrors :class:`WireRoundtrip`
    plus the sparse payload halves)."""
    q_new: Pytree           # qhat + delta (dense-shaped)
    delta: Pytree           # sparse-valued dequantized innovation
    lo: jax.Array           # magnitude-grid floor sidecar (f32 scalar)
    R: jax.Array            # magnitude-grid ceiling sidecar (max |survivor|)
    err_sq: jax.Array       # support-restricted quantization error (see below)
    innovation_sq: jax.Array  # ||delta||^2 (criterion LHS)
    idx: jax.Array          # int32 [k] sorted support (the index payload)
    codes: jax.Array        # uint8 [k] b-bit codes (pre-packing)
    payload: Optional[jax.Array]  # packed uint8 code bytes (with_payload only)


def sparse_roundtrip(backend, grad: Pytree, qhat: Pytree, bits: int, k: int,
                     mode: str, key=None,
                     with_payload: bool = False) -> SparseRoundtrip:
    """Sparsify-then-quantize roundtrip over the flattened innovation.

    ``grad`` is the (EF-corrected) gradient ``g_eff``; the innovation
    ``d = g_eff - qhat`` is flattened over the pytree, ``k`` coordinates
    survive (``mode``: "topk" / "randk", ``key`` for randk), the survivors
    are quantized on the sign-magnitude b-bit grid over ``[lo, hi]``
    (core/compressors.py — contractive on the survivor range, which the
    EF recursion requires; the dense wire's zero-less grid is not), and
    the receiver's dense view is scattered back.  Two f32 sidecars ``(lo,
    hi)`` — the per-leaf radius bucketing of the dense wire does not apply
    (the support already concentrates the scale).

    ``err_sq`` is the **support-restricted** quantization error
    ``sum_{i in S} (d_i - deq_i)^2`` — the criterion's epsilon-hat moment.
    The dropped tail is deliberately NOT counted: it is the sparsifier's
    deferred mass (EF's residual re-injects it next round), not wire
    noise, and folding it into epsilon-hat blows up the 7a threshold's
    ``3(eps + eps_prev)`` term so far past the innovation that every
    worker skips forever after its first upload.
    """
    backend = get_backend(backend)
    gflat, meta = _flat(grad)
    qflat, _ = _flat(qhat)
    d = gflat - qflat
    sel = select_support(mode, d, k, key)
    lo, hi = sparse_grid(sel.vals, bits)
    codes, deq = backend.sparse_quantize(sel.vals, lo, hi, bits)
    delta_flat = scatter_selection(sel, deq, d.shape[0])
    qn_flat = qflat + delta_flat
    err = sel.vals - deq
    err_sq = jnp.sum(err * err)
    inn_sq = jnp.sum(delta_flat * delta_flat)
    payload = pack_flat(codes, bits) if with_payload else None
    return SparseRoundtrip(q_new=_unflat(qn_flat, meta),
                           delta=_unflat(delta_flat, meta),
                           lo=lo, R=hi, err_sq=err_sq, innovation_sq=inn_sq,
                           idx=sel.idx, codes=codes, payload=payload)


# ---------------------------------------------------------------------------
# Code-space inverse maps — recover the integer wire codes from a
# dequantized leaf and re-emit after mutating them.  Used by the fault
# layer (core/faults.py: MSB flips on the packed codes) and usable by any
# consumer that needs to edit a payload without re-running the quantizer.
# Exact on the emit path's own output: ``delta = 2 tau R q - R`` is
# recovered by rounding ``(delta + R) / (2 tau R)`` — the float32 rounding
# noise of the forward map is orders of magnitude below the half-step the
# round absorbs (codes are <= 255).
# ---------------------------------------------------------------------------

def codes_of_delta(delta: jax.Array, R, bits: int) -> jax.Array:
    """Inverse of the dequant map on one leaf: uint8 codes from ``delta``.

    ``R == 0`` emits the midpoint code, matching the forward map's
    convention for an identically-zero innovation.
    """
    t = tau(bits)
    levels = 2 ** bits - 1
    denom = jnp.where(R > 0, 2.0 * t * R, 1.0)
    q = jnp.round((delta.astype(jnp.float32) + R) / denom)
    q = jnp.clip(q, 0, levels)
    q = jnp.where(R > 0, q, (levels + 1) // 2 * jnp.ones_like(q))
    return q.astype(jnp.uint8)


def delta_of_codes(codes: jax.Array, R, bits: int) -> jax.Array:
    """Re-emit the dequantized leaf from (possibly mutated) codes — the
    same expression as quantize.dequantize_innovation, per leaf."""
    t = tau(bits)
    d = 2.0 * t * R * codes.astype(jnp.float32) - R
    return jnp.where(R > 0, d, jnp.zeros_like(d))


# ---------------------------------------------------------------------------
# Axis-packed wire payload helpers — the sharded collective wire format
# shared by launch/train.py (pack along the LAST dim: flattening a
# model-sharded leaf would force GSPMD to regather it).  Same
# little-end-first byte semantics as pack_codes / the Pallas kernels.
# ---------------------------------------------------------------------------

def axis_packable(q, bits: int) -> bool:
    cpb = 8 // bits
    return cpb > 1 and q.ndim >= 1 and q.shape[-1] % cpb == 0


@scoped(WIRE_CODEC)
def pack_codes_along_axis(q, bits: int):
    """Pack 8/b codes per byte along the last dim (no-op layout for b=8 or
    an indivisible last dim: raw uint8 codes ship unpacked)."""
    if not axis_packable(q, bits):
        return q
    cpb = 8 // bits
    parts = q.reshape(q.shape[:-1] + (q.shape[-1] // cpb, cpb))
    acc = parts[..., 0]
    for j in range(1, cpb):
        acc = acc | (parts[..., j] << (bits * j))
    return acc.astype(jnp.uint8)


@scoped(WIRE_CODEC)
def unpack_codes_along_axis(payload, bits: int, orig):
    """Inverse of :func:`pack_codes_along_axis`; ``orig`` supplies the
    unpacked shape (and whether packing applied at all)."""
    if not axis_packable(orig, bits):
        return payload
    cpb = 8 // bits
    mask = (1 << bits) - 1
    parts = [(payload >> (bits * j)) & mask for j in range(cpb)]
    return jnp.stack(parts, axis=-1).reshape(orig.shape)
