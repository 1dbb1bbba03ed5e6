"""Bit-identity of the pluggable wire backends (core/wire.py).

The contract under test: the ``fused`` two-pass backend produces the same
wire bits as the ``reference`` jnp path across the full
{qgd, laq} x bits {2, 4, 8} x {global, per-leaf} grid — bitwise for the
wire content (codes, radii, delta, q_new) and for whole simulated LAQ
trajectories; scalar criterion moments to f32 reduction accuracy (see the
core/wire.py docstring for why the last ulp is fusion-dependent).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (BitSchedule, CriterionConfig, StrategyConfig,
                        run_gradient_based, run_stochastic, worker_update)
from repro.core.quantize import innovation
from repro.core.strategy import aggregate, init_comm_state
from repro.core.wire import (FusedWire, axis_packable, get_backend,
                             pack_codes_along_axis, unpack_codes_along_axis)

BITS = (2, 4, 8)
RADII = (False, True)
GRID = (2, 4, 8)       # adaptive bit_schedule grid under test


def _tree(seed=0):
    """Leaf sizes chosen to exercise padding: odd, non-multiple-of-8/b,
    multi-dim, and > one kernel block."""
    key = jax.random.PRNGKey(seed)
    ks = jax.random.split(key, 6)
    return {
        "w1": jax.random.normal(ks[0], (300,)) * 2,
        "w2": jax.random.normal(ks[1], (17, 5)),
        "w3": jax.random.normal(ks[2], (4097,)) * 0.3,
        "b": jax.random.normal(ks[3], (1,)),
    }


def _qhat(seed=10):
    t = _tree(seed)
    return jax.tree.map(lambda l: 0.5 * l, t)


def _trees_equal(a, b):
    return all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


@pytest.mark.parametrize("bits", BITS)
@pytest.mark.parametrize("per_leaf", RADII)
def test_roundtrip_wire_content_bit_identical(bits, per_leaf):
    g, qh = _tree(), _qhat()
    ref = jax.jit(lambda g, qh: get_backend("reference").roundtrip(
        g, qh, bits, per_leaf))(g, qh)
    fus = jax.jit(lambda g, qh: get_backend("fused").roundtrip(
        g, qh, bits, per_leaf))(g, qh)
    assert _trees_equal(ref.delta, fus.delta)
    assert _trees_equal(ref.q_new, fus.q_new)
    assert _trees_equal(ref.R_tree, fus.R_tree)
    assert float(ref.R_max) == float(fus.R_max)
    np.testing.assert_allclose(float(fus.err_sq), float(ref.err_sq),
                               rtol=1e-6)
    np.testing.assert_allclose(float(fus.innovation_sq),
                               float(ref.innovation_sq), rtol=1e-6)


@pytest.mark.parametrize("kind", ["qgd", "laq"])
@pytest.mark.parametrize("bits", BITS)
@pytest.mark.parametrize("per_leaf", RADII)
def test_worker_update_bit_identical(kind, bits, per_leaf):
    """The state machine sees identical wire bits: masked delta, new qhat,
    upload decision, eps state and wire-bit accounting all match bitwise."""
    g, qh = _tree(), _qhat()
    theta_hist = jnp.full((10,), 0.3, jnp.float32)
    crit = CriterionConfig(D=10, xi=0.08, t_bar=100)

    def upd(backend):
        cfg = StrategyConfig(kind=kind, bits=bits, per_leaf_radius=per_leaf,
                             criterion=crit, wire_backend=backend)
        return jax.jit(lambda g, qh: worker_update(
            g, qh, jnp.float32(0.05), jnp.int32(3), jnp.float32(0.0),
            theta_hist, 0.1, 10, cfg))(g, qh)

    r = upd("reference")
    f = upd("fused")
    names = ("delta_masked", "qhat_new", "eps_hat_sq", "clock", "uploaded",
             "bits_m", "R", "width")
    for name, a, b in zip(names, r, f):
        assert _trees_equal(a, b), f"{name} differs across wire backends"


@pytest.mark.parametrize("kind", ["qgd", "laq"])
@pytest.mark.parametrize("bits", BITS)
@pytest.mark.parametrize("per_leaf", RADII)
def test_trajectory_bit_identical(kind, bits, per_leaf):
    """A whole simulated multi-worker run (vmap + scan, skip criterion in
    the loop) reproduces the identical trajectory on either backend."""
    key = jax.random.PRNGKey(0)
    kc, ka = jax.random.split(key)
    M, p = 10, 20
    centers = jax.random.normal(kc, (M, p))
    scales = 0.5 + jax.random.uniform(ka, (M, p))

    def loss_fn(params, data):
        c, a = data
        return 0.5 * jnp.sum(a * jnp.square(params["x"] - c)) / M

    p0 = {"x": jnp.zeros((p,))}

    def run(backend):
        cfg = StrategyConfig(kind=kind, bits=bits, per_leaf_radius=per_leaf,
                             criterion=CriterionConfig(D=10, xi=0.08, t_bar=100),
                             wire_backend=backend)
        return run_gradient_based(loss_fn, p0, (centers, scales), cfg,
                                  steps=120, alpha=0.3)

    rr, rf = run("reference"), run("fused")
    np.testing.assert_array_equal(np.asarray(rr.loss), np.asarray(rf.loss))
    np.testing.assert_array_equal(np.asarray(rr.cum_bits),
                                  np.asarray(rf.cum_bits))
    np.testing.assert_array_equal(np.asarray(rr.cum_uploads),
                                  np.asarray(rf.cum_uploads))
    np.testing.assert_array_equal(np.asarray(rr.params["x"]),
                                  np.asarray(rf.params["x"]))


@pytest.mark.parametrize("bits", BITS)
@pytest.mark.parametrize("variant", ["wk2", "svrg", "wk2+svrg"])
def test_stochastic_trajectory_bit_identical(bits, variant):
    """The new stochastic kinds ride the same wire: a whole run_stochastic
    trajectory under the WK2 same-sample rule and/or svrg-corrected
    gradients (second backprops, anchor refresh cond, minibatch sampling in
    the loop) reproduces bitwise across wire backends."""
    key = jax.random.PRNGKey(3)
    kx, ky = jax.random.split(key)
    M, n_local, p = 6, 12, 8
    X = jax.random.normal(kx, (M, n_local, p))
    w_true = jnp.linspace(-1.0, 1.0, p)
    Yn = X @ w_true + 0.3 * jax.random.normal(ky, (M, n_local))

    def loss_fn(params, data):
        x, y = data
        return 0.5 * jnp.sum(jnp.square(x @ params["w"] - y)) / (M * n_local)

    p0 = {"w": jnp.zeros((p,))}
    kind = "slaq" if variant == "svrg" else "slaq_wk2"
    grad_mode = "sgd" if variant == "wk2" else "svrg"

    def run(backend):
        cfg = StrategyConfig(kind="laq", bits=bits,
                             criterion=CriterionConfig(D=10, xi=0.08, t_bar=20),
                             wire_backend=backend, grad_mode=grad_mode,
                             svrg_period=7)
        return run_stochastic(loss_fn, p0, (X, Yn), kind, steps=50,
                              alpha=0.3, batch=4, bits=bits, seed=2,
                              laq_cfg=cfg)

    rr, rf = run("reference"), run("fused")
    np.testing.assert_array_equal(np.asarray(rr.loss), np.asarray(rf.loss))
    np.testing.assert_array_equal(np.asarray(rr.cum_bits),
                                  np.asarray(rf.cum_bits))
    np.testing.assert_array_equal(np.asarray(rr.cum_uploads),
                                  np.asarray(rf.cum_uploads))
    np.testing.assert_array_equal(np.asarray(rr.params["w"]),
                                  np.asarray(rf.params["w"]))


@pytest.mark.parametrize("per_leaf", RADII)
@pytest.mark.parametrize("sched_kind", ["radius", "budget"])
def test_adaptive_bits_accounting_matches_across_backends(per_leaf, sched_kind):
    """Satellite fix: per-leaf radii mean ``n_sidecars = n_leaves`` f32
    sidecars in ``upload_bits``; the accounting lives in worker_update and
    must be backend-independent — both backends report identical bits_m,
    widths and cumulative totals through the adaptive path."""
    sched = BitSchedule(kind=sched_kind, thresholds=(0.05, 0.5),
                        total_bits=5e6, horizon=20)
    g = _tree()
    grads = jax.tree.map(lambda l: jnp.stack([l * (1 + 0.1 * w)
                                              for w in range(4)]), g)

    def run(backend):
        cfg = StrategyConfig(kind="laq", bits=4, per_leaf_radius=per_leaf,
                             criterion=CriterionConfig(D=10, xi=0.08, t_bar=100),
                             bit_schedule=sched, wire_backend=backend)
        st = init_comm_state(g, 4, cfg)
        outs = []
        for _ in range(3):
            agg, st, metrics = aggregate(st, grads, 0.1, cfg)
            outs.append((metrics.bits, metrics.mean_bits, st.bits_spent,
                         st.total_bits))
        return outs, agg, st

    (or_, agg_r, st_r) = run("reference")
    (of_, agg_f, st_f) = run("fused")
    for (br, wr, sr, tr), (bf, wf, sf, tf) in zip(or_, of_):
        np.testing.assert_array_equal(np.asarray(br), np.asarray(bf))
        np.testing.assert_array_equal(np.asarray(wr), np.asarray(wf))
        np.testing.assert_array_equal(np.asarray(sr), np.asarray(sf))
        np.testing.assert_array_equal(np.asarray(tr), np.asarray(tf))
    assert _trees_equal(agg_r, agg_f)
    assert _trees_equal(st_r.qhat, st_f.qhat)


@pytest.mark.parametrize("sel", range(len(GRID)))
@pytest.mark.parametrize("per_leaf", RADII)
def test_adaptive_roundtrip_bit_identical(sel, per_leaf):
    """Adaptive pass 2 through the backends at every pinned grid width:
    the staged reference sweep (quantize_dynamic/dequantize_dynamic) vs the
    fused one-sweep pipeline — q_new/delta bitwise, scalar moments to f32
    reduction accuracy (same contract as the fixed-width roundtrip)."""
    g, qh = _tree(), _qhat()
    onehot = jnp.eye(len(GRID), dtype=jnp.float32)[sel]

    def rt(backend):
        def f(g, qh):
            diff, R_tree, _ = innovation(g, qh, per_leaf)
            return get_backend(backend).adaptive_roundtrip(
                g, qh, diff, R_tree, GRID, onehot)
        return jax.jit(f)(g, qh)

    r, f = rt("reference"), rt("fused")
    assert _trees_equal(r[0], f[0]), "q_new differs across wire backends"
    assert _trees_equal(r[1], f[1]), "delta differs across wire backends"
    np.testing.assert_allclose(float(f[2]), float(r[2]), rtol=1e-6)
    np.testing.assert_allclose(float(f[3]), float(r[3]), rtol=1e-6)


# abs-mode threshold pairs that pin the radius schedule to each grid width
# for a whole run (R > both / between / below both), plus the natural
# schedule that walks down the grid as the innovation radius decays
_PIN_2 = (1e30, 2e30)
_PIN_4 = (1e-30, 1e30)
_PIN_8 = (1e-30, 2e-30)


@pytest.mark.parametrize("thresholds",
                         [_PIN_2, _PIN_4, _PIN_8, (0.05, 0.5)])
def test_adaptive_trajectory_bit_identical(thresholds):
    """A whole simulated adaptive run (bit_schedule selection + dynamic
    quantizer in the scan loop) reproduces identically whether pass 2 is
    the staged reference sweep or the fused kernel — at every pinned grid
    width and across the mixed-width natural schedule."""
    key = jax.random.PRNGKey(0)
    kc, ka = jax.random.split(key)
    M, p = 10, 20
    centers = jax.random.normal(kc, (M, p))
    scales = 0.5 + jax.random.uniform(ka, (M, p))

    def loss_fn(params, data):
        c, a = data
        return 0.5 * jnp.sum(a * jnp.square(params["x"] - c)) / M

    p0 = {"x": jnp.zeros((p,))}
    sched = BitSchedule(kind="radius", grid=GRID, thresholds=thresholds)

    def run(backend):
        cfg = StrategyConfig(kind="laq", bits=4,
                             criterion=CriterionConfig(D=10, xi=0.08,
                                                       t_bar=100),
                             bit_schedule=sched, wire_backend=backend)
        return run_gradient_based(loss_fn, p0, (centers, scales), cfg,
                                  steps=120, alpha=0.3)

    rr, rf = run("reference"), run("fused")
    np.testing.assert_array_equal(np.asarray(rr.loss), np.asarray(rf.loss))
    np.testing.assert_array_equal(np.asarray(rr.cum_bits),
                                  np.asarray(rf.cum_bits))
    np.testing.assert_array_equal(np.asarray(rr.cum_uploads),
                                  np.asarray(rf.cum_uploads))
    np.testing.assert_array_equal(np.asarray(rr.params["x"]),
                                  np.asarray(rf.params["x"]))


@pytest.mark.parametrize("sel", range(len(GRID)))
def test_fused_adaptive_pallas_lowering_matches_jnp(sel):
    """The two lowerings of the adaptive fused pass 2 implement one
    algorithm: the interpret-mode width-grid-unrolled Pallas kernel vs the
    dense flat jnp sweep.  Same tolerance contract as the fixed-width
    lowering test."""
    g, qh = _tree(), _qhat()
    onehot = jnp.eye(len(GRID), dtype=jnp.float32)[sel]

    def rt(lowering):
        diff, R_tree, _ = innovation(g, qh, False)
        return FusedWire(lowering=lowering).adaptive_roundtrip(
            g, qh, diff, R_tree, GRID, onehot)

    j, p = rt("jnp"), rt("pallas")
    for a, b in zip(jax.tree.leaves(j[0]), jax.tree.leaves(p[0])):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), atol=1e-5)
    for a, b in zip(jax.tree.leaves(j[1]), jax.tree.leaves(p[1])):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), atol=1e-5)
    np.testing.assert_allclose(float(p[2]), float(j[2]), rtol=1e-4,
                               atol=1e-6)
    np.testing.assert_allclose(float(p[3]), float(j[3]), rtol=1e-4,
                               atol=1e-6)


@pytest.mark.parametrize("bits", BITS)
def test_fused_pallas_lowering_matches_jnp(bits):
    """The two lowerings of the fused backend implement one algorithm:
    interpret-mode Pallas (the TPU kernels) vs the blocked jnp expression.
    Codes are exact; floats to interpret-mode accuracy (no XLA mul-add
    contraction there)."""
    g, qh = _tree(), _qhat()
    jnp_rt = FusedWire(lowering="jnp").roundtrip(g, qh, bits, False,
                                                 with_payload=True)
    pls_rt = FusedWire(lowering="pallas").roundtrip(g, qh, bits, False,
                                                    with_payload=True)
    assert float(jnp_rt.R_max) == float(pls_rt.R_max)
    for a, b in zip(jax.tree.leaves(jnp_rt.delta), jax.tree.leaves(pls_rt.delta)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)
    np.testing.assert_allclose(float(jnp_rt.err_sq), float(pls_rt.err_sq),
                               rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(float(jnp_rt.innovation_sq),
                               float(pls_rt.innovation_sq), rtol=1e-4,
                               atol=1e-6)
    # payload layouts differ only in pad length and in the kernel's rows:
    # real code bytes, read in order, agree
    cpb = 8 // bits
    for pj, pp, leaf in zip(jnp_rt.payload, pls_rt.payload,
                            jax.tree.leaves(g)):
        nbytes = leaf.size // cpb
        np.testing.assert_array_equal(np.asarray(pj[:nbytes]),
                                      np.asarray(pp).reshape(-1)[:nbytes])


def test_dequant_acc_backends_match():
    W, n, bits = 4, 5000, 4
    key = jax.random.PRNGKey(1)
    packed = jax.random.randint(key, (W, 2560), 0, 256).astype(jnp.uint8)
    R = jax.random.uniform(jax.random.fold_in(key, 1), (W,))
    keep = jnp.array([1.0, 0.0, 1.0, 1.0])
    acc = jax.random.normal(jax.random.fold_in(key, 2), (n,))
    ref = get_backend("reference").dequant_acc(packed, R, keep, bits, n, acc)
    fus = FusedWire(lowering="jnp").dequant_acc(packed, R, keep, bits, n, acc)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(fus))


def test_get_backend():
    assert get_backend("fused").name == "fused"
    assert get_backend(FusedWire(lowering="jnp")).name == "fused"
    with pytest.raises(ValueError):
        get_backend("nope")


@pytest.mark.parametrize("bits", BITS)
def test_axis_pack_helpers_roundtrip(bits):
    key = jax.random.PRNGKey(2)
    q = jax.random.randint(key, (6, 16), 0, 2 ** bits).astype(jnp.uint8)
    payload = pack_codes_along_axis(q, bits)
    assert payload.shape[-1] == 16 * bits // 8
    np.testing.assert_array_equal(
        np.asarray(unpack_codes_along_axis(payload, bits, q)), np.asarray(q))
    odd = jax.random.randint(key, (5, 7), 0, 2 ** bits).astype(jnp.uint8)
    if bits == 8 or not axis_packable(odd, bits):
        # raw-code shipping path: identity both ways
        np.testing.assert_array_equal(
            np.asarray(pack_codes_along_axis(odd, bits)), np.asarray(odd))


# ---------------------------------------------------------------------------
# Sparse wire (EF-LAQ compressor pipeline) — the bit-identity contract
# extends to the sparse payload: selection/scatter/moments/packing are
# shared code, only the quantize stage's elementwise map is per-backend.
# ---------------------------------------------------------------------------

SPARSE_BITS = (1, 2, 4)
SPARSE_MODES = ("topk", "randk")


@pytest.mark.parametrize("mode", SPARSE_MODES)
@pytest.mark.parametrize("bits", SPARSE_BITS)
def test_sparse_roundtrip_bit_identical(mode, bits):
    from repro.core.wire import sparse_roundtrip
    g, qh = _tree(), _qhat()
    key = jax.random.PRNGKey(5)
    k = 173    # odd, not a multiple of codes-per-byte

    def rt(backend):
        return jax.jit(lambda g, qh: sparse_roundtrip(
            get_backend(backend), g, qh, bits, k, mode, key=key,
            with_payload=True))(g, qh)

    r, f = rt("reference"), rt("fused")
    np.testing.assert_array_equal(np.asarray(r.idx), np.asarray(f.idx))
    np.testing.assert_array_equal(np.asarray(r.codes), np.asarray(f.codes))
    np.testing.assert_array_equal(np.asarray(r.payload), np.asarray(f.payload))
    assert float(r.lo) == float(f.lo) and float(r.R) == float(f.R)
    assert _trees_equal(r.delta, f.delta)
    assert _trees_equal(r.q_new, f.q_new)
    np.testing.assert_array_equal(np.asarray(r.err_sq), np.asarray(f.err_sq))
    np.testing.assert_array_equal(np.asarray(r.innovation_sq),
                                  np.asarray(f.innovation_sq))


@pytest.mark.parametrize("bits", SPARSE_BITS)
def test_sparse_pallas_lowering_matches_reference(bits):
    """The interpret-mode Pallas sparse kernel (kernels/quant_pack.py)
    mirrors reference_sparse_quantize op-for-op: codes exact, dequantized
    values to interpret-mode float accuracy."""
    from repro.core.compressors import (reference_sparse_quantize,
                                        sparse_grid)
    from repro.kernels import sparse_quantize_pack
    vals = jax.random.normal(jax.random.PRNGKey(2), (397,)) * 1.7
    lo, hi = sparse_grid(vals, bits)
    rc, rd = reference_sparse_quantize(vals, lo, hi, bits)
    _, pc, pd = sparse_quantize_pack(vals, lo, hi, bits, interpret=True)
    np.testing.assert_array_equal(np.asarray(rc), np.asarray(pc))
    np.testing.assert_allclose(np.asarray(pd), np.asarray(rd), atol=1e-6)


@pytest.mark.parametrize("mode", SPARSE_MODES)
@pytest.mark.parametrize("ef", [False, True])
def test_sparse_worker_update_bit_identical(mode, ef):
    """The compressed worker state machine (masked delta, qhat, eps, bit
    accounting, and the EF residual commit) matches bitwise across wire
    backends."""
    from repro.core.compressors import ErrorState, compressor_keys
    g, qh = _tree(), _qhat()
    err = ErrorState(residual=jax.tree.map(
        lambda l: 0.01 * l, g)) if ef else ErrorState(None)
    ckey = compressor_keys(0, jnp.int32(3), 4)[1] if mode == "randk" else None
    theta_hist = jnp.full((10,), 0.3, jnp.float32)
    crit = CriterionConfig(D=10, xi=0.08, t_bar=100)

    def upd(backend):
        cfg = StrategyConfig(kind="laq", bits=2, criterion=crit,
                             wire_backend=backend, compressor=mode,
                             compressor_k=0.05, error_feedback=ef)
        return jax.jit(lambda g, qh: worker_update(
            g, qh, jnp.float32(0.05), jnp.int32(3), jnp.float32(0.0),
            theta_hist, 0.1, 10, cfg, error_m=err, ckey_m=ckey))(g, qh)

    r, f = upd("reference"), upd("fused")
    names = ("delta_masked", "qhat_new", "eps_hat_sq", "clock", "uploaded",
             "bits_m", "R", "width", "lazy", "R_anchor", "error_new")
    for name, a, b in zip(names, r, f):
        assert _trees_equal(a, b), f"{name} differs across wire backends"


@pytest.mark.parametrize("mode", SPARSE_MODES)
@pytest.mark.parametrize("bits", (1, 2))
def test_sparse_trajectory_bit_identical(mode, bits):
    """A whole simulated EF-LAQ run (compressor pipeline + error memory +
    skip criterion in the scan loop) reproduces identically on either
    backend."""
    key = jax.random.PRNGKey(0)
    kc, ka = jax.random.split(key)
    M, p = 8, 24
    centers = jax.random.normal(kc, (M, p))
    scales = 0.5 + jax.random.uniform(ka, (M, p))

    def loss_fn(params, data):
        c, a = data
        return 0.5 * jnp.sum(a * jnp.square(params["x"] - c)) / M

    p0 = {"x": jnp.zeros((p,))}

    def run(backend):
        cfg = StrategyConfig(kind="laq", bits=bits,
                             criterion=CriterionConfig(D=10, xi=0.08,
                                                       t_bar=100),
                             wire_backend=backend, compressor=mode,
                             compressor_k=0.25, error_feedback=True)
        return run_gradient_based(loss_fn, p0, (centers, scales), cfg,
                                  steps=100, alpha=0.1)

    rr, rf = run("reference"), run("fused")
    np.testing.assert_array_equal(np.asarray(rr.loss), np.asarray(rf.loss))
    np.testing.assert_array_equal(np.asarray(rr.cum_bits),
                                  np.asarray(rf.cum_bits))
    np.testing.assert_array_equal(np.asarray(rr.cum_uploads),
                                  np.asarray(rf.cum_uploads))
    np.testing.assert_array_equal(np.asarray(rr.params["x"]),
                                  np.asarray(rf.params["x"]))
