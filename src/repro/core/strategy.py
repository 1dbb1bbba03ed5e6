"""Communication strategies: GD / QGD / LAG / LAQ (+ stochastic variants).

All four gradient-based methods of the paper are one state machine with two
switches:

    quantize?  lazy-skip?
GD     no         no        theta^{k+1} = theta^k - alpha * sum_m grad_m
QGD    yes        no        paper eq. (3)
LAG    no         yes       Chen et al. 2018 (paper ref [6])
LAQ    yes        yes       paper eq. (4) + criterion (7)

The *server* aggregate  ``agg^k = agg^{k-1} + sum_{m in M^k} deltaQ_m^k``  is
maintained as replicated SPMD state.  Stochastic variants (SGD/SLAQ) use the
same machinery on minibatch gradients; for those, ``StrategyConfig.lazy_rule``
selects the skip criterion — the paper's eq. 7a, or the variance-aware
LASG-WK / LASG-PS rules of :mod:`repro.core.lazy_rules` whose per-worker
estimator state rides in ``CommState.lazy``.

Two execution modes share the same per-worker math (``worker_update``):

* **simulated** — a leading worker axis ``W`` on the gradient pytree, vmapped.
  Used by the paper-reproduction benchmarks (M=10 workers on one device).
* **sharded** — called per-shard inside ``jax.shard_map`` where the worker
  axis is a mesh axis; the caller supplies the psum. See ``launch/train.py``.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from .adaptive import BitSchedule, EtaSchedule, select_bits
from .compressors import (COMPRESSORS, ErrorState, compressor_keys,
                          empty_error_state, init_error_state, static_k)
from .criterion import CriterionConfig, push_history, should_skip
from .defense import (AGGREGATORS, DefenseConfig, DefenseState, defense_step,
                      empty_defense_state, init_defense_state,
                      robust_aggregate)
from .faults import FaultConfig, flip_wire_codes
from .lazy_rules import (LAZY_RULES, LasgConfig, LazyState, commit_upload,
                         empty_lazy_state, init_lazy_state, lazy_rule_step)
from .quantize import (dense_bits, sparse_upload_bits, tree_size,
                       tree_sq_norm, upload_bits)
from .wire import get_backend, sparse_roundtrip

Pytree = object

KINDS = ("gd", "qgd", "lag", "laq")


class StrategyConfig(NamedTuple):
    kind: str = "laq"               # one of KINDS
    bits: int = 4                   # quantization bits per coordinate
    criterion: CriterionConfig = CriterionConfig()
    per_leaf_radius: bool = False   # paper: one global R; True = bucketed
    first_round_upload: bool = True  # init clocks at t_bar: round 1 is dense
    state_bf16: bool = False        # store qhat/server_agg in bf16 (beyond-
                                    # paper memory opt; grid values tolerate it
                                    # and the innovation loop self-corrects)
    bit_schedule: Optional[BitSchedule] = None  # None/"constant" -> fixed
                                    # bits; adaptive kinds pick b_m^k per
                                    # worker per round (core/adaptive.py)
    wire_backend: str = "reference"  # quantize-pipeline implementation
                                    # (core/wire.py): "reference" jnp vs
                                    # "fused" two-pass Pallas/blocked-jnp;
                                    # bit-identical wire content either way
    lazy_rule: str = "laq7a"        # skip criterion for lazy kinds
                                    # (core/lazy_rules.py): "laq7a" paper
                                    # eq. 7a; "lasg_wk" variance-corrected
                                    # worker rule; "lasg_wk2" same-sample
                                    # noise-free rule (2nd backprop);
                                    # "lasg_ps" server-side parameter-drift
                                    # rule
    lasg: LasgConfig = LasgConfig()  # constants of the LASG rules
    grad_mode: str = "sgd"          # stochastic local-gradient estimator:
                                    # "sgd" plain minibatch; "svrg"
                                    # variance-reduced (periodic per-worker
                                    # full-gradient anchor in CommState.svrg,
                                    # corrected minibatch gradients fed to
                                    # the lazy rules AND the quantizer).
                                    # Deterministic runs ignore it.
    svrg_period: int = 20           # rounds between svrg anchor refreshes
    eta_schedule: EtaSchedule = EtaSchedule()  # per-round stepsize alpha_k
                                    # (core/adaptive.py): constant / inv_t /
                                    # halving; feeds both the update and the
                                    # criterion's 1/(alpha^2 M^2) term
    participation: str = "full"     # which workers the server reaches each
                                    # round (core/engine.py): "full" |
                                    # "bernoulli" / "fixed_k" client sampling
                                    # | "markov" bursty on/off churn
                                    # | "delay" bounded-staleness async
                                    # ("markov"/"delay": simulated engine only)
    participation_p: float = 1.0    # bernoulli keep-probability / fixed_k
                                    # cohort fraction (k = round(p * W))
    max_delay: int = 0              # "delay": staleness bound D; worker m
                                    # computes at theta^{k - (m mod (D+1))}
    participation_seed: int = 0     # seed of the availability stream
                                    # (independent of batch/compressor RNG)
    compressor: str = "none"        # sparsifying compressor stage
                                    # (core/compressors.py): "none" dense
                                    # quantization (the paper); "topk" /
                                    # "randk" keep k of p innovation
                                    # coordinates before the b-bit grid —
                                    # wire cost 64 + k (b + ceil(log2 p))
    compressor_k: float = 0.25      # kept fraction: k = round(frac * p),
                                    # static under jit
    error_feedback: bool = False    # EF-LAQ: carry the compression residual
                                    # e_m in CommState.error and add it back
                                    # before the next compress (committed on
                                    # upload only, frozen over skips)
    ef_damping: float = 0.5         # injection weight eta on the carried
                                    # residual: g_eff = g + eta e.  eta = 1
                                    # (textbook EF) double-counts the
                                    # innovation reference's implicit error
                                    # carry — loop gain (1 + eta) rho — and
                                    # diverges whenever the compressor's
                                    # contraction rho >= 1/2 (rand-k, 1-bit
                                    # grids); see docs/compressors.md
    compressor_seed: int = 0        # seed of the randk support stream
                                    # (independent of batch / participation)
    markov_sojourn: float = 8.0     # "markov" participation: mean ON-streak
                                    # length in rounds; 1/(1-p) reduces the
                                    # chain to i.i.d. bernoulli(p)
    faults: FaultConfig = FaultConfig()  # fault injection (core/faults.py):
                                    # payload corruption / wire bit-flips /
                                    # crash-restart; all-off by default
    defense: DefenseConfig = DefenseConfig()  # server-side upload validation
                                    # + norm-clipping (core/defense.py); a
                                    # rejected upload is masked exactly like
                                    # a lazy skip, bits counted honestly
    aggregator: str = "sum"         # combination of committed deltas:
                                    # "sum" (the paper's recursion) |
                                    # "trimmed_mean" / "median" coordinate-
                                    # wise robust aggregation (simulated
                                    # engine only; see docs/robustness.md
                                    # for the recursion-drift caveat)
    trim_frac: float = 0.1          # "trimmed_mean": fraction of workers
                                    # trimmed at EACH end (t = floor(f * W),
                                    # min 1)
    # wire mode is a launch-layer concern ("float" psum vs "packed" all_gather);
    # the algorithmic state machine is identical for both.

    @property
    def quantized(self) -> bool:
        return self.kind in ("qgd", "laq")

    @property
    def variance_reduced(self) -> bool:
        return self.grad_mode == "svrg"

    @property
    def lazy(self) -> bool:
        return self.kind in ("lag", "laq")

    @property
    def adaptive(self) -> bool:
        return (self.quantized and self.bit_schedule is not None
                and self.bit_schedule.adaptive)

    @property
    def compressed(self) -> bool:
        return self.compressor != "none"

    @property
    def effective_bits(self) -> int:
        """Static width of the fixed-bit path (a constant schedule routes
        here so it is bit-exact with classic fixed-bit LAQ)."""
        if self.bit_schedule is not None and not self.bit_schedule.adaptive:
            return self.bit_schedule.bits
        return self.bits


class SvrgState(NamedTuple):
    """Per-worker SVRG anchor (``StrategyConfig.grad_mode="svrg"``).

    ``theta_anchor`` is the iterate at the worker's last anchor refresh and
    ``mu_anchor`` its full *local* gradient there; between refreshes the
    runner feeds the corrected minibatch gradient

        g_vr = (n/B) (g(theta; xi) - g(theta_anchor; xi)) + mu_anchor

    to the lazy rules and the quantizer.  Both fields are ``None`` unless
    the strategy is variance-reduced (pytree discipline mirrors
    :class:`~repro.core.lazy_rules.LazyState`: rule-gated fields simply
    vanish from the flattened state).  Leading worker dim in simulated
    mode, one slice per shard in sharded mode — exactly like ``qhat``.
    The refresh itself lives in the engine stages (it needs the loss
    closure and, in simulated mode, the worker's full local data):
    ``apply_svrg_exact`` / ``apply_svrg_streaming`` in ``core/engine.py``.
    """
    theta_anchor: Optional[Pytree]
    mu_anchor: Optional[Pytree]


def init_svrg_state(grad_mode: str, grad_template: Pytree, n_workers: int,
                    *, worker_dim: bool = True) -> SvrgState:
    """Anchor snapshot of the template values (the initial iterate) and a
    zero ``mu``; the runner's round-0 refresh overwrites both."""
    assert grad_mode in ("sgd", "svrg"), grad_mode
    if grad_mode != "svrg":
        return SvrgState(None, None)
    wshape = (n_workers,) if worker_dim else ()

    def snapshot_w(l):
        return jnp.broadcast_to(jnp.asarray(l, jnp.float32), wshape + l.shape)

    return SvrgState(
        theta_anchor=jax.tree.map(snapshot_w, grad_template),
        mu_anchor=jax.tree.map(
            lambda l: jnp.zeros(wshape + l.shape, jnp.float32),
            grad_template))


class CommState(NamedTuple):
    """Replicated/sharded LAQ state.

    ``qhat``/``eps_hat_sq``/``clocks`` carry a leading worker dim W in
    simulated mode; in sharded mode that dim is the mesh worker axis and each
    shard holds its own slice (no leading dim).
    """
    qhat: Pytree            # last uploaded quantized gradient  Q_m(theta_hat)
    server_agg: Pytree      # server aggregate  agg^{k-1}
    eps_hat_sq: jax.Array   # ||eps_hat_m||^2 at last upload
    clocks: jax.Array       # t_m
    bits_spent: jax.Array   # [W] cumulative wire bits per worker (drives the
                            # adaptive budget controller; diagnostics otherwise)
    theta_hist: jax.Array   # [D]  ||theta^{k+1-d} - theta^{k-d}||^2 ring
    total_bits: jax.Array   # float64-ish accumulator (float32 ok for tests)
    total_uploads: jax.Array
    step: jax.Array
    lazy: LazyState         # per-worker LASG estimator state (variance /
                            # smoothness EMAs; pytree fields None for laq7a)
    R_anchor: jax.Array     # [W] anchor radius of the scale-free ("rel")
                            # adaptive thresholds (0 until the bootstrap
                            # round observes the first nonzero R_m)
    svrg: SvrgState         # per-worker SVRG anchor (theta_anchor /
                            # mu_anchor; fields None unless grad_mode="svrg")
    error: ErrorState = ErrorState(None)  # per-worker EF residual e_m
                            # (core/compressors.py; None unless
                            # error_feedback — same gating as lazy/svrg)
    defense: DefenseState = DefenseState(None, None, None)  # per-worker
                            # server-side validation state + reject ledger
                            # (core/defense.py; None unless
                            # DefenseConfig.active — same gating as
                            # lazy/svrg/error)


class RoundMetrics(NamedTuple):
    uploads: jax.Array      # |M^k| this round (transmissions, incl. rejected)
    bits: jax.Array         # wire bits this round
    mean_skip: jax.Array    # fraction of workers skipping
    radius_max: jax.Array   # max_m R_m^k (0 for unquantized)
    mean_bits: jax.Array    # mean selected width over uploading workers
                            # (== the static width for fixed-bit runs)
    rejections: jax.Array = jnp.zeros((), jnp.int32)  # transmissions the
                            # server refused to commit (defense validation)


def init_comm_state(grad_template: Pytree, n_workers: int,
                    cfg: StrategyConfig, *, worker_dim: bool = True) -> CommState:
    """Zero-initialized state. ``grad_template`` gives shapes/dtypes of one
    worker's gradient pytree (no worker dim)."""
    sdtype = jnp.bfloat16 if cfg.state_bf16 else jnp.float32

    def zeros_like_s(l):
        shape = (n_workers,) + l.shape if worker_dim else l.shape
        return jnp.zeros(shape, sdtype)

    assert cfg.lazy_rule in LAZY_RULES, cfg.lazy_rule
    assert cfg.compressor in COMPRESSORS, cfg.compressor
    assert cfg.aggregator in AGGREGATORS, cfg.aggregator
    if cfg.compressed or cfg.error_feedback:
        assert cfg.quantized and not cfg.adaptive, (
            "the compressor pipeline / error feedback require a fixed-bit "
            "quantized kind (qgd / laq)")
    if cfg.faults.wire_faulty:
        assert cfg.quantized and not cfg.adaptive and not cfg.compressed, (
            "wire-code bit-flips model the packed fixed-bit payload: they "
            "need a fixed-bit quantized kind (qgd / laq) without the sparse "
            "compressor pipeline")
    wshape = (n_workers,) if worker_dim else ()
    # clocks start at t_bar when first_round_upload: criterion (7b) then
    # forces a dense first round, bootstrapping qhat / the server aggregate.
    clock0 = cfg.criterion.t_bar if (cfg.lazy and cfg.first_round_upload) else 0
    lazy_rule = cfg.lazy_rule if cfg.lazy else "laq7a"
    return CommState(
        qhat=jax.tree.map(zeros_like_s, grad_template),
        server_agg=jax.tree.map(lambda l: jnp.zeros(l.shape, sdtype), grad_template),
        eps_hat_sq=jnp.zeros(wshape, jnp.float32),
        clocks=jnp.full(wshape, clock0, jnp.int32),
        bits_spent=jnp.zeros(wshape, jnp.float32),
        theta_hist=jnp.zeros((cfg.criterion.D,), jnp.float32),
        total_bits=jnp.zeros((), jnp.float32),
        total_uploads=jnp.zeros((), jnp.int32),
        step=jnp.zeros((), jnp.int32),
        lazy=init_lazy_state(lazy_rule, grad_template, n_workers,
                             worker_dim=worker_dim),
        R_anchor=jnp.zeros(wshape, jnp.float32),
        svrg=init_svrg_state(cfg.grad_mode, grad_template, n_workers,
                             worker_dim=worker_dim),
        error=init_error_state(cfg.error_feedback, grad_template, n_workers,
                               worker_dim=worker_dim),
        defense=init_defense_state(cfg.defense, n_workers,
                                   worker_dim=worker_dim),
    )


# ---------------------------------------------------------------------------
# Per-worker update: the heart of LAQ.  Pure; no collectives.
# ---------------------------------------------------------------------------

class WorkerOut(NamedTuple):
    """Result of :func:`worker_update`.

    The leading eight fields keep the historical positional order, so
    *indexed* access (``out[0]``..``out[7]``) and ``zip``-style iteration
    over a prefix stay valid — but the arity grew from 8, so fixed-arity
    tuple unpacking of the old return must move to the named fields.
    """
    delta_masked: Pytree    # masked contribution to the server refinement
    qhat_new: Pytree
    eps_hat_sq_new: jax.Array
    clock_new: jax.Array
    uploaded: jax.Array     # transmission bit: the worker SENT a payload
                            # (drives bits_m / total_uploads even when the
                            # server rejects it)
    bits_m: jax.Array
    R: jax.Array
    width_m: jax.Array      # selected width b_m^k (static width on the
                            # fixed path, 32 for dense uploads)
    lazy_new: LazyState     # updated LASG estimator state
    R_anchor_new: jax.Array  # updated scale-free threshold anchor
    error_new: ErrorState   # updated EF residual (None-gated pass-through
                            # when error_feedback is off)
    committed: jax.Array = True  # commit bit: the server APPLIED the payload
                            # (== uploaded unless defense validation rejected
                            # it; drives qhat/eps/clock/estimator commits)
    defense_new: DefenseState = DefenseState(None, None, None)  # updated
                            # validation state (None-gated pass-through)
    payload: Optional[list] = None  # the roundtrip's per-leaf packed codes
                            # (with_payload only: the whole-leaf wire ships
                            # them as the quantizer left them)
    payload_R: Pytree = None  # their per-leaf radii (with_payload only)


def worker_update(grad_m: Pytree, qhat_m: Pytree, eps_hat_sq_m, clock_m,
                  bits_spent_m, theta_hist, alpha, n_workers: int,
                  cfg: StrategyConfig, step=None, lazy_m=None,
                  R_anchor_m=None, params=None, grad_stale_m=None,
                  avail_m=None, error_m=None, ckey_m=None, defense_m=None,
                  flip_m=None, fkey_m=None, with_payload=False):
    """One worker's bit-width selection + quantize + skip decision.

    ``lazy_m`` is this worker's :class:`~repro.core.lazy_rules.LazyState`
    slice and ``R_anchor_m`` its scale-free threshold anchor (both optional
    for ``lazy_rule="laq7a"`` with absolute thresholds); ``params`` is the
    current (replicated) iterate, required by the ``lasg_wk2`` / ``lasg_ps``
    rules; ``grad_stale_m`` is the WK2 same-sample second backprop (the
    current minibatch at the worker's stale iterate), required by that rule
    only.  ``avail_m`` is this worker's participation bit (core/engine.py).
    ``error_m`` is this worker's :class:`~repro.core.compressors.ErrorState`
    slice (EF-LAQ: its residual is added back before compressing and
    re-committed on upload) and ``ckey_m`` its rand-k support key
    (``compressor_keys``; ignored by topk).  ``defense_m`` is this worker's
    :class:`~repro.core.defense.DefenseState` slice (required when
    ``cfg.defense.active``); ``flip_m`` / ``fkey_m`` are the wire-fault
    mask bit and flip-position key (``core/faults.py``, bitflip kind only).
    ``with_payload`` (fixed-width quantized kinds) keeps the roundtrip's
    packed payload and its per-leaf radii in ``WorkerOut.payload`` /
    ``payload_R``, so that the sharded step's whole-leaf wire ships the
    codes this round's quantize made instead of quantizing again.

    Masking discipline — ONE code path for every way a payload fails to
    commit.  Two bits gate the state commits:

    * ``uploaded`` — the worker transmitted: the (honest, pre-fault) skip
      rule said upload AND the worker was reachable (``avail_m``).  Drives
      the bits/uploads accounting: a transmission costs wire bits whether
      or not the server accepts it.
    * ``committed`` — the server applied the payload: ``uploaded`` AND the
      defense validation accepted it.  Drives every state commit —
      ``delta_masked``, ``qhat``, ``eps_hat_sq``, clock reset, estimator
      snapshots, the EF residual.  Without an active defense ``committed``
      IS ``uploaded`` (no extra ops), so a lazy skip, an unreachable worker
      and a rejected upload all flow through the same masked-commit block:
      no ``qhat`` commit, clock grows, state frozen.  The only asymmetry is
      honest accounting: rejects pay bits, skips/absences do not.

    Returns a :class:`WorkerOut`; ``delta_masked`` is zero unless
    committed.
    """
    p = tree_size(grad_m)
    if lazy_m is None:
        lazy_m = empty_lazy_state()
    if R_anchor_m is None:
        R_anchor_m = jnp.zeros((), jnp.float32)
    if error_m is None:
        error_m = empty_error_state()
    if cfg.compressed or cfg.error_feedback:
        assert cfg.quantized and not cfg.adaptive, (
            "the compressor pipeline / error feedback require a fixed-bit "
            "quantized kind (qgd / laq)")
    if cfg.faults.wire_faulty:
        assert cfg.quantized and not cfg.adaptive and not cfg.compressed, (
            "wire-code bit-flips need the plain fixed-bit quantized path")
    if cfg.error_feedback:
        # EF: compress the residual-corrected gradient g_eff = g + eta e.
        # eta (cfg.ef_damping) tempers the loop gain — the innovation
        # reference already re-injects untransmitted mass implicitly, so
        # undamped EF counts it twice (see docs/compressors.md)
        assert error_m.residual is not None, \
            "error_feedback needs CommState.error (init_comm_state gates it)"
        g_eff = jax.tree.map(
            lambda g, e: g.astype(jnp.float32) + cfg.ef_damping * e,
            grad_m, error_m.residual)
    else:
        g_eff = grad_m
    # sidecar count is wire-backend-INDEPENDENT by construction: both
    # backends exchange one f32 radius per leaf (per-leaf mode) or one
    # global radius, so bits_m accounting is identical across backends
    # (asserted in tests/test_wire_backend.py).
    n_sidecars = (len(jax.tree_util.tree_leaves(grad_m))
                  if cfg.per_leaf_radius else 1)
    backend = get_backend(cfg.wire_backend)
    if with_payload:
        # the payload ships as the quantizer emitted it: no stage below may
        # change the delta it encodes
        assert (cfg.quantized and not cfg.adaptive and not cfg.compressed
                and not cfg.faults.wire_faulty
                and cfg.defense.clip_mult == 0.0), \
            "with_payload needs the plain fixed-width quantized roundtrip"
    payload = payload_R = None
    if cfg.adaptive:
        sched = cfg.bit_schedule
        step_ = jnp.zeros((), jnp.int32) if step is None else step
        # pass 1 of the wire pipeline: the backend's radius reduction (the
        # fused backend computes R without materializing the diff tensor)
        diff, R_tree, R = backend.innovation(grad_m, qhat_m,
                                             cfg.per_leaf_radius)
        width_m, onehot, R_anchor_new = select_bits(
            sched, R, bits_spent_m, step_, p, n_radii=n_sidecars,
            R_anchor=R_anchor_m)
        # pass 2 through the backend: the reference backend runs the staged
        # quantize_dynamic/dequantize_dynamic pipeline (moved verbatim into
        # WireBackend.adaptive_roundtrip — bitwise anchor), the fused
        # backend the width-grid-unrolled one-sweep kernel
        q_new, delta, err_sq, innovation_sq = backend.adaptive_roundtrip(
            grad_m, qhat_m, diff, R_tree, sched.grid, onehot)
        bits_if_upload = upload_bits(p, width_m, n_radii=n_sidecars,
                                     bit_sidecar=True)
    elif cfg.compressed:
        # sparsify -> quantize -> pack on the (EF-corrected) innovation:
        # core/wire.py sparse_roundtrip, stages from core/compressors.py
        srt = sparse_roundtrip(backend, g_eff, qhat_m, cfg.effective_bits,
                               static_k(cfg.compressor_k, p), cfg.compressor,
                               key=ckey_m)
        q_new, delta, R = srt.q_new, srt.delta, srt.R
        err_sq, innovation_sq = srt.err_sq, srt.innovation_sq
        bits_if_upload = float(sparse_upload_bits(
            p, static_k(cfg.compressor_k, p), cfg.effective_bits,
            n_radii=2))     # two f32 sidecars: the (lo, hi) grid endpoints
        width_m = jnp.full((), float(cfg.effective_bits), jnp.float32)
    elif cfg.quantized:
        rt = backend.roundtrip(g_eff, qhat_m, cfg.effective_bits,
                               cfg.per_leaf_radius, with_payload=with_payload)
        q_new, delta, R = rt.q_new, rt.delta, rt.R_max
        R_tree = rt.R_tree      # the wire-fault layer flips codes per leaf
        if with_payload:
            payload, payload_R = rt.payload, rt.R_tree
        # the fused backend emits both criterion moments as in-pass partial
        # sums; the reference backend spends two extra sweeps on them
        err_sq, innovation_sq = rt.err_sq, rt.innovation_sq
        bits_if_upload = float(upload_bits(p, cfg.effective_bits,
                                           n_radii=n_sidecars))
        width_m = jnp.full((), float(cfg.effective_bits), jnp.float32)
    else:
        q_new = jax.tree.map(lambda g: g.astype(jnp.float32), grad_m)
        delta = jax.tree.map(lambda g, q: g - q, q_new, qhat_m)
        R = jnp.zeros((), jnp.float32)
        err_sq = jnp.zeros((), jnp.float32)
        innovation_sq = tree_sq_norm(delta)
        bits_if_upload = float(dense_bits(p))
        width_m = jnp.full((), 32.0, jnp.float32)

    if not cfg.adaptive:
        R_anchor_new = R_anchor_m

    lazy_pre, stats = lazy_m, None
    if cfg.lazy:
        if cfg.lazy_rule == "laq7a":
            skip = should_skip(innovation_sq, theta_hist, alpha, n_workers,
                               err_sq, eps_hat_sq_m, clock_m, cfg.criterion)
        else:
            skip, lazy_pre, stats = lazy_rule_step(
                cfg.lazy_rule, cfg.lasg, cfg.criterion, grad_m=grad_m,
                params=params, lazy_m=lazy_m, innovation_sq=innovation_sq,
                err_sq=err_sq, eps_hat_sq_m=eps_hat_sq_m, clock_m=clock_m,
                theta_hist=theta_hist, alpha=alpha, n_workers=n_workers,
                grad_stale_m=grad_stale_m)
    else:
        skip = jnp.zeros((), bool)
    uploaded = jnp.logical_not(skip)
    if avail_m is not None:
        # participation mask BEFORE the state commits: an unreachable
        # worker must not upload even when the rule (or the 7b staleness
        # bound) demands it — its clock keeps growing and the overdue
        # upload happens at its next available round
        uploaded = jnp.logical_and(uploaded, avail_m)

    if cfg.faults.wire_faulty and flip_m is not None:
        # wire-level fault: MSB flips on this worker's packed codes, AFTER
        # the (honest) skip decision — corruption happens in encode/
        # transit, not in the rule.  The corrupted payload is what both
        # the server aggregate and the worker's own qhat mirror would
        # commit, so the decoded moments are recomputed from it: the
        # defense gate sees what the server sees.
        delta_f = flip_wire_codes(delta, R_tree, cfg.effective_bits, fkey_m,
                                  cfg.faults.bitflip_frac)
        delta = jax.tree.map(lambda a, b: jnp.where(flip_m, b, a),
                             delta, delta_f)
        q_new = jax.tree.map(lambda qh, d: qh.astype(jnp.float32) + d,
                             qhat_m, delta)
        err_sq = tree_sq_norm(jax.tree.map(
            lambda g, qn: g.astype(jnp.float32) - qn, g_eff, q_new))
        innovation_sq = tree_sq_norm(delta)

    if cfg.defense.active:
        # server-side upload validation + norm-clipping on the decoded
        # payload (core/defense.py).  Per-worker-local by construction, so
        # the same code runs per shard in launch/train.py.
        assert defense_m is not None and defense_m.norm_ema is not None, \
            "cfg.defense.active needs CommState.defense (init_comm_state)"
        accept, clip_scale, defense_new = defense_step(
            cfg.defense, defense_m, innovation_sq, err_sq, uploaded)
        committed = jnp.logical_and(uploaded, accept)
        if cfg.defense.clip_mult > 0.0:
            # the SAME scaled delta updates server_agg and the qhat
            # mirror, preserving server_agg == sum_m qhat_m exactly
            delta = jax.tree.map(lambda d: d * clip_scale, delta)
            q_new = jax.tree.map(lambda qh, d: qh.astype(jnp.float32) + d,
                                 qhat_m, delta)
            innovation_sq = innovation_sq * clip_scale * clip_scale
            if cfg.compressed:
                # the sparse path's err_sq is support-restricted; scaling
                # the dequant values rescales it only approximately —
                # exact at scale 1 (the no-clip case), conservative
                # otherwise (documented in docs/robustness.md)
                err_sq = err_sq * clip_scale * clip_scale
            else:
                err_sq = tree_sq_norm(jax.tree.map(
                    lambda g, qn: g.astype(jnp.float32) - qn, g_eff, q_new))
    else:
        committed = uploaded
        defense_new = defense_m if defense_m is not None \
            else empty_defense_state()

    if stats is not None:
        lazy_new = commit_upload(cfg.lazy_rule, cfg.lasg, lazy_pre, committed,
                                 stats, params=params,
                                 innovation_sq=innovation_sq)
    else:
        lazy_new = lazy_pre
    if avail_m is not None:
        # an unreachable worker ran no local computation this round: hold
        # its estimator state (variance/smoothness EMAs, snapshots) and its
        # adaptive threshold anchor as well
        lazy_new = jax.tree.map(lambda n, o: jnp.where(avail_m, n, o),
                                lazy_new, lazy_m)
        R_anchor_new = jnp.where(avail_m, R_anchor_new, R_anchor_m)

    # the single masked-commit block: `committed` (== `uploaded` without an
    # active defense) gates every state commit; `uploaded` alone pays bits.
    # Select, don't multiply: a rejected Inf payload would turn 0 * inf
    # into NaN and poison the server sum through the mask.
    delta_masked = jax.tree.map(
        lambda d: jnp.where(committed, d, jnp.zeros_like(d)), delta)
    qhat_new = jax.tree.map(lambda qn, qh: jnp.where(committed, qn.astype(qh.dtype), qh),
                            q_new, qhat_m)
    eps_hat_sq_new = jnp.where(committed, err_sq, eps_hat_sq_m)
    clock_new = jnp.where(committed, 0, clock_m + 1).astype(jnp.int32)
    bits_m = uploaded.astype(jnp.float32) * bits_if_upload
    if cfg.error_feedback:
        # the residual commits only on a committed upload (a skipped or
        # rejected round changes nothing server-side, so its compression
        # error never happened): e_new = g_eff - q_new — the mass this
        # round's compress dropped
        error_new = ErrorState(residual=jax.tree.map(
            lambda g, qn, e: jnp.where(committed,
                                       g.astype(jnp.float32) - qn, e),
            g_eff, q_new, error_m.residual))
    else:
        error_new = error_m
    return WorkerOut(delta_masked, qhat_new, eps_hat_sq_new, clock_new,
                     uploaded, bits_m, R, width_m, lazy_new, R_anchor_new,
                     error_new, committed, defense_new, payload, payload_R)


# ---------------------------------------------------------------------------
# Simulated cluster mode (vmap over a leading worker axis).
# ---------------------------------------------------------------------------

def aggregate(state: CommState, grads: Pytree, alpha, cfg: StrategyConfig,
              params: Pytree = None, grads_stale: Pytree = None,
              avail: jax.Array = None, fault_flip: jax.Array = None,
              fault_keys: jax.Array = None):
    """Aggregate per-worker gradients (leading dim W) into the LAQ gradient.

    ``params`` is the current (replicated) iterate — required by the
    ``lasg_wk2`` / ``lasg_ps`` lazy rules, ignored otherwise;
    ``grads_stale`` (leading dim W, same structure as ``grads``) is the WK2
    same-sample second backprop; ``avail`` ([W] bool) is the round's
    participation mask (core/engine.py) — unreachable workers are masked
    exactly like lazy skips; ``fault_flip`` / ``fault_keys`` ([W] bool /
    [W] keys) drive the wire-code bit-flip fault (core/faults.py, engine-
    supplied).  Returns ``(agg_grad, new_state, metrics)``.  The caller
    applies ``theta <- theta - alpha * agg_grad`` (or feeds agg_grad to an
    optimizer) and then calls :func:`finalize_step` with the realized
    parameter change.
    """
    n_workers = state.clocks.shape[0]
    have_stale, have_avail = grads_stale is not None, avail is not None
    have_flip = fault_flip is not None
    have_ckey = cfg.compressor == "randk"
    ckeys = (compressor_keys(cfg.compressor_seed, state.step, n_workers)
             if have_ckey else None)

    def upd(*args):
        # theta_hist / params are replicated across workers: closed over,
        # not vmapped
        (grad_m, qhat_m, eps_m, clock_m, spent_m, lazy_m, anchor_m,
         err_m, defense_m) = args[:9]
        rest = list(args[9:])
        ckey_m = rest.pop(0) if have_ckey else None
        grad_stale_m = rest.pop(0) if have_stale else None
        avail_m = rest.pop(0) if have_avail else None
        flip_m = rest.pop(0) if have_flip else None
        fkey_m = rest.pop(0) if have_flip else None
        return worker_update(grad_m, qhat_m, eps_m, clock_m, spent_m,
                             state.theta_hist, alpha, n_workers, cfg,
                             step=state.step, lazy_m=lazy_m,
                             R_anchor_m=anchor_m, params=params,
                             grad_stale_m=grad_stale_m, avail_m=avail_m,
                             error_m=err_m, ckey_m=ckey_m,
                             defense_m=defense_m, flip_m=flip_m,
                             fkey_m=fkey_m)

    wargs = (grads, state.qhat, state.eps_hat_sq, state.clocks,
             state.bits_spent, state.lazy, state.R_anchor, state.error,
             state.defense)
    if have_ckey:
        wargs = wargs + (ckeys,)
    if have_stale:
        wargs = wargs + (grads_stale,)   # vmap cannot map a None arg
    if have_avail:
        wargs = wargs + (avail,)
    if have_flip:
        wargs = wargs + (fault_flip, fault_keys)
    wu = jax.vmap(upd)(*wargs)

    # Server recursion: agg^k = agg^{k-1} + sum_m deltaQ_m ("sum"), or the
    # robust combination of the committed deltas (core/defense.py) — same
    # scale, bounded drift from the per-worker qhat mirrors (documented in
    # docs/robustness.md).
    if cfg.aggregator == "sum":
        agg = jax.tree.map(lambda a, d: a + jnp.sum(d, axis=0),
                           state.server_agg, wu.delta_masked)
    else:
        robust = robust_aggregate(cfg.aggregator, wu.delta_masked,
                                  wu.committed, cfg.trim_frac)
        agg = jax.tree.map(lambda a, r: a + r, state.server_agg, robust)

    uploaded, bits_m = wu.uploaded, wu.bits_m
    uploads = jnp.sum(uploaded.astype(jnp.int32))
    rejections = jnp.sum(jnp.logical_and(
        uploaded, jnp.logical_not(wu.committed)).astype(jnp.int32))
    bits = jnp.sum(bits_m)
    fup = uploaded.astype(jnp.float32)
    metrics = RoundMetrics(uploads=uploads, bits=bits,
                           mean_skip=1.0 - uploads / n_workers,
                           radius_max=jnp.max(wu.R),
                           mean_bits=jnp.sum(wu.width_m * fup)
                           / jnp.maximum(jnp.sum(fup), 1.0),
                           rejections=rejections)
    new_state = state._replace(
        qhat=wu.qhat_new, server_agg=agg, eps_hat_sq=wu.eps_hat_sq_new,
        clocks=wu.clock_new,
        bits_spent=state.bits_spent + bits_m,
        total_bits=state.total_bits + bits,
        total_uploads=state.total_uploads + uploads,
        step=state.step + 1,
        lazy=wu.lazy_new, R_anchor=wu.R_anchor_new, error=wu.error_new,
        defense=wu.defense_new,
    )
    return agg, new_state, metrics


def finalize_step(state: CommState, theta_diff_sq) -> CommState:
    """Push ||theta^{k+1}-theta^k||^2 into the criterion's history ring."""
    return state._replace(theta_hist=push_history(state.theta_hist, theta_diff_sq))
