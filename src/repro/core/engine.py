"""Unified round engine: ONE implementation of the LAQ communication round.

Before this module the per-round protocol — sample -> local gradients ->
SVRG correction -> WK2 stale backprop -> lazy rule -> quantize -> aggregate
-> update — was hand-threaded three times: ``run_gradient_based`` and
``run_stochastic`` in :mod:`repro.core.simulated` plus the sharded step in
``launch/train.py``.  Every new lever (LASG rules, SVRG, stepsize
schedules) had to be wired in triplicate.  The engine factors the round
into pluggable stages so a new rule plugs in once:

* :class:`GradientSource` — where this round's per-worker gradients come
  from.  ``FullBatchSource`` (deterministic GD/QGD/LAG/LAQ: the full local
  gradient), ``MinibatchSource`` (SGD family: fold_in-keyed minibatches,
  ``(n/B)``-scaled), ``AccumulatingSource`` (the LM-scale worker: the same
  minibatch stream folded over sequential microbatches via
  :func:`accumulate_loss_grads`, with a ``per_device`` parallelism knob and
  a ``deterministic`` full-corpus mode).  The SVRG correction and the WK2
  same-sample stale
  backprop are *engine* stages expressed through the source's ``eval_at``,
  so their math lives here exactly once (:func:`apply_svrg_exact` /
  :func:`apply_svrg_streaming` / :func:`stale_side_grads` — the streaming
  variant is the sharded launch path's documented one-batch-anchor
  degradation).

* :class:`ParticipationModel` — which workers the server can reach this
  round.  ``full`` (every round, the paper's setting), ``bernoulli`` /
  ``fixed_k`` client sampling (LAG's heterogeneous-worker motivation:
  workers are intermittently available), and ``delay`` — bounded-staleness
  async execution where worker ``m`` computes its gradient at the iterate
  from ``d_m <= max_delay`` rounds ago (a replicated params history ring).
  Unavailable workers are masked **exactly like lazy skips** inside
  ``worker_update`` (clock grows, no wire bits, ``qhat`` and estimator
  state frozen), so ``CommState`` clocks, ``total_uploads`` and bits
  accounting stay correct — and the LAQ skip criterion composes with
  sampling (``benchmarks/participation_frontier.py`` measures the
  frontier).  Selected via ``StrategyConfig.participation`` /
  ``participation_p`` / ``max_delay`` / ``participation_seed``.

* the LAQ state machine itself — unchanged, in
  :mod:`repro.core.strategy` (``aggregate`` / ``worker_update``); dense
  baselines (sgd / qsgd / ssgd) run the compressor path instead.

``RoundEngine.round`` is a ``jax.lax.scan`` body; ``run`` scans it and
returns the same :class:`RunResult` the wrappers always produced.  The
wrappers in :mod:`repro.core.simulated` are thin shims over this class and
reproduce their pre-engine trajectories **bitwise** for every existing
kind x lazy_rule x grad_mode x wire_backend combination
(tests/test_engine_parity.py pins them against captured goldens).  The
stage contract — what a new source, participation model or rule must
provide — is documented in ``docs/engine.md``.

Availability semantics: the simulation still *computes* every worker's
gradient (a vmap lane costs nothing to mask, and SPMD shards cannot skip a
backprop anyway); participation governs the **wire** — who may upload,
whose state may advance.  The accounting (uploads, bits, clocks) is what
the paper's communication model measures.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from .adaptive import eta_at
from .compressors import qsgd_compress, ssgd_compress
from .faults import (apply_crashes, bitflip_keys, corrupt_grads,
                     corruption_mask, crash_mask)
from .quantize import dense_bits, tree_size, tree_sq_norm
from .strategy import (CommState, StrategyConfig, SvrgState, aggregate,
                       finalize_step, init_comm_state)

Pytree = object

PARTICIPATION = ("full", "bernoulli", "fixed_k", "markov", "delay")


class RunResult(NamedTuple):
    """Per-round trajectory of a simulated run (all arrays are [K]).

    ``mean_bits`` units differ by family — documented HERE, nowhere else:
    for the LAQ family it is the mean selected quantization width ``b``
    over the workers that uploaded this round (== the static width for
    fixed-bit runs, 32 for dense lazy uploads); for the sgd/qsgd/ssgd
    baselines it is mean *wire bits per coordinate* (total compressed
    payload / p), which for ssgd includes the index overhead.  ``None``
    when a caller constructs a result without the diagnostic.
    """
    params: Pytree
    loss: jax.Array          # [K] global loss per iteration
    grad_norm_sq: jax.Array  # [K]
    cum_uploads: jax.Array   # [K] cumulative communication rounds
    cum_bits: jax.Array      # [K] cumulative wire bits
    quant_err: jax.Array     # [K] max_m R_m (decay diagnostic, paper Fig. 3)
    mean_bits: Optional[jax.Array] = None


def broadcast_w(tree: Pytree, n_workers: int) -> Pytree:
    """Replicate a (replicated) pytree across a leading worker axis, f32."""
    return jax.tree.map(lambda l: jnp.broadcast_to(
        l.astype(jnp.float32), (n_workers,) + l.shape), tree)


# ---------------------------------------------------------------------------
# Gradient sources.
# ---------------------------------------------------------------------------

class FullBatchSource:
    """Deterministic full-gradient source (paper Table 2 methods).

    ``loss_fn(params, data_shard) -> scalar`` is one worker's local loss
    f_m; ``worker_data`` carries a leading worker axis W; the global
    objective is ``sum_m f_m`` (paper eq. 1).
    """
    stochastic = False

    def __init__(self, loss_fn, worker_data: Pytree):
        self.loss_fn = loss_fn
        self.worker_data = worker_data
        self.n_workers = jax.tree_util.tree_leaves(worker_data)[0].shape[0]
        self._grad = jax.grad(loss_fn)

    def sample(self, step):
        return None

    def eval_at(self, params, thetas_w, batches):
        """Per-worker full local gradients.  ``thetas_w=None`` evaluates at
        the replicated ``params`` (the closure form the pre-engine runner
        used — kept so full-participation trajectories stay bitwise);
        otherwise at per-worker iterates (WK2 stale side, delay mode)."""
        if thetas_w is None:
            return jax.vmap(lambda d: self._grad(params, d))(self.worker_data)
        return jax.vmap(lambda t, d: self._grad(t, d))(thetas_w,
                                                       self.worker_data)

    def global_loss(self, params):
        return jnp.sum(jax.vmap(lambda d: self.loss_fn(params, d))(
            self.worker_data))

    def grad_norm_sq(self, params, grads):
        """PR-5 perf fix: the summed per-worker full gradients ARE the
        global gradient, so the record costs a reduction instead of a third
        backprop per round.  (Under ``delay`` participation the summed
        gradients are evaluated at stale iterates — the record is then the
        norm of the aggregate the server actually received.)"""
        return tree_sq_norm(jax.tree.map(lambda g: jnp.sum(g, axis=0), grads))


class MinibatchSource:
    """Minibatch gradient source (paper Table 3 methods).

    Every key derives functionally from ``(seed, stream, round, worker)``
    by ``fold_in`` — no carried split chain — so the batch stream is
    kind-stable and each worker's stream independent (determinism-
    regression-tested).  Stream 0 draws batches, stream 1 the compressor
    randomness.  Worker gradients are scaled by ``n_local / batch`` so
    ``sum_m E[g_m]`` equals the global-loss gradient.
    """
    stochastic = True

    def __init__(self, loss_fn, worker_data: Pytree, *, batch: int, seed: int):
        self.loss_fn = loss_fn
        self.worker_data = worker_data
        leaves = jax.tree_util.tree_leaves(worker_data)
        self.n_workers = leaves[0].shape[0]
        self.n_local = leaves[0].shape[1]
        self.batch = batch
        self.scale = self.n_local / batch
        self._grad = jax.grad(loss_fn)
        self._key0 = jax.random.PRNGKey(seed)
        self._worker_ids = jnp.arange(self.n_workers)

    def stream_keys(self, stream: int, step):
        ks = jax.random.fold_in(jax.random.fold_in(self._key0, stream), step)
        return jax.vmap(lambda m: jax.random.fold_in(ks, m))(self._worker_ids)

    def sample(self, step):
        def sample1(data_m, key):
            idx = jax.random.randint(key, (self.batch,), 0, self.n_local)
            return jax.tree.map(lambda x: x[idx], data_m)

        return jax.vmap(sample1)(self.worker_data, self.stream_keys(0, step))

    def eval_at(self, params, thetas_w, batches):
        """This round's minibatch gradients at per-worker iterates (the
        current params when ``thetas_w=None``; the WK2 stale iterates; the
        SVRG anchors; delay-mode stale params), f32 and ``n/B``-scaled."""
        if thetas_w is None:
            thetas_w = broadcast_w(params, self.n_workers)
        return jax.vmap(lambda t, b: jax.tree.map(
            lambda g: g.astype(jnp.float32) * self.scale,
            self._grad(t, b)))(thetas_w, batches)

    def full_local_grads(self, params):
        """Exact per-worker full local gradients (the SVRG anchor's mu;
        already on the global scale — ``loss_fn`` normalizes by N)."""
        return jax.vmap(lambda d: self._grad(params, d))(self.worker_data)

    def global_loss(self, params):
        return jnp.sum(jax.vmap(lambda d: self.loss_fn(params, d))(
            self.worker_data))

    def grad_norm_sq(self, params, grads):
        # the round's minibatch gradients are noisy estimates: the
        # diagnostic wants the TRUE gradient norm, which costs its own
        # (full-data) backprop here — the full-batch source reuses its
        # exact gradients instead
        return tree_sq_norm(jax.grad(self.global_loss)(params))


def accumulate_loss_grads(loss_fn, params, microbatches):
    """Fold ``(loss, grad)`` over a leading microbatch axis in one scan — the
    levanter ``accumulate_gradients_sharded`` idiom: per-microbatch
    ``value_and_grad`` with an f32 running *mean* (``acc + x / n``), so the
    peak activation memory is one microbatch's backprop regardless of the
    logical batch size.

    ``loss_fn(params, microbatch) -> scalar`` must be **mean-convention**
    (a per-example/per-token mean): the mean of equal-sized microbatch means
    equals the full-batch mean, so the fold reproduces the full-batch
    gradient up to f32 reduction order (one-microbatch folds are exact —
    add-zero and divide-by-one are identity in IEEE).  Shared by
    :class:`AccumulatingSource` and the sharded step's ``loss_and_grads``
    (launch/train.py), so both execution modes accumulate with identical
    arithmetic.
    """
    n = jax.tree_util.tree_leaves(microbatches)[0].shape[0]

    def body(carry, b):
        loss_acc, g_acc = carry
        l, g = jax.value_and_grad(loss_fn)(params, b)
        g_acc = jax.tree.map(lambda a, x: a + x.astype(jnp.float32) / n,
                             g_acc, g)
        return (loss_acc + l / n, g_acc), None

    zero = (jnp.zeros((), jnp.float32),
            jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params))
    return jax.lax.scan(body, zero, microbatches)[0]


class AccumulatingSource:
    """Gradient-accumulating minibatch source — the LM-scale worker.

    Each round every worker draws ``batch`` local examples from the SAME
    fold_in key stream as :class:`MinibatchSource` (stream 0, identical
    indices for identical ``(seed, batch)``), then folds loss+grad over
    ``accum`` sequential microbatches of ``batch / accum`` examples via
    :func:`accumulate_loss_grads` instead of one monolithic backprop.
    ``per_device`` is the parallelism knob expressed the levanter way:
    the largest number of examples evaluated at once, with
    ``accum = batch // per_device`` derived from it.

    Two contracts, both pinned by tests/test_lm_engine.py:

    * ``accum=1`` is **bit-identical** to ``MinibatchSource`` (the fold
      degenerates to add-zero / divide-by-one); ``accum>1`` matches to f32
      reduction order (pinned-ulp) for mean-convention losses.
    * ``deterministic=True`` ignores the sampler and streams the whole
      local corpus through the fold each round — full-batch LAQ (paper
      Table 2 semantics, :class:`FullBatchSource` gradients) at the
      accumulation memory profile; ``stochastic`` reports False so the
      engine treats it as a deterministic method.

    ``scale`` multiplies the folded gradient; the default ``n_local/batch``
    matches ``MinibatchSource`` (sum-convention global objectives).  LM
    losses are token means with the ``1/W`` global normalization already in
    ``loss_fn`` (see ``repro.models.lm_worker_loss``) — pass ``scale=1.0``.
    """

    def __init__(self, loss_fn, worker_data: Pytree, *, batch: Optional[int] = None,
                 seed: int = 0, accum: int = 1, per_device: Optional[int] = None,
                 deterministic: bool = False, scale: Optional[float] = None):
        self.loss_fn = loss_fn
        self.worker_data = worker_data
        leaves = jax.tree_util.tree_leaves(worker_data)
        self.n_workers = leaves[0].shape[0]
        self.n_local = leaves[0].shape[1]
        if deterministic:
            batch = self.n_local
        assert batch is not None, "batch is required for stochastic mode"
        if per_device is not None:
            assert batch % per_device == 0, (batch, per_device)
            accum = batch // per_device
        assert batch % accum == 0, (batch, accum)
        self.batch = batch
        self.accum = accum
        self.micro = batch // accum
        self.deterministic = deterministic
        self.stochastic = not deterministic
        self.scale = (self.n_local / batch) if scale is None else scale
        self._key0 = jax.random.PRNGKey(seed)
        self._worker_ids = jnp.arange(self.n_workers)

    def stream_keys(self, stream: int, step):
        ks = jax.random.fold_in(jax.random.fold_in(self._key0, stream), step)
        return jax.vmap(lambda m: jax.random.fold_in(ks, m))(self._worker_ids)

    def sample(self, step):
        """[W, accum, micro, ...] microbatches.  Stochastic mode draws the
        SAME ``(batch,)`` index vector as ``MinibatchSource`` and reshapes
        it into microbatches; deterministic mode chunks the whole corpus."""
        if self.deterministic:
            return jax.tree.map(
                lambda x: x.reshape((x.shape[0], self.accum, self.micro)
                                    + x.shape[2:]), self.worker_data)

        def sample1(data_m, key):
            idx = jax.random.randint(key, (self.batch,), 0, self.n_local)
            idx = idx.reshape(self.accum, self.micro)
            return jax.tree.map(lambda x: x[idx], data_m)

        return jax.vmap(sample1)(self.worker_data, self.stream_keys(0, step))

    def eval_at(self, params, thetas_w, batches):
        """This round's accumulated gradients at per-worker iterates, f32
        and ``scale``-multiplied — same evaluation-point contract as
        ``MinibatchSource.eval_at`` (WK2 stale iterates, SVRG anchors and
        delay-mode params all route through here with identical
        microbatching)."""
        if thetas_w is None:
            thetas_w = broadcast_w(params, self.n_workers)

        if self.accum == 1:
            # one microbatch: evaluate directly, exactly like
            # MinibatchSource (and like the sharded step's microbatch==1
            # special case) — the scan wrapper would perturb XLA's fusion
            # and cost the bit-identity contract a ulp
            return jax.vmap(lambda t, b: jax.tree.map(
                lambda g: g.astype(jnp.float32) * self.scale,
                jax.grad(self.loss_fn)(
                    t, jax.tree.map(lambda x: jnp.squeeze(x, 0), b))))(
                thetas_w, batches)

        def one(t, mbs):
            _, g = accumulate_loss_grads(self.loss_fn, t, mbs)
            return jax.tree.map(lambda x: x * self.scale, g)

        return jax.vmap(one)(thetas_w, batches)

    def _chunk_full(self, data_m):
        c = self.micro if self.n_local % self.micro == 0 else self.n_local
        return jax.tree.map(
            lambda x: x.reshape((self.n_local // c, c) + x.shape[1:]), data_m)

    def full_local_grads(self, params):
        """Exact per-worker full local gradients (the SVRG anchor's mu),
        accumulated over corpus chunks at the configured microbatch size —
        mean-convention ``loss_fn`` means no extra scale, exactly like
        ``MinibatchSource.full_local_grads``."""
        def one(data_m):
            _, g = accumulate_loss_grads(self.loss_fn, params,
                                         self._chunk_full(data_m))
            return g

        return jax.vmap(one)(self.worker_data)

    def global_loss(self, params):
        def worker_loss(data_m):
            mbs = self._chunk_full(data_m)
            n = jax.tree_util.tree_leaves(mbs)[0].shape[0]

            def body(acc, b):
                return acc + self.loss_fn(params, b) / n, None

            return jax.lax.scan(body, jnp.zeros((), jnp.float32), mbs)[0]

        return jnp.sum(jax.vmap(worker_loss)(self.worker_data))

    def grad_norm_sq(self, params, grads):
        if self.deterministic:
            # the summed full-corpus gradients ARE the global gradient
            # (FullBatchSource's reduction-not-backprop record)
            return tree_sq_norm(jax.tree.map(lambda g: jnp.sum(g, axis=0),
                                             grads))
        return tree_sq_norm(jax.grad(self.global_loss)(params))


# ---------------------------------------------------------------------------
# Shared round stages: SVRG correction and the WK2 stale side.  These are
# the blocks that used to be copy-pasted between run_gradient_based,
# run_stochastic and launch/train.py — they live here once now.
# ---------------------------------------------------------------------------

def apply_svrg_exact(sv: SvrgState, params, grads, grad_at, full_local_grads,
                     step, cfg: StrategyConfig, n_workers: int):
    """SVRG correction with an exact periodic anchor (simulated runners).

    Every ``cfg.svrg_period`` rounds the anchor snaps to the current
    iterate and ``mu`` to the exact full *local* gradient there (inside a
    ``lax.cond`` — the refresh backprop only runs on refresh rounds);
    between refreshes the correction ``mu - g(theta_anchor; xi)`` is added
    to the minibatch gradient.  ``grad_at(thetas_w)`` must evaluate the
    CURRENT sample at arbitrary per-worker iterates (the engine closes it
    over this round's batches) so the same ``corr`` can hit the WK2 stale
    side and anchors cancel in the same-sample difference.

    Returns ``(grads_corrected, corr, sv_new)``.
    """

    def refresh(s):
        mu = full_local_grads(params)
        return SvrgState(
            theta_anchor=broadcast_w(params, n_workers),
            mu_anchor=jax.tree.map(lambda g: g.astype(jnp.float32), mu))

    sv = jax.lax.cond(step % cfg.svrg_period == 0, refresh, lambda s: s, sv)
    g_anchor = grad_at(sv.theta_anchor)
    corr = jax.tree.map(lambda mu, ga: mu - ga, sv.mu_anchor, g_anchor)
    grads = jax.tree.map(lambda g, c: g + c, grads, corr)
    return grads, corr, sv


def apply_svrg_streaming(sv: SvrgState, params, grads, grad_at, step,
                         cfg: StrategyConfig):
    """SVRG correction with a *streaming* one-batch anchor (sharded launch
    path).  The launch path streams data, so the exact full-local-gradient
    anchor is approximated by the current *batch* gradient at refresh time
    (anchor noise frozen for the period rather than eliminated — a
    documented degradation); the refresh is a traced where-select so the
    step stays a single trace, and the anchor backprop runs every step
    (SVRG's inherent 2x compute).  No leading worker dim: one shard's
    slice, like ``qhat`` in the sharded step.

    Returns ``(grads_corrected, corr, sv_new)``.
    """
    refresh = (step % cfg.svrg_period == 0).astype(jnp.float32)
    theta_anchor = jax.tree.map(
        lambda p_, t: refresh * p_.astype(jnp.float32) + (1.0 - refresh) * t,
        params, sv.theta_anchor)
    mu = jax.tree.map(
        lambda g, m: refresh * g.astype(jnp.float32) + (1.0 - refresh) * m,
        grads, sv.mu_anchor)
    g_anchor = grad_at(theta_anchor)
    corr = jax.tree.map(lambda m, ga: m - ga.astype(jnp.float32), mu, g_anchor)
    grads = jax.tree.map(lambda g, c: g.astype(jnp.float32) + c, grads, corr)
    return grads, corr, SvrgState(theta_anchor, mu)


def stale_side_grads(grad_at, theta_last, corr):
    """The WK2 second backprop: the CURRENT sample re-evaluated at the
    stale iterate(s) ``theta_last``, with the SVRG correction (if any)
    applied to this side too so anchor and mu cancel in the same-sample
    difference.  ``grad_at`` is the same evaluator the primal gradients
    used (same microbatching / scaling), closed over this round's batch.
    """
    gs = grad_at(theta_last)
    if corr is not None:
        gs = jax.tree.map(lambda g, c: g.astype(jnp.float32) + c, gs, corr)
    return gs


# ---------------------------------------------------------------------------
# Participation models.
# ---------------------------------------------------------------------------

def participation_mask(cfg: StrategyConfig, step, n_workers: int):
    """[W] bool availability mask for round ``step`` — or ``None`` for the
    modes that never mask (``full``, ``delay``).

    Deterministic in ``(participation_seed, step)`` and independent of the
    batch/compressor streams, so the SAME cohort is drawn by the simulated
    engine and by every shard of the sharded step (each indexes its own
    slot).  ``bernoulli`` keeps each worker independently with probability
    ``participation_p``; ``fixed_k`` keeps exactly
    ``max(1, round(p * W))`` workers drawn uniformly (the k lowest of W
    iid uniform scores — ties have measure zero).
    """
    if cfg.participation in ("full", "delay"):
        return None
    key = jax.random.fold_in(jax.random.PRNGKey(cfg.participation_seed), step)
    if cfg.participation == "bernoulli":
        return jax.random.bernoulli(key, cfg.participation_p, (n_workers,))
    if cfg.participation == "fixed_k":
        k = max(1, int(round(cfg.participation_p * n_workers)))
        scores = jax.random.uniform(key, (n_workers,))
        return scores <= jnp.sort(scores)[k - 1]
    if cfg.participation == "markov":
        raise ValueError(
            "markov churn is stateful (the chain carries the on/off state "
            "between rounds) — it has no stateless mask; use "
            "MarkovParticipation via make_participation (simulated engine "
            "only)")
    raise ValueError(f"unknown participation {cfg.participation!r}; "
                     f"have {PARTICIPATION}")


class FullParticipation:
    """Every worker reachable every round (the paper's setting)."""

    def init(self, params0):
        return None

    def begin_round(self, pstate, step, params):
        """Returns ``(avail, thetas_w, pstate)`` — ``avail`` the [W] bool
        mask (None = all available), ``thetas_w`` per-worker evaluation
        iterates (None = the current replicated params)."""
        return None, None, pstate


class SampledParticipation:
    """Bernoulli / fixed-k client sampling (see :func:`participation_mask`)."""

    def __init__(self, cfg: StrategyConfig, n_workers: int):
        assert 0.0 < cfg.participation_p <= 1.0, cfg.participation_p
        self.cfg = cfg
        self.n_workers = n_workers

    def init(self, params0):
        return None

    def begin_round(self, pstate, step, params):
        return (participation_mask(self.cfg, step, self.n_workers),
                None, pstate)


class MarkovParticipation:
    """Bursty on/off availability: a per-worker two-state Markov chain.

    The carried ROADMAP item: real fleets churn in *bursts* (a worker that
    just dropped tends to stay dropped), which i.i.d. bernoulli sampling
    cannot express.  Each worker holds a bool on/off state; at round start
    it transitions with ``P(on -> off) = 1 / sojourn`` and ``P(off -> on) =
    p_down * p / (1 - p)``, giving stationary availability exactly
    ``participation_p`` and a mean ON-streak of ``markov_sojourn`` rounds
    — so churn burstiness is dialed at *matched mean availability*
    (``benchmarks/participation_frontier.py`` measures the cost of the
    bursts).  ``sojourn = 1 / (1 - p)`` makes both transition
    probabilities equal ``1 - p`` / ``p``-complementary, i.e. the next
    state is independent of the current one: the chain degenerates to
    i.i.d. bernoulli(p), subsuming ``participation="bernoulli"`` as a
    special case (distributionally — the draws come from a different
    stream).  The initial state is drawn from the stationary law on its
    own fold_in stream.  Simulated engine only: the carried chain state is
    exactly what :func:`participation_mask`'s stateless contract (and with
    it the sharded step) cannot express.
    """

    def __init__(self, cfg: StrategyConfig, n_workers: int):
        p = cfg.participation_p
        assert 0.0 < p < 1.0, p
        assert cfg.markov_sojourn >= 1.0, cfg.markov_sojourn
        self.p = p
        self.p_down = min(1.0, 1.0 / cfg.markov_sojourn)
        self.p_up = min(1.0, self.p_down * p / (1.0 - p))
        self.n_workers = n_workers
        self._key0 = jax.random.PRNGKey(cfg.participation_seed)

    def init(self, params0):
        # stationary initial state; stream 1 (transitions draw on stream 0)
        return jax.random.bernoulli(jax.random.fold_in(self._key0, 1),
                                    self.p, (self.n_workers,))

    def begin_round(self, on, step, params):
        u = jax.random.uniform(
            jax.random.fold_in(jax.random.fold_in(self._key0, 0), step),
            (self.n_workers,))
        on = jnp.where(on, u >= self.p_down, u < self.p_up)
        return on, None, on


class DelayedParticipation:
    """Bounded-delay asynchronous workers (heterogeneous per-worker cost).

    Worker ``m`` has the fixed staleness ``d_m = m mod (max_delay + 1)``
    and computes this round's gradient at ``theta^{k - d_m}`` — the server
    applies it at round ``k`` (the classic bounded-staleness async model;
    delays are spread across the grid so every run exercises every
    staleness level).  State is a replicated params history ring of
    ``max_delay + 1`` iterates, pushed at round start; all workers stay
    *reachable* (``avail=None``) — staleness, not absence.
    """

    def __init__(self, max_delay: int, n_workers: int):
        assert max_delay >= 1, "use participation='full' for max_delay=0"
        self.length = max_delay + 1
        self.delays = jnp.arange(n_workers) % self.length

    def init(self, params0):
        return jax.tree.map(
            lambda l: jnp.broadcast_to(l[None], (self.length,) + l.shape),
            params0)

    def begin_round(self, hist, step, params):
        # hist[d] = theta^{k-d} after the push (index 0 = current round)
        hist = jax.tree.map(
            lambda h, p_: jnp.concatenate([p_[None].astype(h.dtype), h[:-1]],
                                          axis=0), hist, params)
        thetas = jax.tree.map(lambda h: h[self.delays], hist)
        return None, thetas, hist


def make_participation(cfg: StrategyConfig, n_workers: int):
    """Participation model for ``cfg`` (normalizing the degenerate knobs:
    ``delay`` with ``max_delay=0`` and sampling with ``p >= 1`` are exactly
    full participation and route to it, keeping trajectories bitwise equal
    to the pre-participation code)."""
    assert cfg.participation in PARTICIPATION, cfg.participation
    if cfg.participation == "delay":
        assert cfg.max_delay >= 0, cfg.max_delay
        if cfg.max_delay == 0:
            return FullParticipation()
        return DelayedParticipation(cfg.max_delay, n_workers)
    if cfg.participation in ("bernoulli", "fixed_k"):
        if cfg.participation_p >= 1.0 and cfg.participation != "fixed_k":
            return FullParticipation()
        if cfg.participation == "fixed_k" and \
                max(1, int(round(cfg.participation_p * n_workers))) == n_workers:
            return FullParticipation()
        return SampledParticipation(cfg, n_workers)
    if cfg.participation == "markov":
        if cfg.participation_p >= 1.0:
            return FullParticipation()
        return MarkovParticipation(cfg, n_workers)
    return FullParticipation()


# ---------------------------------------------------------------------------
# The engine.
# ---------------------------------------------------------------------------

class RoundEngine:
    """One LAQ communication round as a scan body, stages plugged in.

    ``baseline`` selects the dense-baseline path instead of the LAQ state
    machine: ``None`` runs worker_update/aggregate under ``cfg``; one of
    ``("sgd", "qsgd", "ssgd")`` runs the matching compressor with ``bits``
    / ``density`` (CommState is then bookkeeping only).  ``track_history``
    controls the criterion's ``theta_hist`` push (the stochastic wrapper
    historically pushes only for the LAQ family).
    """

    def __init__(self, source, cfg: StrategyConfig, *, alpha,
                 baseline: Optional[str] = None, bits: int = 3,
                 density: float = 0.1, track_history: bool = True,
                 participation=None):
        assert baseline in (None, "sgd", "qsgd", "ssgd"), baseline
        if baseline is not None and not source.stochastic:
            raise ValueError("dense baselines need a stochastic source "
                             "(their compressor keys come from its stream 1)")
        if baseline is not None and cfg.faults.active:
            raise ValueError("fault injection targets the LAQ state machine "
                             "(qhat / clocks / estimator state); the dense "
                             "baselines carry none of it — run them with "
                             "faults off")
        self.source = source
        self.cfg = cfg
        self.alpha = alpha
        self.baseline = baseline
        self.bits = bits
        self.density = density
        self.track_history = track_history
        self.n_workers = source.n_workers
        self.participation = (participation if participation is not None
                              else make_participation(cfg, self.n_workers))
        self.wk2 = (baseline is None and cfg.lazy
                    and cfg.lazy_rule == "lasg_wk2")

    def init_carry(self, params0):
        return (params0, init_comm_state(params0, self.n_workers, self.cfg),
                self.participation.init(params0))

    def round(self, carry, _):
        """Scan body: one communication round.  Returns the new carry and
        the per-round record ``(loss, grad_norm_sq, total_uploads,
        total_bits, quant_err, mean_bits)``."""
        cfg, source = self.cfg, self.source
        params, cst, pstate = carry
        alpha_k = eta_at(cfg.eta_schedule, self.alpha, cst.step)

        avail, thetas_w, pstate = self.participation.begin_round(
            pstate, cst.step, params)
        batches = source.sample(cst.step)
        grads = source.eval_at(params, thetas_w, batches)

        flt = cfg.faults
        if flt.crashy:
            # crash-restart BEFORE the svrg/wk2 stages: the restarted
            # worker's fresh anchors are what this round computes against.
            # mu restarts from this round's (pre-correction) gradient — the
            # streaming-style refresh (core/faults.py).
            cst = apply_crashes(
                cst, crash_mask(flt, cst.step, self.n_workers), params,
                grads, cfg, reconcile=cfg.defense.reconcile_crashes)

        corr = None
        if source.stochastic and cfg.variance_reduced:
            grads, corr, svrg = apply_svrg_exact(
                cst.svrg, params, grads,
                lambda th: source.eval_at(params, th, batches),
                source.full_local_grads, cst.step, cfg, self.n_workers)
            cst = cst._replace(svrg=svrg)

        if self.baseline is None:
            grads_stale = None
            if self.wk2:
                grads_stale = stale_side_grads(
                    lambda th: source.eval_at(params, th, batches),
                    cst.lazy.theta_last, corr)
            # payload corruption AFTER the svrg/wk2 stages: the fault hits
            # the outgoing payload (what the worker ships), not the local
            # computation — the stale side stays honest, so the wk2 rule
            # sees a huge same-sample difference and uploads the garbage,
            # exactly the failure mode a corrupt sender produces
            grads_out = grads
            fault_flip = fault_keys = None
            if flt.grad_faulty:
                grads_out = corrupt_grads(
                    grads, corruption_mask(flt, cst.step, self.n_workers),
                    flt)
            elif flt.wire_faulty:
                fault_flip = corruption_mask(flt, cst.step, self.n_workers)
                fault_keys = bitflip_keys(flt, cst.step, self.n_workers)
            agg, cst, metrics = aggregate(cst, grads_out, alpha_k, cfg,
                                          params=params,
                                          grads_stale=grads_stale,
                                          avail=avail,
                                          fault_flip=fault_flip,
                                          fault_keys=fault_keys)
            qe, mb = metrics.radius_max, metrics.mean_bits
        else:
            agg, cst, qe, mb = self._baseline_round(cst, grads, avail)

        new_params = jax.tree.map(lambda t, g: t - alpha_k * g, params, agg)
        if self.track_history:
            dsq = tree_sq_norm(jax.tree.map(lambda a, b: a - b,
                                            new_params, params))
            cst = finalize_step(cst, dsq)
        rec = (source.global_loss(params), source.grad_norm_sq(params, grads),
               cst.total_uploads, cst.total_bits, qe, mb)
        return (new_params, cst, pstate), rec

    def _baseline_round(self, cst: CommState, grads, avail):
        """Dense-baseline aggregation: every available worker uploads its
        (compressed) gradient; no server recursion, no skip state."""
        kind = self.baseline
        W = self.n_workers
        p = tree_size(grads) // W
        keys_cmp = self.source.stream_keys(1, cst.step)
        if kind == "sgd":
            cgrads = grads
            bits_m = jnp.full((W,), float(dense_bits(p)))
        elif kind == "qsgd":
            cgrads, bits_m = jax.vmap(
                lambda k, g: qsgd_compress(k, g, self.bits))(keys_cmp, grads)
        else:
            cgrads, bits_m = jax.vmap(
                lambda k, g: ssgd_compress(k, g, self.density))(keys_cmp,
                                                                grads)
        if avail is None:
            n_up = W
            mb = jnp.mean(bits_m) / p
        else:
            keep = avail.astype(jnp.float32)
            cgrads = jax.tree.map(
                lambda g: g * keep.reshape((-1,) + (1,) * (g.ndim - 1)),
                cgrads)
            bits_m = bits_m * keep
            n_up = jnp.sum(avail.astype(jnp.int32))
            mb = jnp.sum(bits_m) / jnp.maximum(jnp.sum(keep), 1.0) / p
        agg = jax.tree.map(lambda g: jnp.sum(g, axis=0), cgrads)
        cst = cst._replace(total_bits=cst.total_bits + jnp.sum(bits_m),
                           total_uploads=cst.total_uploads + n_up,
                           step=cst.step + 1)
        return agg, cst, jnp.zeros(()), mb

    def run_from(self, carry, steps: int):
        """Scan ``steps`` rounds from an arbitrary carry — the resume entry
        point (checkpoint restart, the divergence watchdog's chunked
        supervision in core/defense.py).  Returns ``(carry, RunResult)``;
        ``run`` is ``run_from(init_carry(params0))``, so a run split across
        ``run_from`` calls is bitwise identical to one uninterrupted scan
        (tests/test_checkpoint.py pins this through a save/load cycle).
        """
        carry, recs = jax.lax.scan(self.round, carry, None, length=steps)
        loss, gn, cu, cb, qe, mb = recs
        return carry, RunResult(carry[0], loss, gn, cu, cb, qe, mb)

    def run(self, params0, steps: int) -> RunResult:
        _, result = self.run_from(self.init_carry(params0), steps)
        return result
