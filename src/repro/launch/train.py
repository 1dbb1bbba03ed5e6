"""Distributed LAQ training step.

The gradient computation + LAQ aggregation run inside a **partial-auto
shard_map**: manual over the worker axes (``data``, and ``pod`` on multi-pod
meshes), auto over ``model``.  Inside the manual region each worker sees its
own batch shard and computes a *local* gradient (no implicit data-axis psum —
that is exactly what GSPMD would insert for replicated params, and what LAQ
must intercept).  The LAQ state machine quantizes the innovation, applies the
skip criterion, and the aggregation collective is explicit:

* ``wire="float"``  — psum of the (dequantized, skip-masked) innovations.
  Numerically exact LAQ; bits accounted analytically (paper's accounting).
* ``wire="packed"`` — the TPU-native wire format: per-leaf b-bit codes packed
  into uint8 payloads and exchanged with ``all_gather`` over the worker axes
  together with the per-worker radius R and skip mask; every device
  dequantizes and sums (the SPMD replica of the paper's server).  The
  collective payload is physically b/32 of the float gradient.

Packed wire format (per worker, per round):

* **fixed-bit** (``bit_schedule`` None/constant, width b in {2, 4, 8}) —
  per leaf, codes packed little-end-first at 8/b codes per byte, plus two
  sidecars exchanged once per round: the radius ``R`` (f32 per leaf for
  ``per_leaf_radius``, else one global f32) and the skip-mask bit.  Where
  every leaf is whole on its device (no mesh axis left to GSPMD) the
  payload is the flattened leaf's, as the lazy rule's send kernel packed
  it (one quantize a round), and the decode kernel sums it; elsewhere
  codes are quantized again and packed along the leaf's last dim when it
  divides 8/b (odd last dims ship raw uint8 codes).
* **adaptive** (``bit_schedule`` radius/budget, core/adaptive.py) — each
  worker additionally announces its selected width ``b_m^k`` as a third
  sidecar, and every receiver decodes with the sender's tau(b_m^k).  The
  payload buffer is *provisioned* at the static width max(grid) — SPMD
  collectives need static shapes, so the adaptivity shows up in the exact
  wire-bit accounting (``upload_bits`` with variable b + the width sidecar)
  rather than in the buffer shape; a grid capped below 8 shrinks the
  physical buffer correspondingly.  Decode taus come from a grid-table
  lookup, never ``1/(2^b - 1)`` float arithmetic, so packed and float wires
  stay bit-identical.

The skip criterion is pluggable (``StrategyConfig.lazy_rule``): the paper's
eq. 7a, or the variance-aware LASG rules (core/lazy_rules.py) whose
per-worker estimator state (``CommState.lazy``: variance / smoothness EMAs,
plus the stale-iterate snapshot for ``lasg_wk2`` / ``lasg_ps``) and the
scale-free adaptive threshold anchor (``CommState.R_anchor``) ride through
the sharded step like ``qhat`` — one slice per worker shard, reference wire
path.  The ``lasg_wk2`` rule pays a second backprop per step: the *current*
batch re-evaluated at this worker's stale iterate (same microbatching), so
its skip decision is noise-free.

Upload defense (``StrategyConfig.defense``, core/defense.py) runs inside
the sharded step: validation finite-checks each worker's innovation and
quantization error against a per-worker accepted-norm EMA, and a rejected
upload is masked off the wire exactly like a lazy skip (bits still paid —
the ``committed`` mask; docs/robustness.md).  Fault *injection*, robust
aggregation (``aggregator != "sum"``) and norm clipping on the packed wire
are simulated-engine-only and asserted off here.

Three stochastic levers from the simulated engine also apply here — the
round stages themselves are SHARED with ``core/engine.py`` (this module no
longer carries its own copy of the SVRG / WK2 round math):

* ``StrategyConfig.eta_schedule`` — the per-round stepsize ``alpha_k``
  (computed from the replicated ``comm.step``) feeds both the optimizer
  step and the criterion's ``1/(alpha^2 M^2)`` term;
* ``StrategyConfig.grad_mode="svrg"`` — **streaming-anchor** variance
  reduction via :func:`repro.core.engine.apply_svrg_streaming`: every
  ``svrg_period`` steps the anchor snaps to the current iterate and ``mu``
  to the current *batch* gradient (the launch path streams data, so the
  simulated engine's exact full-local-data anchor is approximated by a
  one-batch anchor; the anchor noise is frozen for the period rather than
  eliminated — a documented degradation).  Corrected gradients feed the
  lazy rule and the quantizer exactly as in the simulated engine; the
  anchor state (``CommState.svrg``) rides per worker shard like ``qhat``;
* ``StrategyConfig.participation`` — partial participation
  (core/engine.py): ``"bernoulli"`` / ``"fixed_k"`` client sampling draws
  the round's cohort from :func:`repro.core.engine.participation_mask`
  (deterministic in ``(participation_seed, step)``, so every shard and the
  simulated engine agree on who is reachable); each shard indexes its slot
  of the replicated [W] mask by a *worker-index input* sharded over the
  worker axes.  Unreachable workers are masked exactly like lazy
  skips inside ``worker_update`` (no upload, no wire bits, clocks grow).
  ``"delay"`` (bounded-staleness async) is simulated-engine-only: it needs
  a replicated params-history ring, which at model scale would be
  ``max_delay`` extra copies of the parameters — asserted off here, see
  ``docs/engine.md``.

Tensor parallelism (``model`` axis) stays under GSPMD: inside the manual
region, model-sharded arrays keep their global shapes and einsum/norm
reductions over them lower to the usual collectives.  The step runs on the
caller's mesh with every axis Auto (``launch/mesh.py auto_axes``), whatever
axis types the caller built it with.

The packed wire byte layout this module exchanges is specified normatively
in ``docs/wire-format.md``.
"""
from __future__ import annotations

import functools
import logging
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core.adaptive import eta_at, tau_of_selection, tau_of_width
from repro.core.compressors import ErrorState, compressor_keys
from repro.core.defense import DefenseState
from repro.core.engine import (accumulate_loss_grads, apply_svrg_streaming,
                               participation_mask, stale_side_grads)
from repro.core.quantize import tree_sq_norm
from repro.core.spans import (AGGREGATE, FWD_BWD, LAZY_RULE, OPTIMIZER,
                              WIRE_CODEC)
from repro.core.strategy import (CommState, StrategyConfig, SvrgState,
                                 worker_update)
from repro.core.wire import (get_backend, pack_codes_along_axis,
                             unpack_codes_along_axis)
from repro.core.criterion import push_history
from repro.models import lm_loss, param_pspecs
from repro.models.config import ModelConfig, shared_block_params
from repro.optim import Optimizer

from .mesh import auto_axes, n_workers_of


class TrainState(NamedTuple):
    params: object
    opt_state: object
    comm: CommState
    step: jax.Array


class StepMetrics(NamedTuple):
    loss: jax.Array
    uploads: jax.Array
    bits: jax.Array


def _squeeze0(tree):
    return jax.tree.map(lambda x: jnp.squeeze(x, 0), tree)


def _unsqueeze0(tree):
    return jax.tree.map(lambda x: x[None], tree)


def _flat_aggregate(payloads, radii, skip_mask, grads,
                    strategy: StrategyConfig, worker_axes):
    """The whole-leaf wire (fixed width, every leaf whole on its device):
    the exchange of the payloads and radii that the lazy rule's roundtrip
    already made (``worker_update(..., with_payload=True)``), so each leaf
    gets one radius pass and one pass-2 sweep a round.  Per leaf, the
    payload and its radius are gathered over the workers, and
    ``dequant_acc`` unpacks, dequantizes and masked-sums the workers'
    payloads in one pass; no leaf-shaped code array exists.  Returns the
    sum of innovations."""
    backend = get_backend(strategy.wire_backend)
    bits = strategy.effective_bits
    keep = jnp.logical_not(skip_mask).astype(jnp.float32)
    keep_w = jax.lax.all_gather(keep, worker_axes)                  # [W]
    g_leaves, treedef = jax.tree_util.tree_flatten(grads)
    agg = []
    for payload, R, g in zip(payloads, jax.tree_util.tree_leaves(radii),
                             g_leaves):
        payload_w = jax.lax.all_gather(payload, worker_axes)
        R_w = jax.lax.all_gather(R, worker_axes)
        total = backend.dequant_acc(payload_w, R_w, keep_w, bits, g.size)
        with jax.named_scope(WIRE_CODEC):
            agg.append(total.reshape(g.shape))
    return jax.tree_util.tree_unflatten(treedef, agg)


def _packed_aggregate(grads, qhat, skip_mask, strategy: StrategyConfig,
                      worker_axes, pspecs=None, width=None):
    """The axis-packed uint8 wire, **streamed one leaf at a time**: per leaf,
    innovation -> quantize -> pack -> exchange -> dequantize -> masked sum
    (plus that leaf's local ``q_new`` reconstruction) before the next leaf
    is touched.  Returns (sum_of_innovations, q_new_tree).

    Memory frugality at LM scale: the program never materializes a
    whole-model codes / diff / delta pytree — one leaf's quantize/pack
    intermediates are live at a time, so the transient footprint is
    O(max-leaf) instead of O(model).  The only whole-tree pre-pass is the
    radius: a *scalar* absmax per leaf (global-radius mode maxes the
    scalars with exactly ``tree_inf_norm``'s reduction), so the per-leaf
    code math stays bit-identical to ``quantize_innovation`` /
    ``dequantize_innovation`` (the packed-vs-float parity pinned by
    tests/test_system.py).

    ``pspecs`` (a pytree of PartitionSpec matching ``grads``) pins the
    payload's model-axis sharding through the exchange: without it GSPMD
    replicates the payload over ``model`` *before* the worker-axis
    all_gather, multiplying the exchanged bytes by the model-axis size.

    ``width`` (the per-shard selected bit-width from ``worker_update``)
    switches on the adaptive wire: codes are produced at the selected width,
    the buffer is provisioned at max(grid), and the width rides along as a
    sidecar so receivers decode with the sender's tau (see module docstring).

    The codec keeps each leaf's shape; where every leaf is whole on its
    device and the width is fixed, :func:`_flat_aggregate` runs instead.
    """
    from repro.models.layers import maybe_constrain
    per_leaf = strategy.per_leaf_radius
    adaptive = width is not None
    if adaptive:
        grid = strategy.bit_schedule.grid
        onehot = (jnp.asarray(grid, jnp.float32) == width).astype(jnp.float32)
        provision = max(grid)
    else:
        bits = strategy.effective_bits
        provision = bits
    keep = jnp.logical_not(skip_mask).astype(jnp.float32)
    backend = get_backend(strategy.wire_backend)
    # per-round sidecars exchanged ONCE, outside the per-leaf loop (XLA does
    # not CSE collectives; a per-leaf exchange would issue one tiny
    # collective per parameter tensor)
    keep_w = jax.lax.all_gather(keep, worker_axes)                  # [W]
    if adaptive:
        width_w = jax.lax.all_gather(width, worker_axes)            # [W] sidecar
        tau_w = tau_of_width(grid, width_w)                         # [W]

    # the axis-packed payload codec lives in core/wire.py (one wire format
    # shared with the backend interface): pack 8/b codes per byte ALONG THE
    # LAST DIM (no flatten: a flatten of a model-sharded leaf forces GSPMD
    # to regather it, and at large meshes trips an XLA spmd_partitioner
    # assertion); indivisible last dims and provision 8 ship raw codes
    def gather_dequant_sum(q, R, orig, spec):
        pl = pack_codes_along_axis(q, provision)
        if spec is not None:
            pl = maybe_constrain(pl, *spec)
        payload = jax.lax.all_gather(pl, worker_axes)               # [W, ...]
        if spec is not None:
            payload = maybe_constrain(payload, None, *spec)
        Rw = jax.lax.all_gather(R, worker_axes)                     # [W]
        W = Rw.shape[0]
        codes = jax.vmap(
            lambda p_: unpack_codes_along_axis(p_, provision, orig))(payload)
        if adaptive:
            t = tau_w.reshape((W,) + (1,) * orig.ndim)
        else:
            t = 1.0 / (2.0 ** provision - 1.0)
        Rb = Rw.reshape((W,) + (1,) * orig.ndim)
        kb = keep_w.reshape((W,) + (1,) * orig.ndim)
        delta = (2.0 * t * Rb * codes.astype(jnp.float32) - Rb)
        delta = jnp.where(Rb > 0, delta, 0.0) * kb
        return jnp.sum(delta, axis=0)

    g_leaves, treedef = jax.tree_util.tree_flatten(grads)
    qh_leaves = jax.tree_util.tree_leaves(qhat)
    s_leaves = (jax.tree_util.tree_leaves(pspecs, is_leaf=lambda x: isinstance(x, tuple))
                if pspecs is not None else [None] * len(g_leaves))

    # radius pre-pass: one scalar per leaf — the only whole-tree quantity.
    # The backend's pass-1 absmax primitive mirrors innovation() /
    # tree_inf_norm exactly (reference expressions on CPU; the fused
    # backend's blockwise Pallas reduction off-CPU), and for the global
    # radius a max over the stacked leaf scalars.
    absmax = [backend.leaf_absmax(g, qh)
              for g, qh in zip(g_leaves, qh_leaves)]
    if per_leaf:
        r_leaves = absmax
    else:
        R_glob = jnp.max(jnp.stack(absmax))
        r_leaves = [R_glob] * len(g_leaves)

    t_sel = tau_of_selection(grid, onehot) if adaptive else None

    def stream_leaf(g, qh, R, spec):
        # the streamed hot path: this leaf's codes, payload and dequantized
        # delta are dead before the next leaf starts.  The send-side sweep
        # is the backend's pass-2 leaf primitive: the adaptive codes+delta
        # Pallas kernel off-CPU, the reference expressions otherwise (every
        # fixed-width sweep, and the adaptive one on CPU).
        if adaptive:
            q, delta_local = backend.leaf_quantize_adaptive(
                g, qh, R, grid, onehot, t_sel)
        else:
            q, delta_local = backend.leaf_quantize(g, qh, R, bits)
        agg = gather_dequant_sum(q, R, g, spec)
        q_new = qh.astype(jnp.float32) + delta_local
        return agg, q_new

    streamed = [stream_leaf(g, qh, r, s) for g, qh, r, s
                in zip(g_leaves, qh_leaves, r_leaves, s_leaves)]
    agg_delta = jax.tree_util.tree_unflatten(treedef, [a for a, _ in streamed])
    q_new = jax.tree_util.tree_unflatten(treedef, [qn for _, qn in streamed])
    return agg_delta, q_new


def make_train_step(cfg: ModelConfig, mesh, strategy: StrategyConfig,
                    optimizer: Optimizer, *, lr: float,
                    worker_axes=None, wire: str = "float",
                    hierarchical: bool = False, microbatch: int = 1):
    """Returns ``step(state, batch) -> (state, metrics)`` (to be jitted).

    ``microbatch > 1`` splits each worker's batch into that many sequential
    microbatches with f32 gradient accumulation — the standard production
    lever for the activation-memory term (saved activations shrink by the
    factor; LAQ semantics unchanged, it still sees the full-batch gradient).
    """
    from .mesh import worker_axes_of
    mesh = auto_axes(mesh)
    if worker_axes is None:
        worker_axes = worker_axes_of(mesh, hierarchical=hierarchical)
    W = n_workers_of(mesh, worker_axes)
    wa = worker_axes if len(worker_axes) > 1 else worker_axes[0]
    assert wire in ("float", "packed")
    if strategy.compressed or strategy.error_feedback:
        # the packed wire re-quantizes the raw grads itself (dense per-leaf
        # codes); the sparse pipeline ships index+code payloads whose exact
        # byte layout the sharded exchange does not yet implement — the
        # compressor path rides the float wire with analytic bit accounting
        assert wire == "float", \
            "compressor / error_feedback strategies require wire='float'"
        # global support selection flattens the whole gradient pytree; a
        # reshape of a model-sharded leaf forces a GSPMD regather of every
        # leaf — so the sparse pipeline covers data-parallel meshes only
        assert mesh.shape["model"] == 1, (
            "compressor / error_feedback strategies require a pure "
            "data-parallel mesh (model axis 1): global top-k/rand-k "
            "support selection flattens the gradient pytree")
    assert strategy.participation in ("full", "bernoulli", "fixed_k"), (
        "delay/markov participation is simulated-engine-only: 'delay' would "
        "need a replicated params-history ring of max_delay+1 full parameter "
        "copies, and 'markov' carries a stateful per-worker on/off chain "
        "(see docs/engine.md)")
    assert strategy.max_delay == 0, "max_delay needs participation='delay'"
    assert not strategy.faults.active, (
        "fault injection is simulated-engine-only: the corruption / crash "
        "stages live in RoundEngine.round (core/engine.py), not the sharded "
        "step — the launch path is the *defended* deployment target "
        "(see docs/robustness.md)")
    assert strategy.aggregator == "sum", (
        "trimmed_mean/median aggregation is simulated-engine-only: the "
        "coordinate-wise sort needs every worker's dequantized delta on one "
        "device (see docs/robustness.md); the sharded defenses are "
        "validation + norm-gate + clip, which are per-worker-local")
    if wire == "packed":
        assert strategy.defense.clip_mult == 0.0, (
            "norm-clipping on the packed wire would need a per-worker f32 "
            "scale sidecar (codes are integers); clip rides the float wire, "
            "validate/gate work on both (a reject is one mask bit)")
    # --- partial-auto shard_map: manual over worker axes, auto over model ---
    # Axes of size 1 go manual too: a Pallas kernel (the fused wire) lowers
    # only where no axis is left for GSPMD to partition it over.
    manual = set(worker_axes) | {a for a in mesh.axis_names
                                 if mesh.shape[a] == 1}
    grad_pspecs = None
    wire_layout = None
    flat_wire = False
    if wire == "packed":
        assert strategy.quantized, "packed wire requires a quantized strategy"
        if strategy.adaptive:
            assert all(b in (2, 4, 8) for b in strategy.bit_schedule.grid), \
                "packed wire covers the {2,4,8} grid"
        else:
            assert strategy.effective_bits in (2, 4, 8), \
                "packed wire requires a 2-, 4- or 8-bit quantized strategy"
        from repro.models import init_params
        params_abs = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
        grad_pspecs = param_pspecs(cfg, params_abs, mesh.shape["model"])
        # the whole-leaf wire where no axis is left to GSPMD (so every leaf
        # is whole on its device) and the width is fixed; else the axis codec
        flat_wire = not strategy.adaptive and manual == set(mesh.axis_names)
        sizes = [leaf.size for leaf in jax.tree.leaves(params_abs)]
        # pass-2 sweeps a leaf gets per round: the whole-leaf wire ships the
        # lazy rule's payload, the axis codec quantizes again
        wire_layout = {"flat": (0, 0), "axis": (0, 0),
                       "quantize_passes": 1 if flat_wire else 2}
        wire_layout["flat" if flat_wire else "axis"] = (len(sizes), sum(sizes))
        logging.getLogger(__name__).info(
            "packed wire: %d leaves (%d parameters) flat, %d (%d) on the "
            "axis codec; %d quantize pass(es) a leaf a round",
            *wire_layout["flat"], *wire_layout["axis"],
            wire_layout["quantize_passes"])

    shared_block = None
    if cfg.arch_type == "hybrid":
        shared_block = dict(
            heads=cfg.n_heads, kv_heads=cfg.n_kv_heads, mlp_columns=cfg.d_ff,
            ssm_heads=cfg.ssm_heads, ssm_groups=cfg.ssm_n_groups,
            invocations=len(cfg.hybrid_layers), blocks=cfg.num_mem_blocks,
            params=shared_block_params(cfg))
        logging.getLogger(__name__).info(
            "hybrid: %(heads)d attention heads (%(kv_heads)d kv), "
            "%(mlp_columns)d MLP columns, %(ssm_heads)d Mamba heads in "
            "%(ssm_groups)d groups held; %(invocations)d shared-block "
            "invocations per forward over %(blocks)d blocks of %(params)d "
            "parameters", shared_block)

    def sharded_step(params, opt_state, comm, batch, widx):
        qhat = _squeeze0(comm.qhat)
        eps_hat_sq = jnp.squeeze(comm.eps_hat_sq, 0)
        clock = jnp.squeeze(comm.clocks, 0)
        bits_spent = jnp.squeeze(comm.bits_spent, 0)
        lazy = _squeeze0(comm.lazy)        # LASG estimator state (this shard)
        R_anchor = jnp.squeeze(comm.R_anchor, 0)
        error = _squeeze0(comm.error)      # EF residual (this shard)
        defense = _squeeze0(comm.defense)  # gate EMA / reject ledger (shard)

        def loss_fn(p, b):
            return lm_loss(p, b, cfg) / W          # sum_m loss_m == global mean

        def loss_and_grads(at_params):
            """This worker's batch gradient at an arbitrary iterate (the
            current params; the WK2 stale iterate; the SVRG anchor) —
            microbatching identical for every evaluation point, via the
            engine-shared fold (core/engine.py accumulate_loss_grads, the
            same arithmetic AccumulatingSource runs in the simulated
            engine)."""
            with jax.named_scope(FWD_BWD):
                if microbatch == 1:
                    return jax.value_and_grad(loss_fn)(at_params, batch)
                mb = jax.tree.map(
                    lambda x: x.reshape((microbatch, x.shape[0] // microbatch)
                                        + x.shape[1:]), batch)
                return accumulate_loss_grads(loss_fn, at_params, mb)

        loss, grads = loss_and_grads(params)
        # The wire reads the gradient as its dtype holds it.  Without the
        # barrier XLA may fuse a bf16 gradient's producer into the wire's
        # reductions and skip the rounding (excess precision), differently
        # for each wire backend, and the backends' radii part ways on TPU.
        with jax.named_scope(FWD_BWD):
            grads = jax.lax.optimization_barrier(grads)
        lr_k = eta_at(strategy.eta_schedule, lr, comm.step)

        svrg_new = comm.svrg
        corr = None
        if strategy.variance_reduced:
            # the shared streaming-anchor stage (core/engine.py; the
            # simulated engine uses the exact-anchor variant): the anchor
            # backprop runs every step — svrg's inherent 2x compute
            with jax.named_scope(FWD_BWD):
                grads, corr, sv_new = apply_svrg_streaming(
                    _squeeze0(comm.svrg), params, grads,
                    lambda th: loss_and_grads(th)[1], comm.step, strategy)
            svrg_new = _unsqueeze0(sv_new)

        grads_stale = None
        if strategy.lazy and strategy.lazy_rule == "lasg_wk2":
            # the shared WK2 stage: the SAME batch at the stale iterate
            # (identical microbatching via loss_and_grads), svrg correction
            # applied to both sides so anchor and mu cancel
            with jax.named_scope(FWD_BWD):
                grads_stale = stale_side_grads(
                    lambda th: loss_and_grads(th)[1], lazy.theta_last, corr)

        with jax.named_scope(LAZY_RULE):
            avail = None
            if strategy.participation != "full":
                # this shard's slot of the replicated [W] cohort mask — the
                # SAME draw the simulated engine makes (see module docstring
                # for why the slot comes from the widx input, not axis_index)
                avail = participation_mask(strategy, comm.step,
                                           W)[jnp.squeeze(widx, 0)]

            ckey = None
            if strategy.compressor == "randk":
                # this shard's slot of the round's [W] selection keys — the
                # SAME draw the simulated engine makes (slot from the widx
                # input, not axis_index; see the participation note above)
                ckey = compressor_keys(strategy.compressor_seed, comm.step,
                                       W)[jnp.squeeze(widx, 0)]

            wu = worker_update(grads, qhat, eps_hat_sq, clock, bits_spent,
                               comm.theta_hist, lr_k, W, strategy,
                               step=comm.step, lazy_m=lazy,
                               R_anchor_m=R_anchor, params=params,
                               grad_stale_m=grads_stale, avail_m=avail,
                               error_m=error, ckey_m=ckey, defense_m=defense,
                               with_payload=flat_wire)
        (delta_masked, qhat_new, eps_hat_sq_new, clock_new, uploaded,
         bits_m, width_m) = (wu.delta_masked, wu.qhat_new, wu.eps_hat_sq_new,
                             wu.clock_new, wu.uploaded, wu.bits_m, wu.width_m)

        with jax.named_scope(AGGREGATE):
            if wire == "float":
                agg_delta = jax.tree.map(
                    functools.partial(jax.lax.psum, axis_name=wa),
                    delta_masked)
            else:
                # a defense-rejected upload is masked off the wire exactly
                # like a lazy skip (its bits_m still pay: the payload was
                # sent)
                skip = jnp.logical_not(wu.committed)
                if flat_wire:
                    agg_delta = _flat_aggregate(wu.payload, wu.payload_R,
                                                skip, grads, strategy, wa)
                else:
                    agg_delta, _ = _packed_aggregate(
                        grads, qhat, skip, strategy, wa, pspecs=grad_pspecs,
                        width=width_m if strategy.adaptive else None)

            agg = jax.tree.map(lambda a, d: a.astype(jnp.float32) + d,
                               comm.server_agg, agg_delta)
            agg_store = jax.tree.map(lambda a, s: a.astype(s.dtype), agg,
                                     comm.server_agg)
        with jax.named_scope(OPTIMIZER):
            new_params, new_opt = optimizer.update(agg, opt_state, params,
                                                   lr_k)
            # the parameter motion the lazy rule keeps: XLA fuses its
            # reduction into the update's sweep, and a fusion carries one
            # span, so it is the optimizer's
            dtheta_sq = tree_sq_norm(jax.tree.map(
                lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
                new_params, params))
        with jax.named_scope(LAZY_RULE):
            new_comm = CommState(
                qhat=_unsqueeze0(qhat_new),
                server_agg=agg_store,
                eps_hat_sq=eps_hat_sq_new[None],
                clocks=clock_new[None],
                bits_spent=(bits_spent + bits_m)[None],
                theta_hist=push_history(comm.theta_hist, dtheta_sq),
                total_bits=comm.total_bits + jax.lax.psum(bits_m, wa),
                total_uploads=comm.total_uploads
                + jax.lax.psum(uploaded.astype(jnp.int32), wa),
                step=comm.step + 1,
                lazy=_unsqueeze0(wu.lazy_new),
                R_anchor=wu.R_anchor_new[None],
                svrg=svrg_new,
                error=_unsqueeze0(wu.error_new),
                defense=_unsqueeze0(wu.defense_new),
            )
            metrics = StepMetrics(
                loss=jax.lax.psum(loss, wa),
                uploads=jax.lax.psum(uploaded.astype(jnp.int32), wa),
                bits=jax.lax.psum(bits_m, wa),
            )
        return new_params, new_opt, new_comm, metrics

    def step(state: TrainState, batch):
        comm = state.comm
        specs_comm = CommState(
            qhat=jax.tree.map(lambda _: P(wa), comm.qhat),
            server_agg=jax.tree.map(lambda _: P(), comm.server_agg),
            eps_hat_sq=P(wa), clocks=P(wa), bits_spent=P(wa), theta_hist=P(),
            total_bits=P(), total_uploads=P(), step=P(),
            lazy=jax.tree.map(lambda _: P(wa), comm.lazy),
            R_anchor=P(wa),
            svrg=jax.tree.map(lambda _: P(wa), comm.svrg),
            error=jax.tree.map(lambda _: P(wa), comm.error),
            defense=jax.tree.map(lambda _: P(wa), comm.defense),
        )
        sm = jax.shard_map(
            sharded_step, mesh=mesh,
            in_specs=(jax.tree.map(lambda _: P(), state.params),
                      jax.tree.map(lambda _: P(), state.opt_state),
                      specs_comm,
                      jax.tree.map(lambda _: P(wa), batch),
                      P(wa)),
            out_specs=(jax.tree.map(lambda _: P(), state.params),
                       jax.tree.map(lambda _: P(), state.opt_state),
                       specs_comm,
                       StepMetrics(P(), P(), P())),
            axis_names=manual, check_vma=False)
        new_params, new_opt, new_comm, metrics = sm(
            state.params, state.opt_state, comm, batch,
            jnp.arange(W, dtype=jnp.int32))
        with jax.named_scope(LAZY_RULE):
            new_step = state.step + 1
        return TrainState(new_params, new_opt, new_comm, new_step), metrics

    # introspection: the wire backend the sharded step runs, and on the
    # packed wire how many leaves and parameters take each layout and how
    # many pass-2 sweeps a leaf gets a round ({"flat": (leaves, params),
    # "axis": (leaves, params), "quantize_passes": n}; None on float)
    step.wire_backend = get_backend(strategy.wire_backend).name
    step.wire_layout = wire_layout
    # a hybrid model's shares and shared-block count (None otherwise)
    step.shared_block = shared_block
    return step


# ---------------------------------------------------------------------------
# State construction (concrete and abstract variants)
# ---------------------------------------------------------------------------

def init_train_state(key, cfg: ModelConfig, mesh, strategy: StrategyConfig,
                     optimizer: Optimizer, worker_axes):
    from repro.models import init_params
    from repro.core.strategy import init_comm_state
    params = init_params(key, cfg)
    opt_state = optimizer.init(params)
    W = n_workers_of(mesh, worker_axes)
    comm = init_comm_state(params, W, strategy)
    return TrainState(params, opt_state, comm,
                      jnp.zeros((), jnp.int32))


def train_state_specs(cfg: ModelConfig, mesh, strategy: StrategyConfig,
                      optimizer: Optimizer, worker_axes):
    """Abstract TrainState of ShapeDtypeStructs with NamedShardings attached —
    lowers without allocating (a compile for a described chip, the
    benchmark's shardings)."""
    mesh = auto_axes(mesh)
    from repro.models import init_params
    from repro.core.strategy import init_comm_state

    W = n_workers_of(mesh, worker_axes)
    wa = worker_axes if len(worker_axes) > 1 else worker_axes[0]
    model_size = mesh.shape["model"]

    params_abs = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    pspecs = param_pspecs(cfg, params_abs, model_size)
    opt_abs = jax.eval_shape(optimizer.init, params_abs)
    # optimizer state mirrors params (AdamState carries extra scalars)
    def opt_spec(leaf_path, leaf):
        return _match_param_spec(leaf, params_abs, pspecs)
    # params passed as a real argument (not closed over) so init_comm_state
    # sees tracers: the lasg_ps theta_last snapshot reads template *values*
    comm_abs = jax.eval_shape(lambda p: init_comm_state(p, W, strategy),
                              params_abs)

    def shard(abs_leaf, spec):
        return jax.ShapeDtypeStruct(abs_leaf.shape, abs_leaf.dtype,
                                    sharding=NamedSharding(mesh, spec))

    params_s = jax.tree.map(shard, params_abs, pspecs)

    def opt_state_specs(opt_abs):
        # match each opt leaf to the param it mirrors by shape, else replicate
        shape2spec = {}
        for leaf, spec in zip(jax.tree.leaves(params_abs), jax.tree.leaves(pspecs)):
            shape2spec.setdefault(leaf.shape, spec)
        return jax.tree.map(
            lambda l: shard(l, shape2spec.get(l.shape, P())), opt_abs)

    opt_s = opt_state_specs(opt_abs)

    def comm_leaf_spec(qh_leaf, pspec):
        return shard(qh_leaf, P(*((wa,) + tuple(pspec))))

    def lazy_specs(lz):
        # pytree fields mirror the param pytree with a leading worker dim
        # (like qhat); scalar estimator fields shard over the worker axis
        def tree_specs(t):
            return None if t is None else jax.tree.map(comm_leaf_spec, t, pspecs)
        return lz._replace(
            grad_ema=tree_specs(lz.grad_ema),
            stat_ema=shard(lz.stat_ema, P(wa)),
            stat_count=shard(lz.stat_count, P(wa)),
            sigma_hat_sq=shard(lz.sigma_hat_sq, P(wa)),
            theta_last=tree_specs(lz.theta_last),
        )

    def svrg_specs(sv):
        # both fields mirror the param pytree with a leading worker dim
        def tree_specs(t):
            return None if t is None else jax.tree.map(comm_leaf_spec, t, pspecs)
        return SvrgState(theta_anchor=tree_specs(sv.theta_anchor),
                         mu_anchor=tree_specs(sv.mu_anchor))

    def error_specs(er):
        # the EF residual mirrors qhat: param pytree + leading worker dim
        if er.residual is None:
            return ErrorState(None)
        return ErrorState(residual=jax.tree.map(comm_leaf_spec,
                                                er.residual, pspecs))

    def defense_specs(ds):
        # all-scalar per-worker fields: gate EMA + debias count + rejects
        if ds.norm_ema is None:
            return DefenseState(None, None, None)
        return DefenseState(norm_ema=shard(ds.norm_ema, P(wa)),
                            norm_count=shard(ds.norm_count, P(wa)),
                            rejects=shard(ds.rejects, P(wa)))

    comm_s = CommState(
        qhat=jax.tree.map(comm_leaf_spec, comm_abs.qhat, pspecs),
        server_agg=jax.tree.map(lambda l, sp: shard(l, sp),
                                comm_abs.server_agg, pspecs),
        eps_hat_sq=shard(comm_abs.eps_hat_sq, P(wa)),
        clocks=shard(comm_abs.clocks, P(wa)),
        bits_spent=shard(comm_abs.bits_spent, P(wa)),
        theta_hist=shard(comm_abs.theta_hist, P()),
        total_bits=shard(comm_abs.total_bits, P()),
        total_uploads=shard(comm_abs.total_uploads, P()),
        step=shard(comm_abs.step, P()),
        lazy=lazy_specs(comm_abs.lazy),
        R_anchor=shard(comm_abs.R_anchor, P(wa)),
        svrg=svrg_specs(comm_abs.svrg),
        error=error_specs(comm_abs.error),
        defense=defense_specs(comm_abs.defense),
    )
    step_s = shard(jax.ShapeDtypeStruct((), jnp.int32), P())
    return TrainState(params_s, opt_s, comm_s, step_s)


def _match_param_spec(leaf, params_abs, pspecs):
    for pl, sp in zip(jax.tree.leaves(params_abs), jax.tree.leaves(pspecs)):
        if pl.shape == leaf.shape:
            return sp
    return P()


def batch_specs(cfg: ModelConfig, mesh, batch: int, seq: int, worker_axes=None):
    """Global batch sharded over *all* data-parallel axes (regardless of LAQ
    worker granularity — hierarchical mode keeps per-pod data parallelism
    under GSPMD)."""
    mesh = auto_axes(mesh)
    dp = ("pod", "data") if "pod" in mesh.axis_names else ("data",)
    s = NamedSharding(mesh, P(dp, None))
    return {
        "tokens": jax.ShapeDtypeStruct((batch, seq), jnp.int32, sharding=s),
        "targets": jax.ShapeDtypeStruct((batch, seq), jnp.int32, sharding=s),
    }
