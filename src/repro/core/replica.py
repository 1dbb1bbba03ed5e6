"""Lazy-replica publishing: the LAQ wire as a model-delta CDN.

The paper's lazy rule (eq. 7a) skips worker *uploads* whose innovation is
below a drift threshold.  This module applies the same change-detection
idea on the **push** side: a trainer continuously publishes *quantized
parameter deltas* to a fleet of inference replicas, and skips the push
entirely while the parameters have not moved enough to matter — LAG's
skip rule as generic change detection (Chen et al., 2018), with DGC-style
delta compression (Lin et al., 2018) making the continuous weight sync
bandwidth-feasible.  Nothing here touches training; the publisher is a
passive observer of the parameter stream.

Protocol (normative spec: ``docs/serving.md``; byte semantics shared with
the upload wire, ``docs/wire-format.md``):

* The publisher tracks ``theta_pub`` — the fleet's dequantize-accumulated
  view of the parameters, maintained with exactly the upload path's
  ``qhat`` recursion: after a quantized push,
  ``theta_pub <- theta_pub + dequant(quant(theta - theta_pub))``, so the
  quantization error does NOT accumulate across pushes (each push
  quantizes the *remaining* difference).
* Push decision — the lazy rule.  The innovation radius
  ``R = max_leaf ||theta - theta_pub||_inf`` is compared against a
  *scale-free relative threshold*: ``push iff R > threshold * A`` where
  ``A`` is the decaying peak envelope ``A^k = max(R^k, anchor_decay *
  A^{k-1})`` — literally the ``BitSchedule`` rel-anchor machinery of
  :mod:`repro.core.adaptive` (``threshold=0`` always pushes;
  ``threshold >= 1`` never pushes lazily, leaving resync-only mode).
* Bounded staleness.  Every skipped round increments ``rounds_behind``;
  when it would exceed ``max_staleness`` the publisher sends a
  **full-precision resync** (raw f32 parameters, ``dense_bits(p)`` on the
  wire) that restores *bitwise* equality between replica and trainer and
  resets the error recursion.  ``max_staleness=0`` degenerates to
  always-push-float32 (the serving baseline).
* Adaptive width.  With ``bit_schedule`` set (a rel-mode
  :class:`~repro.core.adaptive.BitSchedule`), the per-push width is chosen
  by :func:`~repro.core.adaptive.select_bits` from the shared anchor and
  announced in the message (the 8-bit width sidecar of the wire spec).

The wire content of a push is produced by the pluggable
:class:`~repro.core.wire.WireBackend` **one leaf at a time** (the per-leaf
streamed idiom of the sharded ``_packed_aggregate``): per leaf, innovation
-> quantize -> pack before the next leaf is touched, so the transient
footprint is O(max leaf), and the replica decodes with the same per-leaf
streaming.  Per-leaf radii are required (``per_leaf_radius`` semantics):
parameter-delta scales differ by orders of magnitude between embedding /
norm / projection leaves, exactly the bucketing argument of the training
wire.

Bitwise contract (pinned by tests/test_replica.py and the
``serve_frontier`` harness on BOTH wire backends): a replica that applies
every message reproduces ``theta_pub`` bit-for-bit — the decode path
(:func:`repro.core.wire.delta_of_codes` on the unpacked payload) is
expression-identical to the publisher's ``q_new`` accumulation — and a
resync restores bitwise equality with the trainer.  While skipping, the
staleness drift is bounded: ``||theta - replica||_inf = R <= threshold *
A`` on every skipped round (plus ``tau(b) * R_push`` quantization error
after the preceding push, the paper's Fig. 1 guarantee).

Everything here is host-side orchestration over device arrays: the
publisher runs between jitted trainer rounds, not inside them.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from .adaptive import BitSchedule, select_bits
from .quantize import dense_bits, tree_size, unpack_codes, upload_bits
from .wire import delta_of_codes, get_backend

Pytree = object


class PublishConfig(NamedTuple):
    """Publisher-side knobs (see module docstring for semantics)."""
    bits: int = 4                   # quantized-push width (fixed mode)
    threshold: float = 0.25         # push iff R > threshold * anchor; 0 = always
    anchor_decay: float = 0.9       # peak-envelope decay per round (fixed mode)
    max_staleness: int = 8          # skipped rounds tolerated before a resync
    wire_backend: object = "reference"   # name or WireBackend instance
    bit_schedule: Optional[BitSchedule] = None  # rel-mode schedule: adaptive width

    def validate(self) -> "PublishConfig":
        assert self.bits in (1, 2, 4, 8), self.bits
        assert self.threshold >= 0.0, self.threshold
        assert 0.0 < self.anchor_decay <= 1.0, self.anchor_decay
        assert self.max_staleness >= 0, self.max_staleness
        if self.bit_schedule is not None:
            self.bit_schedule.validate()
            assert self.bit_schedule.adaptive, \
                "constant schedules belong in PublishConfig.bits"
            assert self.bit_schedule.threshold_mode == "rel", \
                "the publisher anchor is the rel-mode anchor; abs-threshold " \
                "schedules have no shared anchor to reuse"
        return self


class PublisherState(NamedTuple):
    """Trainer-side publishing state (host-side; pytrees hold device arrays)."""
    theta_pub: Pytree           # the fleet's dequantize-accumulated view (f32)
    R_anchor: jax.Array         # decaying peak envelope A^k (f32 scalar)
    rounds_behind: int = 0      # consecutive rounds since the last message
    seq: int = 0                # publisher round counter
    n_pushes: int = 0           # quantized delta pushes sent
    n_resyncs: int = 0          # full-precision resyncs sent
    bits_sent: float = 0.0      # cumulative wire bits (analytic accounting)


class DeltaMsg(NamedTuple):
    """One quantized parameter-delta push (per-leaf packed payload)."""
    seq: int                    # publisher round this delta was cut at
    width: int                  # quantization bits b (the width sidecar)
    bits: float                 # analytic wire cost of this message
    payloads: list              # per-leaf packed uint8 codes (wire spec §3)
    radii: list                 # per-leaf f32 scalar radii (wire spec §1)


class ResyncMsg(NamedTuple):
    """Full-precision resync: raw f32 parameters (bounded-staleness escape)."""
    seq: int
    bits: float
    params: Pytree


class ReplicaState(NamedTuple):
    """One inference replica's serving weights + freshness bookkeeping."""
    params: Pytree              # serving weights (f32)
    rounds_behind: int = 0      # rounds since the last applied message
    seq: int = -1               # seq of the last applied message
    n_applied: int = 0
    n_resyncs: int = 0


def _f32_copy(tree: Pytree) -> Pytree:
    return jax.tree.map(lambda l: jnp.asarray(l, jnp.float32), tree)


def init_publisher(params: Pytree, cfg: PublishConfig) -> PublisherState:
    """Publisher with the fleet bootstrapped at an exact snapshot of
    ``params`` — the initial full-precision sync is accounted at
    ``dense_bits(p)`` (every policy pays it, so byte comparisons stay
    honest)."""
    cfg.validate()
    return PublisherState(theta_pub=_f32_copy(params),
                          R_anchor=jnp.zeros((), jnp.float32),
                          bits_sent=float(dense_bits(tree_size(params))))


def init_replica(snapshot: Pytree) -> ReplicaState:
    """Replica joining the fleet from a full-precision snapshot (the same
    snapshot the publisher's ``theta_pub`` started from, or a later
    :class:`ResyncMsg` payload)."""
    return ReplicaState(params=_f32_copy(snapshot))


def _leaf_radii(backend, g_leaves, q_leaves):
    """Pass 1, streamed: one scalar innovation radius per leaf (the fused
    backend's absmax kernel / the reference max-abs, via the backend's own
    ``innovation`` on a single-leaf tree — no whole-model diff is ever
    materialized)."""
    radii = []
    for g, q in zip(g_leaves, q_leaves):
        if g.size == 0:
            radii.append(jnp.zeros((), jnp.float32))
            continue
        _, _, R = backend.innovation(g, q, per_leaf=True)
        radii.append(R)
    return radii


def publish(cfg: PublishConfig, state: PublisherState,
            params: Pytree):
    """One publisher round against the current trainer ``params``.

    Returns ``(msg, new_state)`` where ``msg`` is ``None`` (lazy skip), a
    :class:`DeltaMsg` (quantized push) or a :class:`ResyncMsg`
    (full-precision bounded-staleness escape).  Decision order:

    1. ``R == 0`` — the published view already equals the parameters:
       skip (and never resync; there is nothing to say).
    2. ``threshold == 0`` or ``R > threshold * A`` — quantized push.
    3. ``rounds_behind + 1 > max_staleness`` — full resync.
    4. otherwise — skip (``rounds_behind`` grows).
    """
    cfg.validate()
    backend = get_backend(cfg.wire_backend)
    g_leaves, treedef = jax.tree_util.tree_flatten(params)
    q_leaves = jax.tree_util.tree_leaves(state.theta_pub)
    radii = _leaf_radii(backend, g_leaves, q_leaves)
    R_max = (jnp.max(jnp.stack(radii)) if radii
             else jnp.zeros((), jnp.float32))
    p = tree_size(params)
    n_leaves = len(g_leaves)

    # anchor + width: the BitSchedule rel-anchor machinery.  Adaptive mode
    # routes through select_bits itself (shared anchor, budget-aware);
    # fixed mode maintains the identical peak-envelope expression.
    if cfg.bit_schedule is not None:
        b_sel, _, anchor_new = select_bits(
            cfg.bit_schedule, R_max, state.bits_sent, state.seq, p,
            n_radii=n_leaves, R_anchor=state.R_anchor)
        width = int(b_sel)
    else:
        width = cfg.bits
        anchor_new = jnp.maximum(R_max, cfg.anchor_decay * state.R_anchor)

    Rm, A = float(R_max), float(anchor_new)
    base = state._replace(R_anchor=anchor_new, seq=state.seq + 1)

    if Rm == 0.0:
        return None, base._replace(rounds_behind=state.rounds_behind + 1)

    if cfg.threshold == 0.0 or Rm > cfg.threshold * A:
        # pass 2, streamed: per leaf, quantize -> pack -> q_new before the
        # next leaf is touched (payload layout is the backend's; byte
        # semantics are the wire spec's)
        qn_leaves, payloads, radii_out = [], [], []
        for g, q in zip(g_leaves, q_leaves):
            if g.size == 0:
                qn_leaves.append(jnp.zeros(g.shape, jnp.float32))
                payloads.append(jnp.zeros((0,), jnp.uint8))
                radii_out.append(jnp.zeros((), jnp.float32))
                continue
            rt = backend.roundtrip(g, q, width, per_leaf=True,
                                   with_payload=True)
            qn_leaves.append(rt.q_new)
            payloads.append(rt.payload[0].reshape(-1))   # flat bytes
            radii_out.append(rt.R_tree)
        bits = float(upload_bits(p, width, n_radii=n_leaves,
                                 bit_sidecar=cfg.bit_schedule is not None))
        msg = DeltaMsg(seq=state.seq, width=width, bits=bits,
                       payloads=payloads, radii=radii_out)
        return msg, base._replace(
            theta_pub=jax.tree_util.tree_unflatten(treedef, qn_leaves),
            rounds_behind=0, n_pushes=state.n_pushes + 1,
            bits_sent=state.bits_sent + bits)

    if state.rounds_behind + 1 > cfg.max_staleness:
        bits = float(dense_bits(p))
        msg = ResyncMsg(seq=state.seq, bits=bits, params=_f32_copy(params))
        return msg, base._replace(
            theta_pub=_f32_copy(params), rounds_behind=0,
            n_resyncs=state.n_resyncs + 1,
            bits_sent=state.bits_sent + bits)

    return None, base._replace(rounds_behind=state.rounds_behind + 1)


def apply_message(state: ReplicaState, msg,
                  cfg: PublishConfig) -> ReplicaState:
    """Replica side: dequantize-accumulate a :class:`DeltaMsg` into the
    serving weights (per-leaf streamed, bitwise equal to the publisher's
    ``theta_pub`` recursion), install a :class:`ResyncMsg` snapshot
    verbatim, or age one round on ``None``."""
    if msg is None:
        return state._replace(rounds_behind=state.rounds_behind + 1)
    if isinstance(msg, ResyncMsg):
        return ReplicaState(params=_f32_copy(msg.params), rounds_behind=0,
                            seq=msg.seq, n_applied=state.n_applied + 1,
                            n_resyncs=state.n_resyncs + 1)
    leaves, treedef = jax.tree_util.tree_flatten(state.params)
    new_leaves = []
    for leaf, payload, R in zip(leaves, msg.payloads, msg.radii):
        if leaf.size == 0:
            new_leaves.append(leaf)
            continue
        # payloads may be pad-extended (cpb / Pallas BLOCK multiples);
        # codes are in order, so the first `size` are the real ones
        codes = unpack_codes(payload, msg.width)[:leaf.size]
        delta = delta_of_codes(codes, R, msg.width).reshape(leaf.shape)
        new_leaves.append(leaf + delta)
    return ReplicaState(params=jax.tree_util.tree_unflatten(treedef,
                                                            new_leaves),
                        rounds_behind=0, seq=msg.seq,
                        n_applied=state.n_applied + 1,
                        n_resyncs=state.n_resyncs)


def staleness_drift(params: Pytree, replica: ReplicaState) -> float:
    """Serving-freshness diagnostic: ``||theta - replica||_inf`` (the bound
    the lazy rule enforces on skipped rounds is ``threshold * A`` against
    the published view; see module docstring)."""
    return max(float(jnp.max(jnp.abs(jnp.asarray(g, jnp.float32) - r)))
               if g.size else 0.0
               for g, r in zip(jax.tree_util.tree_leaves(params),
                               jax.tree_util.tree_leaves(replica.params)))
