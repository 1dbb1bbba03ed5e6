"""Expert-parallel MoE: top-k router + capacity-based gather dispatch.

Sharding scheme: expert weight tensors carry a leading expert dim sharded on
the ``model`` mesh axis; token activations are replicated across ``model``
(they are batch-sharded on ``data``).  Dispatch is *gather-based* — per
(expert, slot) we compute the source token index and gather — so the HLO
contains only real expert matmuls, not the O(T*E*C) one-hot dispatch einsum
of the classic Switch formulation (which would dwarf the useful FLOPs).

Two paths:
* **capacity path** (train / prefill, S > 1): tokens grouped per batch row,
  per-expert capacity C = Tg * top_k / E * capacity_factor, overflow dropped
  (standard GShard/Switch semantics).  The combine gather over the
  expert-sharded buffer lowers to an all-gather over ``model`` under GSPMD
  (``ModelConfig.moe_combine="scatter"`` replaces it with an all-reduce).
* **dense path** (decode, S == 1): every local expert is applied to every
  token and the result masked-combined with a contraction over the sharded
  expert dim (an all-reduce). With a handful of tokens per device the expert
  *weight reads* dominate decode cost regardless of routing, so this wastes
  nothing that matters while staying GSPMD-exact.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .config import ModelConfig
from .layers import normal_init


def _shard_experts(x, spec):
    """Pin a tensor's expert dim to the model axis when a mesh is ambient.

    Without this GSPMD re-shards the f32 *cotangents* of the dispatch/expert
    buffers to replicated inside the remat backward — an all-gather of
    E*C*D f32 per layer (measured: 2x 5 GiB/layer on qwen3-moe train_4k).
    Constraints on the forward values propagate to the cotangents.
    """
    try:
        mesh = jax.sharding.get_abstract_mesh()
        if mesh is None or "model" not in mesh.axis_names:
            return x
        return jax.lax.with_sharding_constraint(x, P(*spec))
    except Exception:  # no mesh context (single-device tests)
        return x


def init_moe(key, cfg: ModelConfig, dtype):
    D, E, F = cfg.d_model, cfg.n_experts, cfg.moe_d_ff
    ks = jax.random.split(key, 4)
    return {
        "router": normal_init(ks[0], (D, E), D ** -0.5, jnp.float32),
        "w_gate": normal_init(ks[1], (E, D, F), D ** -0.5, dtype),
        "w_up": normal_init(ks[2], (E, D, F), D ** -0.5, dtype),
        "w_down": normal_init(ks[3], (E, F, D), F ** -0.5, dtype),
    }


import functools


@functools.lru_cache(maxsize=None)
def _make_dispatch(S: int, dtype_name: str):
    """Gather-dispatch with a hand-written transpose.

    ``xe = x[b, src]`` (x [B,S,D], src [B,E,C] -> [B,E,C,D]; src<0 => 0).
    The autodiff transpose of this gather is a scatter with a packed 2-vector
    index layout that GSPMD partitions by REPLICATING the E-sharded updates —
    an all-gather of E*C*D f32 per layer (measured: 2x 5 GiB/layer on
    qwen3-moe train_4k). Writing the transpose ourselves in the batched
    .at[].add form lowers to partial scatters + one all-reduce of [B,S,D]
    (the pattern GSPMD gets right).
    """
    dtype = jnp.dtype(dtype_name)

    @jax.custom_vjp
    def dispatch(x, src):
        B = x.shape[0]
        b_idx = jnp.arange(B)[:, None, None]
        xe = x[b_idx, jnp.maximum(src, 0)]
        return jnp.where((src >= 0)[..., None], xe, 0)

    def fwd(x, src):
        return dispatch(x, src), src

    def bwd(src, g):
        B, D = g.shape[0], g.shape[-1]
        b_idx = jnp.arange(B)[:, None, None]
        # re-pin the cotangent's expert sharding: inside the remat backward
        # GSPMD otherwise treats g as replicated and all-gathers it
        g = _shard_experts(g, (None, "model", None, None))
        g = jnp.where((src >= 0)[..., None], g, 0)
        dx = jnp.zeros((B, S, D), g.dtype)
        dx = dx.at[b_idx, jnp.maximum(src, 0)].add(g, mode="drop")
        return dx.astype(dtype), None

    dispatch.defvjp(fwd, bwd)
    return dispatch


def _dispatch(x, src):
    return _make_dispatch(x.shape[1], jnp.dtype(x.dtype).name)(x, src)


def _router(p, x, cfg: ModelConfig):
    """x:[..., D] -> (probs, topk weights, topk ids, aux_loss)."""
    logits = (x.astype(jnp.float32) @ p["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_ids = jax.lax.top_k(probs, cfg.top_k)
    top_w = top_w / jnp.maximum(jnp.sum(top_w, axis=-1, keepdims=True), 1e-9)
    # Switch-style load-balance aux loss: E * sum_e f_e * P_e
    E = cfg.n_experts
    assign = jnp.sum(jax.nn.one_hot(top_ids, E, dtype=jnp.float32), axis=-2)
    f_e = jnp.mean(assign, axis=tuple(range(assign.ndim - 1)))
    P_e = jnp.mean(probs, axis=tuple(range(probs.ndim - 1)))
    aux = E * jnp.sum(f_e * P_e)
    return top_w, top_ids, aux


def _experts_apply(p, xe):
    """xe:[...,E,C,D] grouped per expert; batched SwiGLU."""
    h = jax.nn.silu(jnp.einsum("becd,edf->becf", xe, p["w_gate"]))
    h = h * jnp.einsum("becd,edf->becf", xe, p["w_up"])
    return jnp.einsum("becf,efd->becd", h, p["w_down"])


def moe_forward_capacity(p, x, cfg: ModelConfig):
    """Train/prefill path. x:[B,S,D] -> ([B,S,D], aux_loss).

    Groups = batch rows (aligned with the data-sharded batch dim, so all
    cumsum/sort work is shard-local).
    """
    B, S, D = x.shape
    E, K = cfg.n_experts, cfg.top_k
    C = max(1, int(S * K / E * cfg.capacity_factor))
    C = min(C, S)

    top_w, top_ids, aux = _router(p, x, cfg)           # [B,S,K]

    # position of each token within its expert's buffer
    assign = jnp.sum(jax.nn.one_hot(top_ids, E, dtype=jnp.int32), axis=2)  # [B,S,E]
    pos_all = jnp.cumsum(assign, axis=1) * assign - 1                      # [B,S,E]
    pos_k = jnp.take_along_axis(pos_all, top_ids, axis=2)                  # [B,S,K]
    keep = pos_k < C                                                       # overflow -> drop

    # inverse map: src[b,e,c] = token index feeding slot (e,c)
    b_idx = jnp.arange(B)[:, None, None]
    t_idx = jnp.broadcast_to(jnp.arange(S)[None, :, None], (B, S, K))
    src = jnp.full((B, E, C), -1, jnp.int32)
    src = src.at[b_idx, top_ids, jnp.where(keep, pos_k, C)].set(
        t_idx, mode="drop")                                                # [B,E,C]

    # gather-dispatch (x replicated over model; src sharded on E -> local)
    valid = (src >= 0)[..., None]
    xe = _dispatch(x, src).astype(x.dtype)                                 # [B,E,C,D]
    xe = _shard_experts(xe, (None, "model", None, None))

    ye = _experts_apply(p, xe)                                             # [B,E,C,D]
    ye = _shard_experts(ye, (None, "model", None, None))

    if cfg.moe_combine == "scatter":
        # expert-side scatter-add: GSPMD computes partial scatters per model
        # shard and all-reduces [B,S,D] (T*D payload, vs E*C*D for gather)
        wsrc = jnp.zeros((B, E, C), jnp.float32)
        wsrc = wsrc.at[b_idx, top_ids, jnp.where(keep, pos_k, C)].set(
            top_w * keep.astype(jnp.float32), mode="drop")
        upd = ye * wsrc[..., None].astype(ye.dtype)
        upd = jnp.where(valid, upd, 0)
        upd = _shard_experts(upd, (None, "model", None, None))
        out = jnp.zeros((B, S, D), x.dtype)
        out = out.at[b_idx, jnp.maximum(src, 0)].add(upd, mode="drop")
    else:
        # baseline: per-token gather (all-gather of the expert buffer)
        out_k = ye[b_idx, top_ids, jnp.minimum(pos_k, C - 1)]              # [B,S,K,D]
        w = (top_w * keep.astype(jnp.float32)).astype(x.dtype)
        out = jnp.einsum("bskd,bsk->bsd", out_k, w)
    return out, aux


def moe_forward_dense(p, x, cfg: ModelConfig):
    """Decode path (S small): apply all experts, mask-combine, reduce over E."""
    top_w, top_ids, aux = _router(p, x, cfg)           # [B,S,K]
    E = cfg.n_experts
    # gate[b,s,e] = weight if e in top-k else 0
    gate = jnp.sum(jax.nn.one_hot(top_ids, E, dtype=jnp.float32)
                   * top_w[..., None], axis=2)         # [B,S,E]
    h = jax.nn.silu(jnp.einsum("bsd,edf->bsef", x, p["w_gate"]))
    h = h * jnp.einsum("bsd,edf->bsef", x, p["w_up"])
    ye = jnp.einsum("bsef,efd->bsed", h, p["w_down"])  # [B,S,E,D]
    out = jnp.einsum("bsed,bse->bsd", ye, gate.astype(x.dtype))
    return out, aux


def moe_forward(p, x, cfg: ModelConfig):
    if x.shape[1] == 1:
        return moe_forward_dense(p, x, cfg)
    return moe_forward_capacity(p, x, cfg)
