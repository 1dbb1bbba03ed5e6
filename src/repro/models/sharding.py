"""Parameter / cache PartitionSpec rules.

Megatron-style tensor parallelism on the ``model`` axis, with a universal
divisibility guard: a dim is sharded only when the *semantic* unit count
(heads, experts, ff, inner) divides the model-axis size; otherwise it is
replicated.  This is what lets e.g. mamba2-130m (24 SSM heads) or
musicgen-medium (24 attention heads) lower on a 16-way model axis — small
models simply don't tensor-parallelize, rather than being resharded
silently.
"""
from __future__ import annotations

import jax
from jax.sharding import PartitionSpec as P

from .config import ModelConfig


def _div(n: int, ms: int) -> bool:
    return n > 0 and n % ms == 0


def param_pspecs(cfg: ModelConfig, params, model_size: int, model_axis="model"):
    """A pytree of PartitionSpec mirroring ``params``."""
    ms = model_size
    m = model_axis
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    di = cfg.d_inner

    def rule(path, leaf):
        names = [getattr(k, "key", str(k)) for k in path]
        name = names[-1]
        # a leading layer, shared-block or invocation dim
        stacked = names[0] in ("blocks", "shared", "hybrid")
        def wrap(*spec):
            return P(*(((None,) + spec) if stacked else spec))

        if name == "embed":
            return P(m if _div(cfg.padded_vocab(), ms) else None, None)
        if name == "lm_head":
            return P(None, m if _div(cfg.padded_vocab(), ms) else None)
        if name == "final_norm":
            return P(None)
        # attention
        if name == "wq":
            return wrap(None, m if _div(H, ms) else None)
        if name in ("wk", "wv"):
            return wrap(None, m if _div(KV, ms) else None)
        if name == "wo":
            return wrap(m if _div(H, ms) else None, None)
        # dense mlp vs moe (moe tensors have a leading expert dim)
        if name in ("w_gate", "w_up"):
            if "moe" in names:
                return wrap(m if _div(cfg.n_experts, ms) else None, None, None)
            return wrap(None, m if _div(cfg.d_ff, ms) else None)
        if name == "w_down":
            if "moe" in names:
                return wrap(m if _div(cfg.n_experts, ms) else None, None, None)
            return wrap(m if _div(cfg.d_ff, ms) else None, None)
        # hybrid invocation: the adapter's second matrix splits like gate/up
        if name in ("adapter_gate", "adapter_up"):
            return wrap(None, m if _div(cfg.d_ff, ms) else None)
        if name == "router":
            return wrap(None, None)
        # mamba2
        if name in ("w_z", "w_x"):
            return wrap(None, m if _div(di, ms) else None)
        if name in ("conv_x",):
            return wrap(None, m if _div(di, ms) else None)
        if name in ("conv_x_b", "gate_norm"):
            return wrap(m if _div(di, ms) else None)
        if name == "w_dt":
            return wrap(None, m if _div(cfg.ssm_heads, ms) else None)
        if name == "out_proj":
            return wrap(m if _div(di, ms) else None, None)
        # small vectors: replicate
        return wrap(*([None] * (leaf.ndim - (1 if stacked else 0))))

    return jax.tree_util.tree_map_with_path(rule, params)


def cache_pspecs(cfg: ModelConfig, cache, data_size: int, model_size: int,
                 data_axis="data", model_axis="model"):
    """KV/SSM cache specs: batch on data (if divisible), seq / heads on model.

    The KV cache shards its *sequence* dim on the model axis (flash-decode);
    the mamba state shards heads when divisible.
    """
    def rule(path, leaf):
        names = [getattr(k, "key", str(k)) for k in path]
        name = names[-1]
        if name == "pos":
            return P()
        batch = leaf.shape[1]
        d = data_axis if _div(batch, data_size) else None
        if name in ("k", "v"):           # [L,B,Sc,KV,hd]
            seq = leaf.shape[2]
            s = model_axis if _div(seq, model_size) else None
            return P(None, d, s, None, None)
        if name == "ssm":                # [L,B,H,P,N]
            h = model_axis if _div(leaf.shape[2], model_size) else None
            return P(None, d, h, None, None)
        if name.startswith("conv_"):     # [L,B,K-1,C]
            c = model_axis if (name == "conv_x" and _div(leaf.shape[3], model_size)) else None
            return P(None, d, None, c)
        return P(*([None] * leaf.ndim))

    return jax.tree_util.tree_map_with_path(rule, cache)
