"""Decoder stack: scan-over-layers assembly of blocks, per arch family.

Layers are *stacked*: block parameters carry a leading layer dim and the
forward is a single ``lax.scan`` over it (MaxText-style), so the HLO contains
one layer body regardless of depth and compile time does not grow with it.

Hybrid stacks are Zamba2's (arXiv:2411.15242; the layer equations of
``transformers``' ``Zamba2ForCausalLM``).  Every layer is a Mamba2 layer
``x + mamba(norm(x + t))``; ``t`` is 0, except at the layers
``cfg.hybrid_layers`` lists, where it is the output of a *shared* block fed
with the embedding output ``e``:

    t = linear_j(mlp_b(norm(attn_b(norm(cat(x, e))))))

``attn_b`` reads the ``2 * d_model`` concatenation and scores at
``(hd / 2) ** -0.5``; ``mlp_b`` is ``gelu(g) * u`` with invocation ``j``'s
low-rank adapter added to ``g`` and ``u``; the block has no residual of its
own.  The ``cfg.num_mem_blocks`` shared blocks ``b = j % num_mem_blocks``
alternate over the invocations ``j``; each invocation has its own adapter
and ``linear``.  The runs of Mamba layers between hybrid layers are scanned;
each hybrid layer is its own call, so its invocation's weights are static
slices.  Its per-invocation KV caches are a stacked ``[n_hybrid, ...]``
array.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.spans import SHARED_BLOCK

from .attention import (attention_forward, decode_attention, init_attention,
                        init_kv_cache)
from .config import ModelConfig
from .layers import gelu_adapted_mlp, init_mlp, normal_init, rms_norm, swiglu
from .mamba2 import (init_mamba2, init_mamba_cache, mamba2_decode,
                     mamba2_forward)
from .moe import init_moe, moe_forward, moe_forward_dense


# ---------------------------------------------------------------------------
# Block initializers
# ---------------------------------------------------------------------------

def init_attn_block(key, cfg: ModelConfig, dtype):
    k1, k2 = jax.random.split(key)
    p = {"ln1": jnp.zeros((cfg.d_model,), jnp.float32),
         "ln2": jnp.zeros((cfg.d_model,), jnp.float32),
         "attn": init_attention(k1, cfg, dtype)}
    if cfg.n_experts:
        p["moe"] = init_moe(k2, cfg, dtype)
    else:
        p["mlp"] = init_mlp(k2, cfg.d_model, cfg.d_ff, dtype)
    return p


def init_mamba_block(key, cfg: ModelConfig, dtype):
    return {"ln": jnp.zeros((cfg.d_model,), jnp.float32),
            "mamba": init_mamba2(key, cfg, dtype)}


def init_shared_block(key, cfg: ModelConfig, dtype):
    D = cfg.d_model
    k1, k2 = jax.random.split(key)
    return {"ln_in": jnp.zeros((2 * D,), jnp.float32),
            "attn": init_attention(k1, cfg, dtype, d_in=2 * D),
            "ln_mlp": jnp.zeros((D,), jnp.float32),
            "mlp": init_mlp(k2, D, cfg.d_ff, dtype)}


def init_invocation(key, cfg: ModelConfig, dtype):
    """One hybrid layer's own weights: the MLP adapter and the linear."""
    D, F, r = cfg.d_model, cfg.d_ff, cfg.adapter_rank
    ks = jax.random.split(key, 4)
    return {"adapter_in": normal_init(ks[0], (D, r), D ** -0.5, dtype),
            "adapter_gate": normal_init(ks[1], (r, F), r ** -0.5, dtype),
            "adapter_up": normal_init(ks[2], (r, F), r ** -0.5, dtype),
            "linear": normal_init(ks[3], (D, D), D ** -0.5, dtype)}


def init_params(key, cfg: ModelConfig):
    dtype = cfg.param_dtype
    V, D = cfg.padded_vocab(), cfg.d_model
    k_embed, k_head, k_blocks, k_shared = jax.random.split(key, 4)
    params = {
        "embed": normal_init(k_embed, (V, D), 1.0, dtype),
        "final_norm": jnp.zeros((D,), jnp.float32),
        "lm_head": normal_init(k_head, (D, V), D ** -0.5, dtype),
    }
    keys = jax.random.split(k_blocks, cfg.n_layers)
    if cfg.arch_type in ("dense", "moe", "vlm", "audio"):
        params["blocks"] = jax.vmap(lambda k: init_attn_block(k, cfg, dtype))(keys)
    elif cfg.arch_type == "ssm":
        params["blocks"] = jax.vmap(lambda k: init_mamba_block(k, cfg, dtype))(keys)
    elif cfg.arch_type == "hybrid":
        params["blocks"] = jax.vmap(lambda k: init_mamba_block(k, cfg, dtype))(keys)
        k_mem, k_inv = jax.random.split(k_shared)
        params["shared"] = jax.vmap(lambda k: init_shared_block(k, cfg, dtype))(
            jax.random.split(k_mem, cfg.num_mem_blocks))
        params["hybrid"] = jax.vmap(lambda k: init_invocation(k, cfg, dtype))(
            jax.random.split(k_inv, len(cfg.hybrid_layers)))
    else:
        raise ValueError(cfg.arch_type)
    return params


# ---------------------------------------------------------------------------
# Block forwards (train / prefill)
# ---------------------------------------------------------------------------

def attn_block_fwd(bp, x, positions, cfg: ModelConfig, *, return_kv=False):
    """Returns (x, aux, kv)."""
    h, kv = (attention_forward(bp["attn"], rms_norm(x, bp["ln1"], cfg.norm_eps),
                               positions, cfg, return_kv=True)
             if return_kv else
             (attention_forward(bp["attn"], rms_norm(x, bp["ln1"], cfg.norm_eps),
                                positions, cfg), None))
    x = x + h
    h2 = rms_norm(x, bp["ln2"], cfg.norm_eps)
    if "moe" in bp:
        m, aux = moe_forward(bp["moe"], h2, cfg)
    else:
        m, aux = swiglu(h2, **bp["mlp"]), jnp.zeros((), jnp.float32)
    return x + m, aux, kv


def mamba_block_fwd(bp, x, cfg: ModelConfig, *, return_state=False, t=None):
    """``x + mamba(norm(x))``, or ``x + mamba(norm(x + t))`` at a hybrid
    layer."""
    h_in = x if t is None else x + t
    h, state, tail = mamba2_forward(bp["mamba"], rms_norm(h_in, bp["ln"], cfg.norm_eps), cfg)
    return x + h, (state, tail) if return_state else None


def shared_block(sp, hp, x, e, attend, cfg: ModelConfig):
    """The shared block's output ``t`` at one hybrid layer, and what
    ``attend(attn_params, h, scale)`` returns beside the attention output
    (its KV).  ``sp``: the shared block's weights; ``hp``: the invocation's
    adapter and linear.  Forward, prefill and decode differ only in
    ``attend``."""
    with jax.named_scope(SHARED_BLOCK):
        h = rms_norm(jnp.concatenate([x, e], axis=-1), sp["ln_in"], cfg.norm_eps)
        a, kv = attend(sp["attn"], h, (cfg.hd / 2) ** -0.5)
        h = rms_norm(a, sp["ln_mlp"], cfg.norm_eps)
        m = gelu_adapted_mlp(h, **sp["mlp"], adapter_in=hp["adapter_in"],
                             adapter_gate=hp["adapter_gate"],
                             adapter_up=hp["adapter_up"])
        return (m @ hp["linear"]).astype(x.dtype), kv


def _hybrid_stack(params, x, cfg: ModelConfig, layer, mcache=None, kv=None):
    """The hybrid stack in layer order.  ``layer(bp, x, mc, inv)`` runs one
    layer: ``inv`` is ``None`` for a Mamba layer, else ``(sp, hp, kvj)``,
    the shared block, adapter and linear of the hybrid layer's invocation
    ``j`` and its KV cache (``None`` without one); it returns ``(x, mc_new,
    kv_new)``.  ``mcache`` (``kv``) is a per-layer (per-invocation) tree
    with a leading axis, or ``None``.  Runs of Mamba layers are one scan
    each.  Returns ``x``, the new
    per-layer tree (or ``None``) and the invocations' KV in order."""
    blocks = params["blocks"]
    out_m, out_kv = [], []

    def part(tree, lo, hi):
        return None if tree is None else jax.tree.map(lambda a: a[lo:hi], tree)

    def run(x, lo, hi):
        if hi <= lo:
            return x
        def body(x, xs):
            x, mc, _ = layer(xs[0], x, xs[1], None)
            return x, mc
        x, mcs_new = jax.lax.scan(body, x, (part(blocks, lo, hi),
                                            part(mcache, lo, hi)))
        out_m.append(mcs_new)
        return x

    lo = 0
    for j, h in enumerate(cfg.hybrid_layers):
        x = run(x, lo, h)
        inv = (_layer_slice(params["shared"], j % cfg.num_mem_blocks),
               _layer_slice(params["hybrid"], j),
               None if kv is None else _layer_slice(kv, j))
        x, mc, kv_j = layer(_layer_slice(blocks, h), x,
                            None if mcache is None else _layer_slice(mcache, h),
                            inv)
        out_m.append(None if mc is None else jax.tree.map(lambda a: a[None], mc))
        out_kv.append(kv_j)
        lo = h + 1
    x = run(x, lo, cfg.n_layers)
    new_m = (None if mcache is None else
             jax.tree.map(lambda *a: jnp.concatenate(a), *out_m))
    return x, new_m, out_kv


# ---------------------------------------------------------------------------
# Full-stack forward: training (no caches)
# ---------------------------------------------------------------------------

def forward(params, tokens, cfg: ModelConfig):
    """tokens:[B,S] -> (logits [B,S,V], aux_loss)."""
    B, S = tokens.shape
    x = params["embed"][tokens].astype(cfg.compute_dtype)
    positions = jnp.arange(S)

    def maybe_remat(fn):
        return jax.checkpoint(fn) if cfg.remat else fn

    # the scan carry keeps the compute dtype: params held in f32 (the SVRG
    # anchor) would otherwise promote a layer's output past its input
    if cfg.arch_type == "hybrid":
        e = x

        def attend(ap, h, scale):
            return attention_forward(ap, h, positions, cfg, scale=scale), None

        @maybe_remat
        def layer(bp, x, mc, inv):
            t = None
            if inv is not None:
                t, _ = shared_block(inv[0], inv[1], x, e, attend, cfg)
            return mamba_block_fwd(bp, x, cfg, t=t)[0].astype(x.dtype), None, None

        x, _, _ = _hybrid_stack(params, x, cfg, layer)
        aux = jnp.zeros((), jnp.float32)
    elif cfg.arch_type in ("dense", "moe", "vlm", "audio"):
        @maybe_remat
        def layer(x, bp):
            y, a, _ = attn_block_fwd(bp, x, positions, cfg)
            return y.astype(x.dtype), a

        def body(carry, bp):
            x, aux = carry
            x, a = layer(x, bp)
            return (x, aux + a), None
        (x, aux), _ = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                                   params["blocks"])
    elif cfg.arch_type == "ssm":
        @maybe_remat
        def layer(x, bp):
            y, _ = mamba_block_fwd(bp, x, cfg)
            return y.astype(x.dtype)

        def body(x, bp):
            return layer(x, bp), None
        x, _ = jax.lax.scan(body, x, params["blocks"])
        aux = jnp.zeros((), jnp.float32)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = (x @ params["lm_head"].astype(cfg.compute_dtype)).astype(jnp.float32)
    return logits, aux


def _layer_slice(tree, i):
    return jax.tree.map(lambda a: a[i], tree)


# ---------------------------------------------------------------------------
# Serving: cache init / prefill / decode
# ---------------------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch: int, max_len: int):
    cache = {"pos": jnp.zeros((), jnp.int32)}
    if cfg.arch_type in ("dense", "moe", "vlm", "audio"):
        cache["attn"] = init_kv_cache(cfg, batch, max_len, cfg.n_layers)
    elif cfg.arch_type == "ssm":
        cache["mamba"] = init_mamba_cache(cfg, batch, cfg.n_layers)
    elif cfg.arch_type == "hybrid":
        cache["mamba"] = init_mamba_cache(cfg, batch, cfg.n_layers)
        cache["attn"] = init_kv_cache(cfg, batch, max_len, len(cfg.hybrid_layers))
    return cache


def prefill(params, tokens, cfg: ModelConfig, max_len: int):
    """Processes the prompt; returns (last_token_logits, cache)."""
    B, S = tokens.shape
    assert not cfg.sliding_window or S <= cfg.sliding_window, \
        "ring-buffer prefill not supported; window must cover the prompt"
    x = params["embed"][tokens].astype(cfg.compute_dtype)
    positions = jnp.arange(S)
    cache = init_cache(cfg, B, max_len)
    Sc = jax.tree_util.tree_leaves(cache["attn"])[0].shape[2] if "attn" in cache else 0

    def place_kv(kv):
        k, v = kv
        z = jnp.zeros((B, Sc) + k.shape[2:], cfg.compute_dtype)
        return (jax.lax.dynamic_update_slice(z, k.astype(z.dtype), (0, 0, 0, 0)),
                jax.lax.dynamic_update_slice(z, v.astype(z.dtype), (0, 0, 0, 0)))

    if cfg.arch_type == "hybrid":
        e = x

        def attend(ap, h, scale):
            return attention_forward(ap, h, positions, cfg, return_kv=True,
                                     scale=scale)

        def layer(bp, x, mc, inv):
            t = kv = None
            if inv is not None:
                t, kv = shared_block(inv[0], inv[1], x, e, attend, cfg)
                kv = place_kv(kv)
            x, (st, tail) = mamba_block_fwd(bp, x, cfg, return_state=True, t=t)
            return x, {"ssm": st, "conv_x": tail["x"], "conv_B": tail["B"],
                       "conv_C": tail["C"]}, kv

        x, cache["mamba"], kvs = _hybrid_stack(params, x, cfg, layer,
                                               mcache=cache["mamba"])
        if kvs:
            cache["attn"] = {"k": jnp.stack([k for k, _ in kvs]),
                             "v": jnp.stack([v for _, v in kvs])}
    elif cfg.arch_type in ("dense", "moe", "vlm", "audio"):
        def body(carry, bp):
            x, aux = carry
            x, a, kv = attn_block_fwd(bp, x, positions, cfg, return_kv=True)
            return (x, aux + a), place_kv(kv)
        (x, _), (ks, vs) = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                                        params["blocks"])
        cache["attn"] = {"k": ks, "v": vs}
    elif cfg.arch_type == "ssm":
        def body(x, bp):
            x, st = mamba_block_fwd(bp, x, cfg, return_state=True)
            return x, st
        x, (states, tails) = jax.lax.scan(body, x, params["blocks"])
        cache["mamba"] = {"ssm": states, "conv_x": tails["x"],
                          "conv_B": tails["B"], "conv_C": tails["C"]}

    cache["pos"] = jnp.asarray(S, jnp.int32)
    x = rms_norm(x[:, -1:], params["final_norm"], cfg.norm_eps)
    logits = (x @ params["lm_head"].astype(cfg.compute_dtype)).astype(jnp.float32)
    return logits, cache


def decode_step(params, cache, tokens, cfg: ModelConfig):
    """One-token decode. tokens:[B,1] -> (logits [B,1,V], new cache)."""
    pos = cache["pos"]
    x = params["embed"][tokens].astype(cfg.compute_dtype)
    new_cache = dict(cache)

    if cfg.arch_type == "hybrid":
        e = x

        def layer(bp, x, mc, inv):
            t = kv = None
            if inv is not None:
                ck, cv = inv[2]["k"], inv[2]["v"]

                def attend(ap, h, scale):
                    out, nk, nv = decode_attention(ap, h, ck, cv, pos, cfg, scale)
                    return out, (nk, nv)
                t, kv = shared_block(inv[0], inv[1], x, e, attend, cfg)
            h_in = x if t is None else x + t
            h, mc = mamba2_decode(bp["mamba"], rms_norm(h_in, bp["ln"], cfg.norm_eps),
                                  mc, cfg)
            return x + h, mc, kv

        x, new_cache["mamba"], kvs = _hybrid_stack(
            params, x, cfg, layer, mcache=cache["mamba"], kv=cache["attn"])
        if kvs:
            new_cache["attn"] = {"k": jnp.stack([k for k, _ in kvs]),
                                 "v": jnp.stack([v for _, v in kvs])}
    elif cfg.arch_type in ("dense", "moe", "vlm", "audio"):
        def body(x, xs):
            bp, ck, cv = xs
            h, nk, nv = decode_attention(bp["attn"],
                                         rms_norm(x, bp["ln1"], cfg.norm_eps),
                                         ck, cv, pos, cfg)
            x = x + h
            h2 = rms_norm(x, bp["ln2"], cfg.norm_eps)
            if "moe" in bp:
                m, _ = moe_forward_dense(bp["moe"], h2, cfg)
            else:
                m = swiglu(h2, **bp["mlp"])
            return x + m, (nk, nv)
        x, (ks, vs) = jax.lax.scan(body, x, (params["blocks"],
                                             cache["attn"]["k"], cache["attn"]["v"]))
        new_cache["attn"] = {"k": ks, "v": vs}
    elif cfg.arch_type == "ssm":
        def body(x, xs):
            bp, cslice = xs
            h, nc = mamba2_decode(bp["mamba"], rms_norm(x, bp["ln"], cfg.norm_eps),
                                  cslice, cfg)
            return x + h, nc
        x, nmamba = jax.lax.scan(body, x, (params["blocks"], cache["mamba"]))
        new_cache["mamba"] = nmamba

    new_cache["pos"] = pos + 1
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = (x @ params["lm_head"].astype(cfg.compute_dtype)).astype(jnp.float32)
    return logits, new_cache
