"""Serving steps: prefill + single-token decode with sharded caches.

Decode runs one new token against a KV cache of ``max_len``.  The KV cache
sequence dim is sharded over the ``model`` axis (flash-decode); recurrent
caches (mamba) shard heads.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.models import (cache_pspecs, decode_step, init_cache, init_params,
                          param_pspecs, prefill)
from repro.models.config import ModelConfig


def make_prefill_step(cfg: ModelConfig, max_len: int):
    def prefill_step(params, tokens):
        return prefill(params, tokens, cfg, max_len)
    return prefill_step


def make_decode_step(cfg: ModelConfig):
    def serve_step(params, cache, tokens):
        return decode_step(params, cache, tokens, cfg)
    return serve_step


def make_greedy_decode_step(cfg: ModelConfig):
    """One-token greedy decode with the argmax folded into the jitted body:
    (params, cache, tokens[B,1]) -> (next_tokens[B,1] int32, new cache).

    Keeping token selection on-device means the decode loop never pulls
    logits ([B,1,V] f32) back to the host — only the [B,1] int32 token ids
    cross, and only when the caller asks for them.
    """
    def greedy_step(params, cache, tokens):
        logits, cache = decode_step(params, cache, tokens, cfg)
        nxt = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32) % cfg.vocab
        return nxt, cache
    return greedy_step


def make_greedy_prefill_step(cfg: ModelConfig, max_len: int):
    """Prefill returning (first_greedy_token[B,1] int32, cache) — the
    argmax over the last-position logits folded into the jit, mirroring
    :func:`make_greedy_decode_step`."""
    def greedy_prefill(params, tokens):
        logits, cache = prefill(params, tokens, cfg, max_len)
        nxt = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32) % cfg.vocab
        return nxt, cache
    return greedy_prefill


def jit_serve(cfg: ModelConfig, max_len: int):
    """(jitted greedy prefill, jitted greedy decode) for the serve loop.

    The decode jit **donates the cache argument** (arg 1): the KV cache is
    by far the largest serve-time buffer and is dead the moment the step
    returns the updated one, so without donation every decoded token pays
    a full cache copy.  Callers must treat the passed-in cache as consumed
    (rebind to the returned one) — and must warm the jit with a throwaway
    cache first, since the warmup call eats its input too.
    """
    prefill_fn = jax.jit(make_greedy_prefill_step(cfg, max_len))
    decode_fn = jax.jit(make_greedy_decode_step(cfg), donate_argnums=(1,))
    return prefill_fn, decode_fn


def _dp_axes(mesh):
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def serve_specs(cfg: ModelConfig, mesh, batch: int, seq_len: int, *,
                decode_pos: int | None = None):
    """(params_specs, cache_specs, tokens_specs) as sharded SDS for lowering.

    For decode shapes the cache is sized/validated at ``seq_len`` (ring
    buffer of ``sliding_window`` when configured) with ``pos = decode_pos``.
    """
    from .mesh import auto_axes
    mesh = auto_axes(mesh)
    dp = _dp_axes(mesh)
    data_size = 1
    for a in dp:
        data_size *= mesh.shape[a]
    model_size = mesh.shape["model"]

    params_abs = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    pspecs = param_pspecs(cfg, params_abs, model_size)
    params_s = jax.tree.map(
        lambda l, sp: jax.ShapeDtypeStruct(l.shape, l.dtype,
                                           sharding=NamedSharding(mesh, sp)),
        params_abs, pspecs)

    cache_abs = jax.eval_shape(lambda: init_cache(cfg, batch, seq_len))
    cspecs = cache_pspecs(cfg, cache_abs, data_size, model_size,
                          data_axis=dp if len(dp) > 1 else dp[0])
    cache_s = jax.tree.map(
        lambda l, sp: jax.ShapeDtypeStruct(l.shape, l.dtype,
                                           sharding=NamedSharding(mesh, sp)),
        cache_abs, cspecs)

    tok_spec = P(dp if batch % data_size == 0 else None, None)
    tokens_s = jax.ShapeDtypeStruct((batch, 1), jnp.int32,
                                    sharding=NamedSharding(mesh, tok_spec))
    return params_s, cache_s, tokens_s
