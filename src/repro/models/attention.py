"""GQA attention: chunked (flash-style) prefill/train + cached decode.

Training/prefill uses an online-softmax formulation: the query axis is split
into statically-unrolled chunks, and for each query chunk a ``lax.scan`` runs
over only the key/value chunks at or before it — so the HLO does not pay for
the upper causal triangle (≈6% waste at q_chunk=1024, instead of 2x for the
naive full-grid approach).

Decode attends one query token against a cache whose *sequence* dimension is
sharded over the ``model`` mesh axis (flash-decode style): GSPMD turns the
softmax max/sum and the PV contraction over the sharded dim into the standard
partial-reduction collectives.  This sidesteps the ``kv_heads < model-axis``
divisibility trap (e.g. 8 KV heads on a 16-way model axis).

Sliding-window decode (``cfg.sliding_window > 0``) uses a ring-buffer cache of
``window`` slots, so the cache stays bounded at any context length.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .config import ModelConfig
from .layers import apply_rope, normal_init, rms_norm


def init_attention(key, cfg: ModelConfig, dtype, d_in: int = 0):
    """q/k/v read ``d_in`` features (``d_model`` by default); ``wo`` writes
    ``d_model``."""
    D, H, KV, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    d_in = d_in or D
    ks = jax.random.split(key, 4)
    s_in = d_in ** -0.5
    s_out = (H * hd) ** -0.5
    p = {
        "wq": normal_init(ks[0], (d_in, H * hd), s_in, dtype),
        "wk": normal_init(ks[1], (d_in, KV * hd), s_in, dtype),
        "wv": normal_init(ks[2], (d_in, KV * hd), s_in, dtype),
        "wo": normal_init(ks[3], (H * hd, D), s_out, dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.zeros((hd,), dtype)
        p["k_norm"] = jnp.zeros((hd,), dtype)
    return p


def _project_qkv(p, x, positions, cfg: ModelConfig):
    B, S, _ = x.shape
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    q = (x @ p["wq"]).reshape(B, S, H, hd)
    k = (x @ p["wk"]).reshape(B, S, KV, hd)
    v = (x @ p["wv"]).reshape(B, S, KV, hd)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def chunked_causal_attention(q, k, v, q_positions, kv_positions,
                             cfg: ModelConfig, scale: float = 0.0):
    """Online-softmax causal attention. q:[B,S,H,hd] k,v:[B,S,KV,hd].
    Scores are scaled by ``scale`` (``1/sqrt(hd)`` by default)."""
    B, S, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    scale = scale or 1.0 / math.sqrt(hd)
    Cq = min(cfg.q_chunk, S)
    if S % Cq:
        Cq = S                      # irregular lengths: one q chunk
    Ck = math.gcd(min(cfg.kv_chunk, Cq), Cq)
    nq = S // Cq
    assert S % Cq == 0 and Cq % Ck == 0, (S, Cq, Ck)

    out_chunks = []
    for qi in range(nq):                        # statically unrolled
        qc = q[:, qi * Cq:(qi + 1) * Cq]        # [B,Cq,H,hd]
        qp = q_positions[qi * Cq:(qi + 1) * Cq]
        n_kv = (qi + 1) * Cq // Ck              # only blocks at/below diagonal
        kc = k[:, :n_kv * Ck].reshape(B, n_kv, Ck, KV, hd).transpose(1, 0, 2, 3, 4)
        vc = v[:, :n_kv * Ck].reshape(B, n_kv, Ck, KV, hd).transpose(1, 0, 2, 3, 4)
        kp = kv_positions[:n_kv * Ck].reshape(n_kv, Ck)

        qg = qc.reshape(B, Cq, KV, G, hd)       # grouped-query layout (no kv repeat)

        def body(carry, xs):
            m, l, acc = carry                   # [B,KV,G,Cq], ..., [B,KV,G,Cq,hd]
            kj, vj, kpj = xs                    # [B,Ck,KV,hd], [Ck]
            s = jnp.einsum("bqkgd,bskd->bkgqs", qg, kj).astype(jnp.float32) * scale
            mask = qp[:, None] >= kpj[None, :]  # causal
            if cfg.sliding_window:
                mask &= (qp[:, None] - kpj[None, :]) < cfg.sliding_window
            s = jnp.where(mask[None, None, None], s, -1e30)
            # the running max only keeps exp() in range and the output does
            # not depend on it, so it is not differentiated.  Its derivative
            # would test which scores equal it; on the TPU XLA can take the
            # max from the scores before their bf16 rounding, so no score
            # equals it and the test's count of 0 divides into a NaN.
            m_new = jax.lax.stop_gradient(jnp.maximum(m, jnp.max(s, axis=-1)))
            pexp = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(pexp, axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bkgqs,bskd->bkgqd", pexp.astype(vj.dtype), vj).astype(jnp.float32)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, KV, G, Cq), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((B, KV, G, Cq), jnp.float32)
        a0 = jnp.zeros((B, KV, G, Cq, hd), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), (kc, vc, kp))
        o = (acc / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)
        # [B,KV,G,Cq,hd] -> [B,Cq,KV,G,hd] -> [B,Cq,H,hd]
        out_chunks.append(o.transpose(0, 3, 1, 2, 4).reshape(B, Cq, H, hd))
    return jnp.concatenate(out_chunks, axis=1)


def attention_forward(p, x, positions, cfg: ModelConfig, *, return_kv: bool = False,
                      scale: float = 0.0):
    """Train/prefill path. x:[B,S,d_in]; positions:[S]."""
    B, S, _ = x.shape
    q, k, v = _project_qkv(p, x, positions, cfg)
    o = chunked_causal_attention(q, k, v, positions, positions, cfg, scale)
    out = o.reshape(B, S, cfg.n_heads * cfg.hd) @ p["wo"]
    if return_kv:
        return out, (k, v)
    return out


def init_kv_cache(cfg: ModelConfig, batch: int, max_len: int, n_blocks: int,
                  dtype=jnp.bfloat16):
    """Stacked-over-layers KV cache. Ring buffer if sliding_window set."""
    Sc = min(max_len, cfg.sliding_window) if cfg.sliding_window else max_len
    KV, hd = cfg.n_kv_heads, cfg.hd
    shape = (n_blocks, batch, Sc, KV, hd)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def decode_attention(p, x, cache_k, cache_v, pos, cfg: ModelConfig,
                     scale: float = 0.0):
    """One-token decode. x:[B,1,d_in]; cache_[kv]:[B,Sc,KV,hd]; pos: scalar.

    Returns (out [B,1,D], new_k, new_v).  The cache sequence dim is expected
    to be sharded over the model axis; the softmax/PV reductions over it
    lower to partial-max/partial-sum collectives under GSPMD.
    """
    B, _, _ = x.shape
    Sc = cache_k.shape[1]
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    G = H // KV
    positions = jnp.full((1,), pos, jnp.int32)
    q, k, v = _project_qkv(p, x, positions, cfg)
    slot = jnp.mod(pos, Sc) if cfg.sliding_window else pos
    new_k = jax.lax.dynamic_update_slice(cache_k, k.astype(cache_k.dtype), (0, slot, 0, 0))
    new_v = jax.lax.dynamic_update_slice(cache_v, v.astype(cache_v.dtype), (0, slot, 0, 0))

    qg = q.reshape(B, 1, KV, G, hd)
    s = jnp.einsum("bqkgd,bskd->bkgqs", qg, new_k.astype(q.dtype)).astype(jnp.float32)
    s = s * scale if scale else s / math.sqrt(hd)
    valid = jnp.arange(Sc) < jnp.minimum(pos + 1, Sc)   # full + ring buffer
    s = jnp.where(valid[None, None, None, None, :], s, -1e30)
    w = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqs,bskd->bqkgd", w.astype(new_v.dtype), new_v)
    out = o.reshape(B, 1, H * hd) @ p["wo"]
    return out, new_k, new_v
