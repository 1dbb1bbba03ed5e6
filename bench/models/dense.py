"""Plain reference of a dense pre-norm decoder (StableLM-2 widths), float32.

Each block is ``x + attn(norm(x))`` then ``x + mlp(norm(x))``: multi-head
causal softmax attention with rotary embeddings on the whole head (the two
halves of each head rotated as a pair), and a SiLU-gated MLP.  Attention is
written out in full, ``softmax(q k^T / sqrt(hd) + mask) v``, with no
chunking.  The weights are in the layout the program stores them.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .common import DTYPES, ce_sum, einsum_at, rms_norm, vocab_rows


def _dims(m):
    hd = m["head_dim"] or m["d_model"] // m["n_heads"]
    return dict(D=m["d_model"], H=m["n_heads"], KV=m["n_kv_heads"], hd=hd,
                F=m["d_ff"], L=m["n_layers"], V=vocab_rows(m))


def layout(m: dict):
    """``(path, shape, dtype, init)`` of every weight, as the program keeps
    them: layer weights stacked on a leading layer axis."""
    d = _dims(m)
    D, H, KV, hd, F, L, V = (d[k] for k in ("D", "H", "KV", "hd", "F", "L", "V"))
    w = DTYPES[m["param_dtype"]]
    f32 = jnp.float32
    return [
        (("embed",), (V, D), w, ("normal", 1.0)),
        (("final_norm",), (D,), f32, ("zeros",)),
        (("lm_head",), (D, V), w, ("normal", D ** -0.5)),
        (("blocks", "ln1"), (L, D), f32, ("zeros",)),
        (("blocks", "ln2"), (L, D), f32, ("zeros",)),
        (("blocks", "attn", "wq"), (L, D, H * hd), w, ("normal", D ** -0.5)),
        (("blocks", "attn", "wk"), (L, D, KV * hd), w, ("normal", D ** -0.5)),
        (("blocks", "attn", "wv"), (L, D, KV * hd), w, ("normal", D ** -0.5)),
        (("blocks", "attn", "wo"), (L, H * hd, D), w,
         ("normal", (H * hd) ** -0.5)),
        (("blocks", "mlp", "w_gate"), (L, D, F), w, ("normal", D ** -0.5)),
        (("blocks", "mlp", "w_up"), (L, D, F), w, ("normal", D ** -0.5)),
        (("blocks", "mlp", "w_down"), (L, F, D), w, ("normal", F ** -0.5)),
    ]


def _rope(x, theta):
    """Rotary embedding of ``x [R, S, heads, hd]`` at positions 0..S-1."""
    S, hd = x.shape[1], x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs      # [S, hd/2]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _block(p, x, m, ein):
    d = _dims(m)
    R, S, _ = x.shape
    H, KV, hd = d["H"], d["KV"], d["hd"]
    a = p["attn"]
    h = rms_norm(x, p["ln1"], m["norm_eps"])
    q = _rope(ein("rsd,de->rse", h, a["wq"]).reshape(R, S, H, hd),
              m["rope_theta"])
    k = _rope(ein("rsd,de->rse", h, a["wk"]).reshape(R, S, KV, hd),
              m["rope_theta"])
    v = ein("rsd,de->rse", h, a["wv"]).reshape(R, S, KV, hd)
    k = jnp.repeat(k, H // KV, axis=2)
    v = jnp.repeat(v, H // KV, axis=2)
    s = ein("rqhd,rkhd->rhqk", q, k) / jnp.sqrt(float(hd))
    causal = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(causal, s, -jnp.inf)
    o = ein("rhqk,rkhd->rqhd", jax.nn.softmax(s, axis=-1), v)
    x = x + ein("rse,ed->rsd", o.reshape(R, S, H * hd), a["wo"])
    mlp = p["mlp"]
    h = rms_norm(x, p["ln2"], m["norm_eps"])
    g = jax.nn.silu(ein("rsd,df->rsf", h, mlp["w_gate"]))
    u = ein("rsd,df->rsf", h, mlp["w_up"])
    return x + ein("rsf,fd->rsd", g * u, mlp["w_down"])


def loss_sum(params, batch, m: dict, precision: str = "f32"):
    """Summed next-token cross entropy of ``batch`` under float32
    ``params`` (the program's layout)."""
    ein = einsum_at(precision)
    x = params["embed"][batch["tokens"]]
    block = jax.checkpoint(lambda p, x: _block(p, x, m, ein))
    x, _ = jax.lax.scan(lambda x, p: (block(p, x), None), x, params["blocks"])
    x = rms_norm(x, params["final_norm"], m["norm_eps"])
    return ce_sum(ein("rsd,dv->rsv", x, params["lm_head"]), batch["targets"])


def flops_per_token(m: dict, seq: int) -> float:
    """Forward plus backward model FLOPs per trained token: three times the
    forward's multiply-adds, recomputation not counted.  Attention counts
    the causal half of its score and value products."""
    d = _dims(m)
    D, H, KV, hd, F = (d[k] for k in ("D", "H", "KV", "hd", "F"))
    proj = D * (H + 2 * KV) * hd + H * hd * D
    attn = 2 * (seq / 2) * H * hd
    mlp = 3 * D * F
    return 3 * 2 * (m["n_layers"] * (proj + attn + mlp) + D * d["V"])
