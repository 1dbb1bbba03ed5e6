"""Plain reference of Zamba2 (Glorioso et al. 2024, arXiv:2411.15242), float32.

The layer equations are those of ``transformers``' ``Zamba2ForCausalLM``.
Every layer is a Mamba2 layer ``x + mixer(norm(x + t))``.  ``t`` is 0, but at
the hybrid layers (``hybrid_layers``) it is the output of a shared block fed
with the embedding output ``e``, with no residual of its own:

    h = norm(cat(x, e))                      (width 2 * d_model)
    a = attn(h)       causal softmax attention, rotary embedding on the
                      whole head (halves paired), scores scaled by
                      (head_dim / 2) ** -0.5
    h = norm(a)
    t = linear_j(down(gelu(g) * u)),  g, u = h @ gate, h @ up, each plus
                      invocation j's adapter (h @ adapter_in) @ adapter_*

``num_mem_blocks`` shared blocks alternate over the invocations
(``j % num_mem_blocks``); the adapters and linears are one per invocation.
The mixer is as in ``mamba2.py``, with ``ssm_n_groups`` groups: each head
reads its group's B and C, and the gated norm normalises each group's
channels.  The SSD is written as its quadratic form over the whole
sequence, a few heads at a time so that it fits; attention is written out
in full.  The weights are in the layout the program stores them.

A tensor-parallel share (``ssm_n_heads``, ``n_heads``, ``d_ff`` below the
published widths) is computed as its chip computes it, without the
exchanges: attention's and the MLP's outputs are that chip's partial sums,
and the gated norm covers the channels held.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .common import DTYPES, ce_sum, einsum_at, rms_norm, vocab_rows
from .dense import _rope
from .mamba2 import _a_log, _causal_conv, _dt_bias

# heads of one pass of the quadratic SSD: [rows, 4, seq, seq] f32 temporaries
HEADS_PER_PASS = 4


def _dims(m):
    P = m["ssm_head_dim"]
    Hs = m.get("ssm_n_heads") or m["ssm_expand"] * m["d_model"] // P
    G = m["ssm_n_groups"]
    return dict(D=m["d_model"], H=m["n_heads"], KV=m["n_kv_heads"],
                hd=m["head_dim"], F=m["d_ff"], L=m["n_layers"],
                V=vocab_rows(m), Hs=Hs, P=P, di=Hs * P, G=G,
                N=m["ssm_state"], K=m["ssm_conv"], M=m["num_mem_blocks"],
                J=len(m["hybrid_layers"]), r=m["adapter_rank"])


def layout(m: dict):
    """``(path, shape, dtype, init)`` of every weight, as the program keeps
    them: Mamba layers stacked on a leading layer axis, shared blocks on a
    leading block axis, adapters and linears on a leading invocation axis."""
    d = _dims(m)
    D, H, KV, hd, F, L, V = (d[k] for k in ("D", "H", "KV", "hd", "F", "L", "V"))
    Hs, di, GN, K, M, J, r = (d["Hs"], d["di"], d["G"] * d["N"], d["K"], d["M"],
                              d["J"], d["r"])
    w = DTYPES[m["param_dtype"]]
    f32 = jnp.float32
    mb, sb, at = ("blocks", "mamba"), ("shared",), ("shared", "attn")
    return [
        (("embed",), (V, D), w, ("normal", 1.0)),
        (("final_norm",), (D,), f32, ("zeros",)),
        (("lm_head",), (D, V), w, ("normal", D ** -0.5)),
        (("blocks", "ln"), (L, D), f32, ("zeros",)),
        (mb + ("w_z",), (L, D, di), w, ("normal", D ** -0.5)),
        (mb + ("w_x",), (L, D, di), w, ("normal", D ** -0.5)),
        (mb + ("w_B",), (L, D, GN), w, ("normal", D ** -0.5)),
        (mb + ("w_C",), (L, D, GN), w, ("normal", D ** -0.5)),
        (mb + ("w_dt",), (L, D, Hs), w, ("normal", D ** -0.5)),
        (mb + ("conv_x",), (L, K, di), w, ("normal", K ** -0.5)),
        (mb + ("conv_B",), (L, K, GN), w, ("normal", K ** -0.5)),
        (mb + ("conv_C",), (L, K, GN), w, ("normal", K ** -0.5)),
        (mb + ("conv_x_b",), (L, di), w, ("zeros",)),
        (mb + ("conv_B_b",), (L, GN), w, ("zeros",)),
        (mb + ("conv_C_b",), (L, GN), w, ("zeros",)),
        (mb + ("A_log",), (L, Hs), f32, _a_log),
        (mb + ("Dp",), (L, Hs), f32, ("ones",)),
        (mb + ("dt_bias",), (L, Hs), f32, _dt_bias),
        (mb + ("gate_norm",), (L, di), w, ("zeros",)),
        (mb + ("out_proj",), (L, di, D), w, ("normal", di ** -0.5)),
        (sb + ("ln_in",), (M, 2 * D), f32, ("zeros",)),
        (at + ("wq",), (M, 2 * D, H * hd), w, ("normal", (2 * D) ** -0.5)),
        (at + ("wk",), (M, 2 * D, KV * hd), w, ("normal", (2 * D) ** -0.5)),
        (at + ("wv",), (M, 2 * D, KV * hd), w, ("normal", (2 * D) ** -0.5)),
        (at + ("wo",), (M, H * hd, D), w, ("normal", (H * hd) ** -0.5)),
        (sb + ("ln_mlp",), (M, D), f32, ("zeros",)),
        (sb + ("mlp", "w_gate"), (M, D, F), w, ("normal", D ** -0.5)),
        (sb + ("mlp", "w_up"), (M, D, F), w, ("normal", D ** -0.5)),
        (sb + ("mlp", "w_down"), (M, F, D), w, ("normal", F ** -0.5)),
        (("hybrid", "adapter_in"), (J, D, r), w, ("normal", D ** -0.5)),
        (("hybrid", "adapter_gate"), (J, r, F), w, ("normal", r ** -0.5)),
        (("hybrid", "adapter_up"), (J, r, F), w, ("normal", r ** -0.5)),
        (("hybrid", "linear"), (J, D, D), w, ("normal", D ** -0.5)),
    ]


def _ssd_heads(cb, dt, A, x, ein):
    """``y_t = sum_{s<=t} cb_ts exp(sum_{s<r<=t} dt_r A) dt_s x_s`` for a few
    heads: ``cb [R,t,s]`` their group's ``C_t . B_s``, ``dt [R,S,h]``,
    ``A [h]``, ``x [R,S,h,P]``."""
    S = x.shape[1]
    cum = jnp.cumsum(jnp.transpose(dt * A, (0, 2, 1)), axis=-1)     # [R,h,S]
    causal = jnp.tril(jnp.ones((S, S), bool))
    seg = jnp.where(causal, cum[..., :, None] - cum[..., None, :], -jnp.inf)
    mix = cb[:, None] * jnp.exp(seg) * jnp.transpose(dt, (0, 2, 1))[:, :, None, :]
    return ein("rhts,rshp->rthp", mix, x)


def _mixer(mp, h, m, ein):
    """The Mamba2 mixer on the normed input ``h``, through ``out_proj``."""
    d = _dims(m)
    R, S, _ = h.shape
    Hs, P, G, N = d["Hs"], d["P"], d["G"], d["N"]
    z = ein("rsd,de->rse", h, mp["w_z"])
    xs = jax.nn.silu(_causal_conv(ein("rsd,de->rse", h, mp["w_x"]),
                                  mp["conv_x"], mp["conv_x_b"]))
    Bm = jax.nn.silu(_causal_conv(ein("rsd,dn->rsn", h, mp["w_B"]),
                                  mp["conv_B"], mp["conv_B_b"]))
    Cm = jax.nn.silu(_causal_conv(ein("rsd,dn->rsn", h, mp["w_C"]),
                                  mp["conv_C"], mp["conv_C_b"]))
    dt = jax.nn.softplus(ein("rsd,dh->rsh", h, mp["w_dt"]) + mp["dt_bias"])
    A = -jnp.exp(mp["A_log"])
    xh = xs.reshape(R, S, Hs, P)
    heads = jax.checkpoint(lambda cb, dt, A, x: _ssd_heads(cb, dt, A, x, ein))
    Hg, ys = Hs // G, []
    for g in range(G):
        cb = ein("rtn,rsn->rts", Cm[..., g * N:(g + 1) * N],
                 Bm[..., g * N:(g + 1) * N])
        for lo in range(g * Hg, (g + 1) * Hg, HEADS_PER_PASS):
            hi = min(lo + HEADS_PER_PASS, (g + 1) * Hg)
            ys.append(heads(cb, dt[..., lo:hi], A[lo:hi], xh[:, :, lo:hi]))
    y = jnp.concatenate(ys, axis=2) + xh * mp["Dp"][:, None]
    y = (y.reshape(R, S, G, -1)
         * jax.nn.silu(z).reshape(R, S, G, -1))                  # per group
    y = rms_norm(y, mp["gate_norm"].reshape(G, -1), m["norm_eps"])
    return ein("rse,ed->rsd", y.reshape(R, S, d["di"]), mp["out_proj"])


def _attention(ap, h, m, ein):
    d = _dims(m)
    R, S, _ = h.shape
    H, KV, hd = d["H"], d["KV"], d["hd"]
    q = _rope(ein("rsd,de->rse", h, ap["wq"]).reshape(R, S, H, hd),
              m["rope_theta"])
    k = _rope(ein("rsd,de->rse", h, ap["wk"]).reshape(R, S, KV, hd),
              m["rope_theta"])
    v = ein("rsd,de->rse", h, ap["wv"]).reshape(R, S, KV, hd)
    k = jnp.repeat(k, H // KV, axis=2)
    v = jnp.repeat(v, H // KV, axis=2)
    s = ein("rqhd,rkhd->rhqk", q, k) * (hd / 2) ** -0.5
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
    o = ein("rhqk,rkhd->rqhd", jax.nn.softmax(s, axis=-1), v)
    return ein("rse,ed->rsd", o.reshape(R, S, H * hd), ap["wo"])


def _shared(sp, hp, x, e, m, ein):
    """The shared block's output ``t`` at one hybrid layer."""
    eps = m["norm_eps"]
    h = rms_norm(jnp.concatenate([x, e], axis=-1), sp["ln_in"], eps)
    h = rms_norm(_attention(sp["attn"], h, m, ein), sp["ln_mlp"], eps)
    mlp = sp["mlp"]
    a = ein("rsd,dk->rsk", h, hp["adapter_in"])
    g = ein("rsd,df->rsf", h, mlp["w_gate"]) + ein("rsk,kf->rsf", a, hp["adapter_gate"])
    u = ein("rsd,df->rsf", h, mlp["w_up"]) + ein("rsk,kf->rsf", a, hp["adapter_up"])
    y = ein("rsf,fd->rsd", jax.nn.gelu(g, approximate=False) * u, mlp["w_down"])
    return ein("rsd,de->rse", y, hp["linear"])


def _at(tree, i):
    return jax.tree.map(lambda a: a[i], tree)


def hidden(params, tokens, m: dict, precision: str = "f32"):
    """The final norm's output ``[rows, seq, d_model]``."""
    ein = einsum_at(precision)
    eps = m["norm_eps"]
    x = params["embed"][tokens]
    e = x
    layer = jax.checkpoint(lambda bp, x, t: x + _mixer(
        bp["mamba"], rms_norm(x + t, bp["ln"], eps), m, ein))
    shared = jax.checkpoint(lambda sp, hp, x, e: _shared(sp, hp, x, e, m, ein))
    hybrid = list(m["hybrid_layers"])
    for i in range(m["n_layers"]):
        t = jnp.zeros((), x.dtype)
        if i in hybrid:
            j = hybrid.index(i)
            t = shared(_at(params["shared"], j % m["num_mem_blocks"]),
                       _at(params["hybrid"], j), x, e)
        x = layer(_at(params["blocks"], i), x, t)
    return rms_norm(x, params["final_norm"], eps)


def loss_sum(params, batch, m: dict, precision: str = "f32"):
    """Summed next-token cross entropy of ``batch`` under float32
    ``params`` (the program's layout)."""
    ein = einsum_at(precision)
    x = hidden(params, batch["tokens"], m, precision)
    return ce_sum(ein("rsd,dv->rsv", x, params["lm_head"]), batch["targets"])


def flops_per_token(m: dict, seq: int) -> float:
    """Forward plus backward model FLOPs per trained token: three times the
    forward's multiply-adds, recomputation not counted.  The SSD as the
    chunked algorithm does its useful work at chunk ``ssm_chunk`` (the
    causal half of each group's ``C . B`` and of the heads' products, the
    chunk states and their read-out); attention the causal half of its
    score and value products; each shared-block invocation its projections,
    adapter and linear."""
    d = _dims(m)
    D, H, KV, hd, F, r = (d[k] for k in ("D", "H", "KV", "hd", "F", "r"))
    Hs, P, G, N, K, di = (d[k] for k in ("Hs", "P", "G", "N", "K", "di"))
    Q = min(m["ssm_chunk"], seq)
    proj = D * (2 * di + 2 * G * N + Hs) + di * D
    conv = K * (di + 2 * G * N)
    ssd = (Q / 2) * G * N + (Q / 2) * Hs * P + 2 * Hs * P * N
    attn = 2 * D * (H + 2 * KV) * hd + H * hd * D + 2 * (seq / 2) * H * hd
    block = attn + 3 * D * F + D * r + 2 * r * F + D * D
    return 3 * 2 * (d["L"] * (proj + conv + ssd) + d["J"] * block
                    + D * d["V"])
