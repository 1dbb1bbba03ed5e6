"""Plain reference of Mamba2 (Dao & Gu 2024, arXiv:2405.21060), float32.

Each block is ``x + out_proj(norm(y * silu(z)))`` over the RMS-normed input,
where ``x``, ``B`` and ``C`` pass a depthwise causal convolution and a SiLU,
``dt = softplus(. + dt_bias)``, ``A = -exp(A_log)``, and the SSD is written
as its quadratic form over the whole sequence (the paper's "attention"
view, eq. 16): ``y_t = sum_{s<=t} (C_t . B_s) exp(sum_{s<r<=t} dt_r A)
dt_s x_s + D x_t``.  One group (``ngroups = 1``).  The weights are in the
layout the program stores them, the projections as separate matrices.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .common import DTYPES, ce_sum, einsum_at, rms_norm, vocab_rows


def _dims(m):
    di = m["ssm_expand"] * m["d_model"]
    return dict(D=m["d_model"], di=di, H=di // m["ssm_head_dim"],
                P=m["ssm_head_dim"], N=m["ssm_n_groups"] * m["ssm_state"],
                K=m["ssm_conv"], L=m["n_layers"], V=vocab_rows(m))


def _a_log(key, shape):
    return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))


def _dt_bias(key, shape):
    lo, hi = jnp.log(1e-3), jnp.log(1e-1)
    dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32) * (hi - lo) + lo)
    dt = jnp.maximum(dt, 1e-4)
    return dt + jnp.log(-jnp.expm1(-dt))        # softplus^-1


def layout(m: dict):
    """``(path, shape, dtype, init)`` of every weight, as the program keeps
    them: layer weights stacked on a leading layer axis."""
    d = _dims(m)
    D, di, H, N, K, L, V = (d[k] for k in ("D", "di", "H", "N", "K", "L", "V"))
    w = DTYPES[m["param_dtype"]]
    f32 = jnp.float32
    mb = ("blocks", "mamba")
    return [
        (("embed",), (V, D), w, ("normal", 1.0)),
        (("final_norm",), (D,), f32, ("zeros",)),
        (("lm_head",), (D, V), w, ("normal", D ** -0.5)),
        (("blocks", "ln"), (L, D), f32, ("zeros",)),
        (mb + ("w_z",), (L, D, di), w, ("normal", D ** -0.5)),
        (mb + ("w_x",), (L, D, di), w, ("normal", D ** -0.5)),
        (mb + ("w_B",), (L, D, N), w, ("normal", D ** -0.5)),
        (mb + ("w_C",), (L, D, N), w, ("normal", D ** -0.5)),
        (mb + ("w_dt",), (L, D, H), w, ("normal", D ** -0.5)),
        (mb + ("conv_x",), (L, K, di), w, ("normal", K ** -0.5)),
        (mb + ("conv_B",), (L, K, N), w, ("normal", K ** -0.5)),
        (mb + ("conv_C",), (L, K, N), w, ("normal", K ** -0.5)),
        (mb + ("conv_x_b",), (L, di), w, ("zeros",)),
        (mb + ("conv_B_b",), (L, N), w, ("zeros",)),
        (mb + ("conv_C_b",), (L, N), w, ("zeros",)),
        (mb + ("A_log",), (L, H), f32, _a_log),
        (mb + ("Dp",), (L, H), f32, ("ones",)),
        (mb + ("dt_bias",), (L, H), f32, _dt_bias),
        (mb + ("gate_norm",), (L, di), w, ("zeros",)),
        (mb + ("out_proj",), (L, di, D), w, ("normal", di ** -0.5)),
    ]


def _causal_conv(u, w, b):
    """Depthwise causal convolution: ``out_t = sum_i w_i u_{t-K+1+i} + b``."""
    K, S = w.shape[0], u.shape[1]
    pad = jnp.pad(u, ((0, 0), (K - 1, 0), (0, 0)))
    return sum(pad[:, i:i + S] * w[i] for i in range(K)) + b


def _block(p, x, m, ein):
    d = _dims(m)
    R, S, _ = x.shape
    H, P = d["H"], d["P"]
    mp = p["mamba"]
    h = rms_norm(x, p["ln"], m["norm_eps"])
    z = ein("rsd,de->rse", h, mp["w_z"])
    xs = jax.nn.silu(_causal_conv(ein("rsd,de->rse", h, mp["w_x"]),
                                  mp["conv_x"], mp["conv_x_b"]))
    Bm = jax.nn.silu(_causal_conv(ein("rsd,dn->rsn", h, mp["w_B"]),
                                  mp["conv_B"], mp["conv_B_b"]))
    Cm = jax.nn.silu(_causal_conv(ein("rsd,dn->rsn", h, mp["w_C"]),
                                  mp["conv_C"], mp["conv_C_b"]))
    dt = jax.nn.softplus(ein("rsd,dh->rsh", h, mp["w_dt"]) + mp["dt_bias"])
    A = -jnp.exp(mp["A_log"])
    cum = jnp.cumsum(jnp.transpose(dt * A, (0, 2, 1)), axis=-1)   # [R,H,S]
    causal = jnp.tril(jnp.ones((S, S), bool))
    seg = jnp.where(causal, cum[..., :, None] - cum[..., None, :], -jnp.inf)
    decay = jnp.exp(seg)                                         # [R,H,t,s]
    cb = ein("rtn,rsn->rts", Cm, Bm)
    mix = cb[:, None] * decay * jnp.transpose(dt, (0, 2, 1))[:, :, None, :]
    xh = xs.reshape(R, S, H, P)
    y = ein("rhts,rshp->rthp", mix, xh) + xh * mp["Dp"][:, None]
    y = y.reshape(R, S, d["di"])
    y = rms_norm(y * jax.nn.silu(z), mp["gate_norm"], m["norm_eps"])
    return x + ein("rse,ed->rsd", y, mp["out_proj"])


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
    forward's multiply-adds, recomputation not counted.  The SSD is counted
    as the chunked algorithm does its useful work at chunk ``ssm_chunk``:
    the causal half of the intra-chunk products, the chunk states and their
    read-out."""
    d = _dims(m)
    D, di, H, P, N, K = (d[k] for k in ("D", "di", "H", "P", "N", "K"))
    Q = min(m["ssm_chunk"], seq)
    proj = D * (2 * di + 2 * N + H) + di * D
    conv = K * (di + 2 * N)
    ssd = (Q / 2) * N + (Q / 2) * H * P + 2 * H * P * N
    head = D * d["V"]
    return 3 * 2 * (m["n_layers"] * (proj + conv + ssd) + head)
