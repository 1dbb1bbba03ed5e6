"""Mamba2 / SSD (state-space duality) block — chunked scan + O(1) decode.

Implements the SSD algorithm of Dao & Gu 2024 (arXiv:2405.21060): the
sequence is split into chunks; within a chunk the recurrence is evaluated as
a (masked, decay-weighted) attention-like quadratic form; across chunks a
linear ``lax.scan`` carries the [H, P, N] SSM state.  Decode is a single
recurrent state update — O(1) in context length.

Projection weights are kept as *separate* tensors per stream (z / x / B / C /
dt) rather than one fused ``in_proj`` so each can carry its own sharding
(the fused layout would interleave model-sharded and replicated segments in
one matrix).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .config import ModelConfig
from .layers import normal_init, rms_norm


def init_mamba2(key, cfg: ModelConfig, dtype):
    D = cfg.d_model
    di, H, N, G, K = cfg.d_inner, cfg.ssm_heads, cfg.ssm_state, cfg.ssm_n_groups, cfg.ssm_conv
    ks = jax.random.split(key, 9)
    s = D ** -0.5
    dt = jnp.exp(jax.random.uniform(ks[7], (H,)) * (jnp.log(0.1) - jnp.log(0.001))
                 + jnp.log(0.001))
    dt_bias = dt + jnp.log(-jnp.expm1(-dt))   # inverse softplus
    return {
        "w_z": normal_init(ks[0], (D, di), s, dtype),
        "w_x": normal_init(ks[1], (D, di), s, dtype),
        "w_B": normal_init(ks[2], (D, G * N), s, dtype),
        "w_C": normal_init(ks[3], (D, G * N), s, dtype),
        "w_dt": normal_init(ks[4], (D, H), s, dtype),
        "conv_x": normal_init(ks[5], (K, di), K ** -0.5, dtype),
        "conv_B": normal_init(ks[6], (K, G * N), K ** -0.5, dtype),
        "conv_C": normal_init(ks[8], (K, G * N), K ** -0.5, dtype),
        "conv_x_b": jnp.zeros((di,), dtype),
        "conv_B_b": jnp.zeros((G * N,), dtype),
        "conv_C_b": jnp.zeros((G * N,), dtype),
        "A_log": jnp.log(jnp.arange(1, H + 1, dtype=jnp.float32)),
        "Dp": jnp.ones((H,), jnp.float32),
        "dt_bias": dt_bias.astype(jnp.float32),
        "gate_norm": jnp.zeros((di,), dtype),
        "out_proj": normal_init(jax.random.fold_in(key, 99), (di, D), di ** -0.5, dtype),
    }


def _causal_conv(u, w, b):
    """Depthwise causal conv. u:[B,S,C], w:[K,C] -> [B,S,C]."""
    K = w.shape[0]
    pad = jnp.pad(u, ((0, 0), (K - 1, 0), (0, 0)))
    out = sum(pad[:, i:i + u.shape[1]] * w[i] for i in range(K))
    return out + b


def ssd_chunked(x, dt, A, Bm, Cm, chunk: int):
    """SSD scan of one group. x:[B,S,H,P] dt:[B,S,H] A:[H] Bm,Cm:[B,S,N].

    Returns y:[B,S,H,P] and the final state [B,H,P,N].
    """
    Bsz, S, H, P = x.shape
    N = Bm.shape[-1]
    Q = min(chunk, S)
    assert S % Q == 0
    nc = S // Q

    xc = x.reshape(Bsz, nc, Q, H, P)
    dtc = dt.reshape(Bsz, nc, Q, H)
    Bc = Bm.reshape(Bsz, nc, Q, N)
    Cc = Cm.reshape(Bsz, nc, Q, N)

    a = dtc * A                                    # [B,nc,Q,H] log-decay per step
    cum_a = jnp.cumsum(a, axis=2)
    seg_a = cum_a[:, :, -1:]                        # total chunk decay [B,nc,1,H]

    # ---- intra-chunk (quadratic, attention-like) ----
    CB = jnp.einsum("bcin,bcjn->bcij", Cc, Bc)      # [B,nc,Q,Q]
    # clamp: above-diagonal (i<j) exponents are positive and would inf/NaN
    # through the masking where() in the backward pass.
    dlog = jnp.minimum(cum_a[:, :, :, None, :] - cum_a[:, :, None, :, :], 0.0)
    decay = jnp.exp(dlog)                           # [B,nc,i,j,H]
    ii, jj = jnp.meshgrid(jnp.arange(Q), jnp.arange(Q), indexing="ij")
    mask = (ii >= jj)[None, None, :, :, None]
    att = jnp.where(mask, CB[..., None] * decay * dtc[:, :, None, :, :], 0.0)
    y_intra = jnp.einsum("bcijh,bcjhp->bcihp", att.astype(x.dtype), xc)

    # ---- chunk summary states ----
    # S_c = sum_j exp(seg_a - cum_a_j) * dt_j * B_j (x) x_j   -> [B,nc,H,N,P]
    w_j = jnp.exp(seg_a - cum_a) * dtc                          # [B,nc,Q,H]
    states = jnp.einsum("bcjh,bcjn,bcjhp->bchnp",
                        w_j.astype(x.dtype), Bc.astype(x.dtype), xc)

    # ---- inter-chunk recurrence over nc ----
    seg_decay = jnp.exp(seg_a[:, :, 0, :])                      # [B,nc,H]

    def scan_fn(R, xs):
        st, dec = xs                                            # [B,H,N,P], [B,H]
        R_new = R * dec[..., None, None] + st.astype(jnp.float32)
        return R_new, R                                         # emit state ENTERING chunk

    R0 = jnp.zeros((Bsz, H, N, P), jnp.float32)
    Rfinal, R_in = jax.lax.scan(
        scan_fn,
        R0,
        (states.transpose(1, 0, 2, 3, 4), seg_decay.transpose(1, 0, 2)),
    )
    R_in = R_in.transpose(1, 0, 2, 3, 4)                        # [B,nc,H,N,P]

    # ---- inter-chunk contribution ----
    y_inter = jnp.einsum("bcin,bchnp,bcih->bcihp",
                         Cc.astype(jnp.float32), R_in, jnp.exp(cum_a))
    y = (y_intra.astype(jnp.float32) + y_inter).reshape(Bsz, S, H, P)
    # final state: [B,H,P,N] layout for the decode cache
    return y.astype(x.dtype), Rfinal.transpose(0, 1, 3, 2)


def _gated_norm(y, z, w, cfg: ModelConfig):
    """RMS norm of ``y * silu(z)`` over each group's ``d_inner / G``
    channels."""
    G = cfg.ssm_n_groups
    h = (y * jax.nn.silu(z)).reshape(y.shape[:-1] + (G, -1))
    return rms_norm(h, w.reshape(G, -1), cfg.norm_eps).reshape(y.shape)


def mamba2_forward(p, x, cfg: ModelConfig, chunk: int = 0):
    """Train/prefill path. x:[B,S,D] -> ([B,S,D], final_state, conv_tail)."""
    chunk = chunk or cfg.ssm_chunk
    B, S, D = x.shape
    H, P, N = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
    z = x @ p["w_z"]
    xin = _causal_conv(x @ p["w_x"], p["conv_x"], p["conv_x_b"])
    Bm = _causal_conv(x @ p["w_B"], p["conv_B"], p["conv_B_b"])
    Cm = _causal_conv(x @ p["w_C"], p["conv_C"], p["conv_C_b"])
    xin, Bm, Cm = jax.nn.silu(xin), jax.nn.silu(Bm), jax.nn.silu(Cm)
    dt = jax.nn.softplus((x @ p["w_dt"]).astype(jnp.float32) + p["dt_bias"])
    A = -jnp.exp(p["A_log"])
    # each group's heads read that group's B and C
    Hg, xh = H // cfg.ssm_n_groups, xin.reshape(B, S, H, P)
    parts = [ssd_chunked(xh[:, :, g * Hg:(g + 1) * Hg],
                         dt[..., g * Hg:(g + 1) * Hg], A[g * Hg:(g + 1) * Hg],
                         Bm[..., g * N:(g + 1) * N], Cm[..., g * N:(g + 1) * N],
                         chunk) for g in range(cfg.ssm_n_groups)]
    y = jnp.concatenate([y_ for y_, _ in parts], axis=2)
    state = jnp.concatenate([s_ for _, s_ in parts], axis=1)
    y = y + xh * p["Dp"][:, None].astype(x.dtype)
    y = y.reshape(B, S, cfg.d_inner)
    y = _gated_norm(y, z, p["gate_norm"], cfg)
    out = y @ p["out_proj"]
    # conv tail: last K-1 *pre-conv* projected inputs, for decode continuation
    K = cfg.ssm_conv
    tail = {
        "x": (x @ p["w_x"])[:, -(K - 1):],
        "B": (x @ p["w_B"])[:, -(K - 1):],
        "C": (x @ p["w_C"])[:, -(K - 1):],
    }
    return out, state, tail


def init_mamba_cache(cfg: ModelConfig, batch: int, n_blocks: int, dtype=jnp.float32):
    H, P, N, K = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state, cfg.ssm_conv
    return {
        "ssm": jnp.zeros((n_blocks, batch, H, P, N), jnp.float32),
        "conv_x": jnp.zeros((n_blocks, batch, K - 1, cfg.d_inner), dtype),
        "conv_B": jnp.zeros((n_blocks, batch, K - 1, cfg.ssm_n_groups * cfg.ssm_state), dtype),
        "conv_C": jnp.zeros((n_blocks, batch, K - 1, cfg.ssm_n_groups * cfg.ssm_state), dtype),
    }


def _conv_step(tail, new, w, b):
    """tail:[B,K-1,C], new:[B,1,C] -> (out [B,C], new_tail)."""
    window = jnp.concatenate([tail, new.astype(tail.dtype)], axis=1)   # [B,K,C]
    out = jnp.einsum("bkc,kc->bc", window, w) + b
    return out, window[:, 1:]


def mamba2_decode(p, x, cache, cfg: ModelConfig):
    """One-token recurrent step. x:[B,1,D]; cache: one block's slice."""
    B = x.shape[0]
    H, P, N = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
    z = (x @ p["w_z"])[:, 0]
    xin_new = x @ p["w_x"]
    B_new = x @ p["w_B"]
    C_new = x @ p["w_C"]
    xin, tail_x = _conv_step(cache["conv_x"], xin_new, p["conv_x"], p["conv_x_b"])
    Bm, tail_B = _conv_step(cache["conv_B"], B_new, p["conv_B"], p["conv_B_b"])
    Cm, tail_C = _conv_step(cache["conv_C"], C_new, p["conv_C"], p["conv_C_b"])
    xin, Bm, Cm = jax.nn.silu(xin), jax.nn.silu(Bm), jax.nn.silu(Cm)
    dt = jax.nn.softplus((x @ p["w_dt"])[:, 0].astype(jnp.float32) + p["dt_bias"])  # [B,H]
    A = -jnp.exp(p["A_log"])
    dA = jnp.exp(dt * A)                                        # [B,H]
    xh = xin.reshape(B, H, P).astype(jnp.float32)
    # each head reads its group's B and C
    G = cfg.ssm_n_groups
    Bh = jnp.repeat(Bm.reshape(B, G, N), H // G, axis=1).astype(jnp.float32)
    Ch = jnp.repeat(Cm.reshape(B, G, N), H // G, axis=1).astype(jnp.float32)
    s = cache["ssm"] * dA[..., None, None] + \
        jnp.einsum("bh,bhp,bhn->bhpn", dt, xh, Bh)
    y = jnp.einsum("bhpn,bhn->bhp", s, Ch)                      # [B,H,P]
    y = y + xh * p["Dp"][:, None]
    y = y.reshape(B, cfg.d_inner).astype(x.dtype)
    y = _gated_norm(y, z, p["gate_norm"], cfg)
    out = (y @ p["out_proj"])[:, None]
    new_cache = {"ssm": s, "conv_x": tail_x, "conv_B": tail_B, "conv_C": tail_C}
    return out, new_cache
