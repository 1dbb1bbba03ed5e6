"""Shared layers: norms, rotary embeddings, SwiGLU and GELU-gated MLPs,
initializers."""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def maybe_constrain(x, *spec):
    """with_sharding_constraint guarded on an ambient mesh having the axes."""
    try:
        mesh = jax.sharding.get_abstract_mesh()
        names = getattr(mesh, "axis_names", ()) or ()
        for s in spec:
            if s is not None and s not in names:
                return x
        return jax.lax.with_sharding_constraint(x, P(*spec))
    except Exception:
        return x


def normal_init(key, shape, scale, dtype):
    return (scale * jax.random.normal(key, shape)).astype(dtype)


def rms_norm(x, weight, eps: float):
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps)
    return (x * (1.0 + weight.astype(jnp.float32))).astype(dt)


def rope_freqs(head_dim: int, theta: float):
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x, positions, theta: float):
    """x: [..., S, H, hd]; positions: [..., S] (broadcastable)."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                     # [hd/2]
    ang = positions[..., None].astype(jnp.float32) * freqs   # [..., S, hd/2]
    cos = jnp.cos(ang)[..., None, :]                  # [..., S, 1, hd/2]
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def swiglu(x, w_gate, w_up, w_down):
    h = jax.nn.silu(x @ w_gate) * (x @ w_up)
    return h @ w_down


def gelu_adapted_mlp(x, w_gate, w_up, w_down, adapter_in, adapter_gate,
                     adapter_up):
    """Zamba2's shared MLP: ``gelu(g) * u`` (erf GELU), where ``g`` and ``u``
    each add a low-rank adapter's share, ``(x @ adapter_in) @ adapter_*``, to
    the shared projection."""
    a = x @ adapter_in
    g = x @ w_gate + a @ adapter_gate
    u = x @ w_up + a @ adapter_up
    return (jax.nn.gelu(g, approximate=False) * u) @ w_down


def init_mlp(key, d_model: int, d_ff: int, dtype):
    k1, k2, k3 = jax.random.split(key, 3)
    s_in = d_model ** -0.5
    s_out = d_ff ** -0.5
    return {
        "w_gate": normal_init(k1, (d_model, d_ff), s_in, dtype),
        "w_up": normal_init(k2, (d_model, d_ff), s_in, dtype),
        "w_down": normal_init(k3, (d_ff, d_model), s_out, dtype),
    }
