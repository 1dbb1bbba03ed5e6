"""What the plain references share: matmuls at a stated precision, the
norm and the loss, and weights made from the seed in the program's layout.

The reference computes in float32 with every matmul at ``HIGHEST``.  The
control computes the same function in float8, the precision step below the
configuration's bfloat16: every matmul takes its operands and gives its
result in float8 (e4m3 forward; e5m2 for the backward pass's cotangents and
the gradients it gives), each tensor scaled by its own absolute maximum,
with float32 accumulation inside the matmul, as the program computes in
bfloat16 with float32 accumulation.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


def _to_fp8(x, dtype):
    """``x`` rounded to ``dtype`` under a per-tensor scale that maps its
    largest magnitude to the format's largest finite value (clipped there:
    float8 has no room above it)."""
    top = float(jnp.finfo(dtype).max)
    s = jax.lax.stop_gradient(jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top)
    return jnp.clip(x / s, -top, top).astype(dtype).astype(jnp.float32) * s


def _einsum(eq, a, b):
    return jnp.einsum(eq, a, b, precision=HIGHEST,
                      preferred_element_type=jnp.float32)


E4M3, E5M2 = jnp.float8_e4m3fn, jnp.float8_e5m2


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _einsum_fp8(eq, a, b):
    return _to_fp8(_einsum(eq, _to_fp8(a, E4M3), _to_fp8(b, E4M3)), E4M3)


def _einsum_fp8_fwd(eq, a, b):
    qa, qb = _to_fp8(a, E4M3), _to_fp8(b, E4M3)
    return _to_fp8(_einsum(eq, qa, qb), E4M3), (qa, qb)


def _einsum_fp8_bwd(eq, res, g):
    qa, qb = res
    _, vjp = jax.vjp(functools.partial(_einsum, eq), qa, qb)
    return tuple(_to_fp8(x, E5M2) for x in vjp(_to_fp8(g, E5M2)))


_einsum_fp8.defvjp(_einsum_fp8_fwd, _einsum_fp8_bwd)


def einsum_at(precision: str):
    """``einsum(eq, a, b)`` in float32 (``"f32"``) or float8 (``"fp8"``)."""
    if precision == "f32":
        return _einsum
    if precision == "fp8":
        return _einsum_fp8
    raise ValueError(f"unknown reference precision {precision!r}")


def rms_norm(x, w, eps):
    """RMS norm with the scale stored as ``1 + w`` (zero-initialised)."""
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * (1.0 + w)


def ce_sum(logits, targets):
    """Summed next-token cross entropy over ``[rows, seq, vocab]`` logits."""
    lse = jax.nn.logsumexp(logits, axis=-1)
    tgt = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return jnp.sum(lse - tgt)


def vocab_rows(model: dict) -> int:
    """Rows of the embedding and columns of the head: the vocabulary padded
    to a multiple of 16, as the published Mamba2 and the program pad it."""
    return -(-model["vocab"] // 16) * 16


def make_weights(key, layout):
    """Weights of ``layout`` from ``key``: a list of ``(path, shape, dtype,
    init)`` where ``init`` is ``("normal", std)``, ``("zeros",)``,
    ``("ones",)`` or a callable ``(key, shape) -> float32 array``.  Returns
    the nested dict the program's parameters use.  Call under ``jax.jit``.
    """
    out = {}
    for i, (path, shape, dtype, init) in enumerate(layout):
        k = jax.random.fold_in(key, i)
        if callable(init):
            v = init(k, shape)
        elif init[0] == "normal":
            v = init[1] * jax.random.normal(k, shape, jnp.float32)
        elif init[0] == "zeros":
            v = jnp.zeros(shape, jnp.float32)
        elif init[0] == "ones":
            v = jnp.ones(shape, jnp.float32)
        else:
            raise ValueError(init)
        node = out
        for p in path[:-1]:
            node = node.setdefault(p, {})
        node[path[-1]] = v.astype(dtype)
    return out
