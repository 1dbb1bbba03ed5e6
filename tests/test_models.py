"""Per-architecture smoke tests (reduced same-family variants) + numerics:
chunked attention vs naive softmax, SSD scan vs naive recurrence, MoE
capacity path vs dense reference, prefill/decode consistency, and
LAQ-train-step integration smokes (dense/mamba2/moe on the 8-device mesh
with exact wire-bit accounting)."""
import json
import math
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config, smoke_config
from repro.models import (decode_step, forward, init_params,
                          lm_loss, n_params, prefill)
from repro.models.attention import chunked_causal_attention
from repro.models.config import ModelConfig
from repro.models.mamba2 import ssd_chunked
from repro.models.moe import init_moe, moe_forward_capacity, moe_forward_dense


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_arch_smoke_train_step(arch):
    """One forward + one SGD train step on the reduced config: shapes + no NaN."""
    cfg = smoke_config(get_config(arch))
    key = jax.random.PRNGKey(0)
    params = init_params(key, cfg)
    assert sum(l.size for l in jax.tree.leaves(params)) == n_params(cfg)
    B, S = 2, 64
    tokens = jax.random.randint(key, (B, S), 0, cfg.vocab)
    batch = {"tokens": tokens, "targets": jnp.roll(tokens, -1, 1)}
    logits, aux = forward(params, tokens, cfg)
    assert logits.shape == (B, S, cfg.padded_vocab())
    assert bool(jnp.isfinite(logits).all()), f"{arch}: non-finite logits"
    loss, grads = jax.value_and_grad(lm_loss)(params, batch, cfg)
    assert bool(jnp.isfinite(loss))
    gn = sum(float(jnp.sum(jnp.abs(g.astype(jnp.float32)))) for g in jax.tree.leaves(grads))
    assert math.isfinite(gn) and gn > 0, f"{arch}: bad grads"
    new = jax.tree.map(lambda p, g: p - 0.01 * g.astype(p.dtype), params, grads)
    loss2 = lm_loss(new, batch, cfg)
    assert bool(jnp.isfinite(loss2))


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_arch_smoke_serve(arch):
    """Prefill then 3 decode steps; last-prompt-token logits must match the
    training forward exactly."""
    cfg = smoke_config(get_config(arch))
    key = jax.random.PRNGKey(1)
    params = init_params(key, cfg)
    B, S = 2, 32
    tokens = jax.random.randint(key, (B, S), 0, cfg.vocab)
    full_logits, _ = forward(params, tokens, cfg)
    last, cache = prefill(params, tokens, cfg, max_len=S + 8)
    np.testing.assert_allclose(np.asarray(last[:, 0]), np.asarray(full_logits[:, -1]),
                               atol=2e-2, rtol=1e-2)
    for i in range(3):
        nxt = jnp.argmax(last[:, -1:], -1).astype(jnp.int32)
        nxt = jnp.clip(nxt, 0, cfg.vocab - 1)
        last, cache = decode_step(params, cache, nxt, cfg)
        assert bool(jnp.isfinite(last).all())
    assert int(cache["pos"]) == S + 3


def test_decode_equals_teacher_forcing():
    """Decode logits at position t must match the full forward at t."""
    cfg = smoke_config(get_config("stablelm-1.6b"))
    key = jax.random.PRNGKey(2)
    params = init_params(key, cfg)
    B, S = 1, 24
    tokens = jax.random.randint(key, (B, S), 0, cfg.vocab)
    full_logits, _ = forward(params, tokens, cfg)
    _, cache = prefill(params, tokens[:, :8], cfg, max_len=S)
    for t in range(8, S):
        logits, cache = decode_step(params, cache, tokens[:, t:t + 1], cfg)
        if t < S - 1:
            np.testing.assert_allclose(np.asarray(logits[:, 0]),
                                       np.asarray(full_logits[:, t]),
                                       atol=3e-2, rtol=2e-2)


def test_chunked_attention_matches_naive():
    cfg = ModelConfig(name="t", arch_type="dense", n_layers=1, d_model=64,
                      vocab=64, n_heads=4, n_kv_heads=2, head_dim=16,
                      d_ff=64, q_chunk=16, kv_chunk=8)
    key = jax.random.PRNGKey(0)
    B, S, H, KV, hd = 2, 64, 4, 2, 16
    q = jax.random.normal(key, (B, S, H, hd), jnp.float32)
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, S, KV, hd), jnp.float32)
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, S, KV, hd), jnp.float32)
    pos = jnp.arange(S)
    out = chunked_causal_attention(q, k, v, pos, pos, cfg)
    # naive reference
    kk = jnp.repeat(k, H // KV, axis=2)
    vv = jnp.repeat(v, H // KV, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, kk) / np.sqrt(hd)
    mask = pos[:, None] >= pos[None, :]
    s = jnp.where(mask[None, None], s, -1e30)
    ref = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), vv)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_sliding_window_attention():
    cfg = ModelConfig(name="t", arch_type="dense", n_layers=1, d_model=64,
                      vocab=64, n_heads=2, n_kv_heads=2, head_dim=16,
                      d_ff=64, q_chunk=16, kv_chunk=8, sliding_window=8)
    key = jax.random.PRNGKey(0)
    B, S, H, hd = 1, 32, 2, 16
    q = jax.random.normal(key, (B, S, H, hd))
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, S, H, hd))
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, S, H, hd))
    pos = jnp.arange(S)
    out = chunked_causal_attention(q, k, v, pos, pos, cfg)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(hd)
    mask = (pos[:, None] >= pos[None, :]) & (pos[:, None] - pos[None, :] < 8)
    s = jnp.where(mask[None, None], s, -1e30)
    ref = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_ssd_chunked_matches_naive_recurrence():
    key = jax.random.PRNGKey(0)
    B, S, H, P, N = 2, 32, 3, 8, 5
    ks = jax.random.split(key, 4)
    x = jax.random.normal(ks[0], (B, S, H, P), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H))) * 0.5
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    Bm = jax.random.normal(ks[3], (B, S, N), jnp.float32)
    Cm = jax.random.normal(jax.random.fold_in(key, 9), (B, S, N), jnp.float32)
    y, state = ssd_chunked(x, dt, A, Bm, Cm, chunk=8)

    # naive per-step recurrence
    s = np.zeros((B, H, N, P))
    ys = np.zeros((B, S, H, P))
    xn, dtn, An, Bn, Cn = map(np.asarray, (x, dt, A, Bm, Cm))
    for t in range(S):
        dA = np.exp(dtn[:, t] * An)                       # [B,H]
        outer = np.einsum("bh,bn,bhp->bhnp", dtn[:, t], Bn[:, t], xn[:, t])
        s = s * dA[..., None, None] + outer
        ys[:, t] = np.einsum("bn,bhnp->bhp", Cn[:, t], s)
    np.testing.assert_allclose(np.asarray(y), ys, atol=1e-3, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(state), np.transpose(s, (0, 1, 3, 2)),
                               atol=1e-3, rtol=1e-3)


def test_moe_capacity_matches_dense_when_uncapped():
    """With capacity_factor large enough for zero drops the capacity path must
    equal the dense all-experts reference."""
    cfg = ModelConfig(name="t", arch_type="moe", n_layers=1, d_model=32,
                      vocab=64, n_heads=2, n_kv_heads=2, head_dim=16,
                      n_experts=4, top_k=2, moe_d_ff=16,
                      capacity_factor=4.0, param_dtype=jnp.float32,
                      compute_dtype=jnp.float32)
    key = jax.random.PRNGKey(0)
    p = init_moe(key, cfg, jnp.float32)
    x = jax.random.normal(jax.random.fold_in(key, 1), (2, 16, 32), jnp.float32)
    yc, aux_c = moe_forward_capacity(p, x, cfg)
    yd, aux_d = moe_forward_dense(p, x, cfg)
    np.testing.assert_allclose(np.asarray(yc), np.asarray(yd), atol=1e-4)
    np.testing.assert_allclose(float(aux_c), float(aux_d), rtol=1e-5)


def test_moe_scatter_combine_matches_gather():
    import dataclasses
    cfg = ModelConfig(name="t", arch_type="moe", n_layers=1, d_model=32,
                      vocab=64, n_heads=2, n_kv_heads=2, head_dim=16,
                      n_experts=4, top_k=2, moe_d_ff=16,
                      capacity_factor=4.0, param_dtype=jnp.float32,
                      compute_dtype=jnp.float32)
    key = jax.random.PRNGKey(3)
    p = init_moe(key, cfg, jnp.float32)
    x = jax.random.normal(jax.random.fold_in(key, 1), (2, 16, 32))
    yg, _ = moe_forward_capacity(p, x, cfg)
    ys, _ = moe_forward_capacity(
        p, x, dataclasses.replace(cfg, moe_combine="scatter"))
    np.testing.assert_allclose(np.asarray(yg), np.asarray(ys), atol=1e-5)


def test_moe_capacity_drops_overflow():
    """With capacity_factor << 1 the output must stay finite (drops are zeros)."""
    cfg = ModelConfig(name="t", arch_type="moe", n_layers=1, d_model=32,
                      vocab=64, n_experts=4, top_k=2, moe_d_ff=16,
                      n_heads=2, n_kv_heads=2, capacity_factor=0.25,
                      param_dtype=jnp.float32, compute_dtype=jnp.float32)
    key = jax.random.PRNGKey(0)
    p = init_moe(key, cfg, jnp.float32)
    x = jax.random.normal(jax.random.fold_in(key, 1), (2, 16, 32))
    yc, _ = moe_forward_capacity(p, x, cfg)
    assert bool(jnp.isfinite(yc).all())


def test_moe_router_aux_flows_through_accumulated_gradient():
    """The router's load-balance aux loss must reach the router weights
    through the gradient-accumulation fold (core/engine.py
    accumulate_loss_grads) — an aux-only objective folded over microbatches
    yields nonzero router gradients."""
    from repro.core.engine import accumulate_loss_grads
    from repro.models.model import AUX_LOSS_WEIGHT

    cfg = ModelConfig(name="t", arch_type="moe", n_layers=1, d_model=32,
                      vocab=64, n_heads=2, n_kv_heads=2, head_dim=16,
                      n_experts=4, top_k=2, moe_d_ff=16, q_chunk=16,
                      kv_chunk=8, param_dtype=jnp.float32,
                      compute_dtype=jnp.float32)
    params = init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 2, 16), 0, cfg.vocab)
    mbs = {"tokens": tokens, "targets": jnp.roll(tokens, -1, -1)}

    def aux_only(p, b):
        _, aux = forward(p, b["tokens"], cfg)
        return AUX_LOSS_WEIGHT * aux

    loss, grads = accumulate_loss_grads(aux_only, params, mbs)
    assert bool(jnp.isfinite(loss)) and float(loss) > 0
    paths, _ = jax.tree_util.tree_flatten_with_path(grads)
    router = [leaf for path, leaf in paths
              if "router" in jax.tree_util.keystr(path)]
    assert router, "no router leaves in the gradient tree"
    assert max(float(jnp.max(jnp.abs(g))) for g in router) > 0.0, \
        "aux loss did not reach the router through the accumulation fold"
    # the full LM objective (ce + aux) stays finite through the same fold
    full, _ = accumulate_loss_grads(lambda p, b: lm_loss(p, b, cfg),
                                    params, mbs)
    assert bool(jnp.isfinite(full))


_LAQ_ARCH_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import get_config, smoke_config
from repro.core.strategy import StrategyConfig
from repro.optim import sgd
from repro.launch.train import (make_train_step, train_state_specs,
                                init_train_state)
from repro.data import synthetic_lm_batch

out = {}
strategy = StrategyConfig(kind="laq", bits=4, per_leaf_radius=True)
opt = sgd()
mesh = jax.make_mesh((4, 2), ("data", "model"))
wa = ("data",)
# moe runs with microbatch=2: the sharded step folds the round's gradient
# (aux loss included) through accumulate_loss_grads
for arch, accum in (("yi-6b", 1), ("mamba2-130m", 1),
                    ("qwen3-moe-30b-a3b", 2)):
    cfg = smoke_config(get_config(arch))
    state = init_train_state(jax.random.PRNGKey(0), cfg, mesh, strategy,
                             opt, wa)
    specs = train_state_specs(cfg, mesh, strategy, opt, wa)
    state = jax.tree.map(lambda x, s: jax.device_put(x, s.sharding),
                         state, specs)
    batch = synthetic_lm_batch(jax.random.PRNGKey(1), 8, 64, cfg.vocab)
    batch = jax.device_put(batch, NamedSharding(mesh, P("data", None)))
    step = jax.jit(make_train_step(cfg, mesh, strategy, opt, lr=1e-2,
                                   worker_axes=wa, wire="float",
                                   microbatch=accum))
    state, m = step(state, batch)
    out[arch] = {
        "loss": float(m.loss),
        "uploads": int(m.uploads),
        "total_bits": float(state.comm.total_bits),
        "p": int(sum(x.size for x in jax.tree.leaves(state.params))),
        "n_leaves": len(jax.tree.leaves(state.params)),
    }
print("RESULT " + json.dumps(out))
"""


@pytest.mark.slow
def test_laq_step_arch_smokes_subprocess():
    """One LAQ round per architecture family (dense / mamba2 / moe) on the
    (4 data x 2 model) 8-device mesh: loss finite, and the wire accounting
    is exact against the hand-computed first-round cohort — all W workers
    upload (first_round_upload), each paying upload_bits(p, 4,
    n_radii=n_leaves) since per_leaf_radius exchanges one f32 radius per
    leaf."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, PYTHONPATH=os.path.join(repo, "src"))
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", _LAQ_ARCH_SCRIPT], env=env,
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-4000:]
    line = [l for l in r.stdout.splitlines() if l.startswith("RESULT ")][-1]
    out = json.loads(line[len("RESULT "):])
    W = 4
    for arch, o in out.items():
        assert math.isfinite(o["loss"]), (arch, o)
        assert o["uploads"] == W, (arch, o)
        expected = W * (32 * o["n_leaves"] + 4 * o["p"])
        assert o["total_bits"] == float(expected), (arch, o, expected)

