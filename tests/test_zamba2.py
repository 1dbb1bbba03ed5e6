"""Zamba2's hybrid block in the program (``models/stack.py``) against the
plain reference ``bench/models/zamba2.py``, the reference against
``transformers``' ``Zamba2ForCausalLM``, and a tensor-parallel share
against the uncut layer.

(a) The program's loss and gradients match the reference on seeded random
    weights, both in float32.
(b) The reference at a tiny uncut size (2 shared blocks, 2 groups, the
    first 12 layers of the published pattern) gives ``transformers``'
    logits from the same weights.
(c) The shares add up to the uncut layer: attention's 4 head shares after
    ``o_proj``, the adapted MLP's 4 column shares after ``down``, the
    mixer's 2 group shares after ``out_proj``.
(d) ``n_params`` counts the layout.
(e) One round of the sharded LAQ step on the packed wire over two workers.
"""
import dataclasses
import json
import math
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "bench")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from models import zamba2 as ref  # noqa: E402  (bench/models/zamba2.py)
from models.common import make_weights  # noqa: E402

from repro.models import lm_loss, n_params  # noqa: E402
from repro.models.attention import attention_forward  # noqa: E402
from repro.models.config import ModelConfig  # noqa: E402
from repro.models.layers import gelu_adapted_mlp  # noqa: E402
from repro.models.mamba2 import mamba2_forward  # noqa: E402

# a stage of the hybrid at small widths: held Mamba heads set apart from
# expand * d_model, two hybrid layers alternating over two shared blocks
SMALL = dict(name="zamba2-small", arch_type="hybrid", n_layers=4, d_model=64,
             vocab=96, n_heads=4, n_kv_heads=4, head_dim=32, d_ff=96,
             ssm_state=16, ssm_head_dim=16, ssm_expand=2, ssm_conv=4,
             ssm_n_groups=1, ssm_n_heads=6, ssm_chunk=16, hybrid_layers=[1, 3],
             num_mem_blocks=2, adapter_rank=8, rope_theta=10000.0,
             norm_eps=1e-5, q_chunk=32, kv_chunk=16, param_dtype="float32",
             compute_dtype="float32", remat=True)


def program_config(m):
    fields = {f.name for f in dataclasses.fields(ModelConfig)}
    kw = {k: v for k, v in m.items() if k in fields}
    kw.update(param_dtype=jnp.dtype(m["param_dtype"]),
              compute_dtype=jnp.dtype(m["compute_dtype"]))
    return ModelConfig(**kw)


def weights(m, seed=0):
    return jax.jit(lambda k: make_weights(k, ref.layout(m)))(
        jax.random.PRNGKey(seed))


def batch(m, rows=2, seq=64, seed=1):
    s = jax.random.randint(jax.random.PRNGKey(seed), (rows, seq + 1), 0, m["vocab"])
    return {"tokens": s[:, :-1], "targets": s[:, 1:]}


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


# float32 against float32: the chunked SSD and online softmax reorder sums,
# so the program reads a loss gap of ~1e-7 and gradient gaps up to 4e-6
# here.  Rounding the weights to bfloat16 alone moves the loss by 1e-4 and
# the gradients by 3e-2 (checked below), so a bfloat16 reference fails both
# tolerances.
LOSS_TOL, GRAD_TOL = 2e-5, 1e-4


@pytest.mark.parametrize("groups", [1, 2])
def test_program_matches_reference(groups):
    """At one group, as the cell's chip holds, and at two, as the published
    mixer runs (each group's three heads read that group's B and C)."""
    m = dict(SMALL, ssm_n_groups=groups)
    cfg = program_config(m)
    params, b = weights(m), batch(m)
    n = b["tokens"].size
    with jax.default_matmul_precision("highest"):
        reference = jax.jit(jax.value_and_grad(lambda p: ref.loss_sum(p, b, m) / n))
        l_ref, g_ref = reference(params)
        l_prog, g_prog = jax.jit(jax.value_and_grad(
            lambda p: lm_loss(p, b, cfg)))(params)
        # the same reference on weights rounded to bfloat16
        p16 = jax.tree.map(lambda x: x.astype(jnp.bfloat16).astype(jnp.float32),
                           params)
        l16, g16 = reference(p16)
    loss_gap = abs(float(l_prog) - float(l_ref)) / float(l_ref)
    assert loss_gap < LOSS_TOL, loss_gap
    grads = jax.tree.map(rel, g_prog, g_ref)
    assert max(jax.tree.leaves(grads)) < GRAD_TOL, grads
    # every leaf learns, the shared block's and the adapters' included
    assert min(float(jnp.linalg.norm(g)) for g in jax.tree.leaves(g_ref)) > 0
    # the tolerances are tight enough to refuse a bfloat16 reference
    assert abs(float(l16) - float(l_prog)) / float(l_prog) > LOSS_TOL
    assert max(jax.tree.leaves(jax.tree.map(rel, g16, g_prog))) > GRAD_TOL


# ---------------------------------------------------------------------------
# (b) the reference against transformers' Zamba2
# ---------------------------------------------------------------------------

PATTERN = ["mamba"] * 6 + ["hybrid"] + ["mamba"] * 4 + ["hybrid"]   # layers 0-11
# One SSD chunk (ssm_chunk >= the sequence): transformers' CPU path sums its
# inter-chunk recurrence over the wrong axis (``result = (decay_chunk[...,
# None, None] * states_permuted[:, :, None, ...]).sum(dim=2)``, the target
# chunk where the source is meant), which is exact only for one chunk.  The
# program's chunked scan is held to the quadratic form across chunks in (a).
TINY = dict(n_layers=12, d_model=64, vocab=96, n_heads=4, n_kv_heads=4,
            head_dim=32, d_ff=96, ssm_state=16, ssm_head_dim=16, ssm_expand=2,
            ssm_conv=4, ssm_n_groups=2, ssm_n_heads=0, ssm_chunk=64,
            hybrid_layers=[i for i, t in enumerate(PATTERN) if t == "hybrid"],
            num_mem_blocks=2, adapter_rank=8, rope_theta=10000.0,
            norm_eps=1e-5, param_dtype="float32")


def _hf_model(m, params):
    torch = pytest.importorskip("torch")
    tr = pytest.importorskip("transformers")
    D = m["d_model"]
    config = tr.Zamba2Config(
        vocab_size=m["vocab"], hidden_size=D, num_hidden_layers=m["n_layers"],
        layers_block_type=PATTERN, mamba_d_state=m["ssm_state"],
        mamba_d_conv=m["ssm_conv"], mamba_expand=m["ssm_expand"],
        mamba_ngroups=m["ssm_n_groups"],
        n_mamba_heads=m["ssm_expand"] * D // m["ssm_head_dim"],
        chunk_size=m["ssm_chunk"], intermediate_size=m["d_ff"],
        num_attention_heads=m["n_heads"], num_mem_blocks=m["num_mem_blocks"],
        use_shared_attention_adapter=False, adapter_rank=m["adapter_rank"],
        use_mem_rope=True, rope_theta=m["rope_theta"],
        rms_norm_eps=m["norm_eps"], attn_implementation="eager",
        tie_word_embeddings=False)      # the program's head is untied
    model = tr.Zamba2ForCausalLM(config).eval()
    p = jax.tree.map(lambda x: torch.tensor(np.asarray(x, np.float32)), params)

    def put(param, value):
        assert param.shape == value.shape, (param.shape, value.shape)
        param.data.copy_(value)

    one = lambda w: 1.0 + w                     # noqa: E731 (the (1 + w) scale)
    put(model.model.embed_tokens.weight, p["embed"])
    put(model.lm_head.weight, p["lm_head"].T)
    put(model.model.final_layernorm.weight, one(p["final_norm"]))
    hybrid = list(m["hybrid_layers"])
    for i, layer in enumerate(model.model.layers):
        mamba = layer.mamba_decoder if i in hybrid else layer
        mp = jax.tree.map(lambda a: a[i], p["blocks"]["mamba"])
        put(mamba.input_layernorm.weight, one(p["blocks"]["ln"][i]))
        mx = mamba.mamba
        # in_proj: [gate (z), x, B, C (the convolved channels), dt]
        put(mx.in_proj.weight, torch.cat([mp["w_z"].T, mp["w_x"].T, mp["w_B"].T,
                                          mp["w_C"].T, mp["w_dt"].T]))
        conv = torch.cat([mp["conv_x"], mp["conv_B"], mp["conv_C"]], dim=1)
        put(mx.conv1d.weight, conv.T[:, None, :])
        put(mx.conv1d.bias, torch.cat([mp["conv_x_b"], mp["conv_B_b"],
                                       mp["conv_C_b"]]))
        put(mx.dt_bias, mp["dt_bias"])
        put(mx.A_log, mp["A_log"])
        put(mx.D, mp["Dp"])
        put(mx.norm.weight, one(mp["gate_norm"]))
        put(mx.out_proj.weight, mp["out_proj"].T)
        # transformers' CPU path clamps dt at time_step_min; the published
        # kernels, and the program, do not
        mx.time_step_min = 0.0
        if i in hybrid:
            j = hybrid.index(i)
            k = j % m["num_mem_blocks"]
            sp = jax.tree.map(lambda a: a[k], p["shared"])
            hp = jax.tree.map(lambda a: a[j], p["hybrid"])
            blk = layer.shared_transformer
            assert blk.block_id == k
            put(blk.input_layernorm.weight, one(sp["ln_in"]))
            put(blk.self_attn.q_proj.weight, sp["attn"]["wq"].T)
            put(blk.self_attn.k_proj.weight, sp["attn"]["wk"].T)
            put(blk.self_attn.v_proj.weight, sp["attn"]["wv"].T)
            put(blk.self_attn.o_proj.weight, sp["attn"]["wo"].T)
            put(blk.pre_ff_layernorm.weight, one(sp["ln_mlp"]))
            ff = blk.feed_forward
            put(ff.gate_up_proj.weight, torch.cat([sp["mlp"]["w_gate"].T,
                                                   sp["mlp"]["w_up"].T]))
            put(ff.down_proj.weight, sp["mlp"]["w_down"].T)
            adapter = ff.gate_up_proj_adapter_list[j]
            put(adapter[0].weight, hp["adapter_in"].T)
            put(adapter[1].weight, torch.cat([hp["adapter_gate"].T,
                                              hp["adapter_up"].T]))
            put(layer.linear.weight, hp["linear"].T)
    return model, torch


def test_reference_matches_transformers():
    m = TINY
    params = weights(m, seed=3)
    tokens = batch(m, rows=2, seq=48, seed=4)["tokens"]
    model, torch = _hf_model(m, params)
    with torch.no_grad():
        want = model(input_ids=torch.tensor(np.asarray(tokens)),
                     use_cache=False).logits.numpy()
    with jax.default_matmul_precision("highest"):
        got = jax.jit(lambda p, t: jnp.einsum(
            "rsd,dv->rsv", ref.hidden(p, t, m), p["lm_head"]))(params, tokens)
    # float32 on both sides; the chunked scan in transformers and the
    # quadratic form here order their sums differently (~1e-6 relative)
    assert rel(got, want) < 1e-4, rel(got, want)


# ---------------------------------------------------------------------------
# (c) shares of a tensor-parallel rank add up to the uncut layer
# ---------------------------------------------------------------------------

def test_shares_sum_to_the_uncut_layer():
    """The program's layer on each share, summed after its output
    projection, against the reference's uncut layer.  A 4-rank mixer holds
    half a group, whose gated norm then covers the channels held: the
    pair's all-reduce of the mean square is left out (the cell's departure
    ``gated_norm``), so the mixer is split here by its 2 groups, where each
    share holds a whole group."""
    m = dict(SMALL, n_layers=1, hybrid_layers=[0], num_mem_blocks=1,
             ssm_n_groups=2, ssm_n_heads=8)
    params = weights(m, seed=5)
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 32, m["d_model"]))
    e = jax.random.normal(jax.random.PRNGKey(7), (2, 32, m["d_model"]))
    h2 = jnp.concatenate([x, e], -1)
    sp = jax.tree.map(lambda a: a[0], params["shared"])
    hp = jax.tree.map(lambda a: a[0], params["hybrid"])
    mp = jax.tree.map(lambda a: a[0], params["blocks"]["mamba"])
    ein = ref.einsum_at("f32")
    R = 4
    with jax.default_matmul_precision("highest"):
        # attention: 4 ranks of heads
        want = ref._attention(sp["attn"], h2, m, ein)
        hd, Hr = m["head_dim"], m["n_heads"] // R
        cfg = program_config(dict(m, n_heads=Hr, n_kv_heads=Hr))
        got = 0
        for r in range(R):
            cols = slice(r * Hr * hd, (r + 1) * Hr * hd)
            a = {"wq": sp["attn"]["wq"][:, cols], "wk": sp["attn"]["wk"][:, cols],
                 "wv": sp["attn"]["wv"][:, cols], "wo": sp["attn"]["wo"][cols]}
            got = got + attention_forward(a, h2, jnp.arange(32), cfg,
                                          scale=(hd / 2) ** -0.5)
        assert rel(got, want) < 1e-5, rel(got, want)

        # the adapted MLP: 4 ranks of columns, the adapter's first matrix whole
        mlp = sp["mlp"]
        want = ein("rsf,fd->rsd", jax.nn.gelu(
            ein("rsd,df->rsf", x, mlp["w_gate"])
            + ein("rsk,kf->rsf", ein("rsd,dk->rsk", x, hp["adapter_in"]),
                  hp["adapter_gate"]), approximate=False)
            * (ein("rsd,df->rsf", x, mlp["w_up"])
               + ein("rsk,kf->rsf", ein("rsd,dk->rsk", x, hp["adapter_in"]),
                     hp["adapter_up"])), mlp["w_down"])
        Fr = m["d_ff"] // R
        got = 0
        for r in range(R):
            c = slice(r * Fr, (r + 1) * Fr)
            got = got + gelu_adapted_mlp(
                x, mlp["w_gate"][:, c], mlp["w_up"][:, c], mlp["w_down"][c],
                hp["adapter_in"], hp["adapter_gate"][:, c], hp["adapter_up"][:, c])
        assert rel(got, want) < 1e-5, rel(got, want)

        # the mixer: 2 group shares, each one group of heads
        want = ref._mixer(mp, x, m, ein)
        G, N, P = 2, m["ssm_state"], m["ssm_head_dim"]
        Hg = m["ssm_n_heads"] // G
        cfg = program_config(dict(m, ssm_n_groups=1, ssm_n_heads=Hg))
        got = 0
        for g in range(G):
            ch = slice(g * Hg * P, (g + 1) * Hg * P)
            hs = slice(g * Hg, (g + 1) * Hg)
            gn = slice(g * N, (g + 1) * N)
            share = {"w_z": mp["w_z"][:, ch], "w_x": mp["w_x"][:, ch],
                     "w_B": mp["w_B"][:, gn], "w_C": mp["w_C"][:, gn],
                     "w_dt": mp["w_dt"][:, hs], "conv_x": mp["conv_x"][:, ch],
                     "conv_B": mp["conv_B"][:, gn], "conv_C": mp["conv_C"][:, gn],
                     "conv_x_b": mp["conv_x_b"][ch], "conv_B_b": mp["conv_B_b"][gn],
                     "conv_C_b": mp["conv_C_b"][gn], "A_log": mp["A_log"][hs],
                     "Dp": mp["Dp"][hs], "dt_bias": mp["dt_bias"][hs],
                     "gate_norm": mp["gate_norm"][ch],
                     "out_proj": mp["out_proj"][ch]}
            got = got + mamba2_forward(share, x, cfg)[0]
        assert rel(got, want) < 1e-5, rel(got, want)


# ---------------------------------------------------------------------------
# (d) parameter counts
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("which", ["small", "cell"])
def test_n_params_counts_the_layout(which):
    if which == "small":
        m = SMALL
    else:
        with open(os.path.join(BENCH, "configs", "zamba2-7b.tp4.l6.json")) as f:
            m = json.load(f)
    count = sum(math.prod(s) for _, s, _, _ in ref.layout(m))
    assert n_params(program_config(m)) == count
    if which == "cell":
        # 6 x 19,841,748 Mamba layers, a shared block of 83,503,616, the
        # invocation's adapter and linear 14,221,312, embedding and head
        # 2 x 28,672,000, the final norm 3,584
        assert count == 274_123_000
    else:
        from repro.models import init_params
        p = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0),
                                               program_config(m)))
        assert sum(x.size for x in jax.tree.leaves(p)) == count


# ---------------------------------------------------------------------------
# (e) one round of the sharded LAQ step
# ---------------------------------------------------------------------------

_STEP_SCRIPT = r"""
import json, os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
sys.path.insert(0, os.path.join(sys.argv[1], "bench"))
sys.path.insert(0, os.path.join(sys.argv[1], "tests"))
import scopes
from test_zamba2 import SMALL, program_config
from repro.core.strategy import StrategyConfig
from repro.launch.mesh import device_mesh
from repro.launch.train import (init_train_state, make_train_step,
                                train_state_specs)
from repro.optim import adamw

cfg = program_config(dict(SMALL, param_dtype="bfloat16",
                          compute_dtype="bfloat16"))
mesh = device_mesh(n_devices=2)
strategy = StrategyConfig(kind="laq", bits=4, per_leaf_radius=True,
                          wire_backend="fused")
opt = adamw()
step = make_train_step(cfg, mesh, strategy, opt, lr=1e-3,
                       worker_axes=("data",), wire="packed", microbatch=2)
specs = train_state_specs(cfg, mesh, strategy, opt, ("data",))
state = init_train_state(jax.random.PRNGKey(0), cfg, mesh, strategy, opt,
                         ("data",))
state = jax.tree.map(lambda x, s: jax.device_put(x, s.sharding), state, specs)
s = jax.random.randint(jax.random.PRNGKey(1), (8, 65), 0, cfg.vocab)
batch = jax.device_put({"tokens": s[:, :-1], "targets": s[:, 1:]},
                       NamedSharding(mesh, P("data", None)))
compiled = jax.jit(step).lower(state, batch).compile()
state, m = compiled(state, batch)
smap = scopes.scope_map(compiled.as_text())
print(json.dumps(dict(
    loss=float(m.loss), uploads=int(m.uploads), bits=float(m.bits),
    leaves=len(jax.tree.leaves(state.params)),
    p=sum(x.size for x in jax.tree.leaves(state.params)),
    shared_block=step.shared_block, layout=step.wire_layout,
    spans=sorted({v for v in smap.values() if v}),
    mu=[float(jnp.linalg.norm(x)) for x in jax.tree.leaves(state.opt_state.mu)])))
"""


def test_sharded_round_on_the_packed_wire():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(REPO, "src") + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", _STEP_SCRIPT, REPO], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-4000:]
    o = json.loads(r.stdout.strip().splitlines()[-1])
    W = 2
    assert math.isfinite(o["loss"]) and o["uploads"] == W, o
    # every worker uploads in round 1: 4 bits a parameter and a radius a leaf
    assert o["bits"] == W * (32 * o["leaves"] + 4 * o["p"]), o
    assert o["layout"] == {"flat": [o["leaves"], o["p"]], "axis": [0, 0],
                           "quantize_passes": 1}, o
    assert o["shared_block"] == dict(
        heads=4, kv_heads=4, mlp_columns=96, ssm_heads=6, ssm_groups=1,
        invocations=2, blocks=2,
        params=128 + 3 * 128 * 128 + 128 * 64 + 64 + 3 * 64 * 96), o
    assert {"laq.shared_block", "laq.fwd_bwd"} <= set(o["spans"])
    # the aggregate reached every leaf (no leaf's gradient lost on the wire)
    assert min(o["mu"]) > 0, o["mu"]


def test_decode_follows_the_forward():
    """Prefill then decode through both hybrid layers' KV caches and the
    Mamba states: the logits at each position are the training forward's."""
    from repro.configs import get_config, smoke_config
    from repro.models import decode_step, forward, init_params, prefill
    cfg = dataclasses.replace(smoke_config(get_config("zamba2-7b")),
                              param_dtype=jnp.float32, compute_dtype=jnp.float32)
    params = init_params(jax.random.PRNGKey(2), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(3), (1, 16), 0, cfg.vocab)
    with jax.default_matmul_precision("highest"):
        full, _ = forward(params, tokens, cfg)
        _, cache = prefill(params, tokens[:, :8], cfg, max_len=16)
        for t in range(8, 15):
            logits, cache = decode_step(params, cache, tokens[:, t:t + 1], cfg)
            # float32: the chunked forward and the recurrent decode differ
            # by their order of summation alone
            np.testing.assert_allclose(np.asarray(logits[:, 0]),
                                       np.asarray(full[:, t]), atol=1e-4,
                                       rtol=1e-4)
