"""End-to-end system tests.

1. Mini paper reproduction: multinomial logistic regression (strongly convex)
   on the synthetic MNIST-like mixture, M=10 workers — validates the paper's
   Table-2 ordering (bits: LAQ < QGD < GD, LAQ < LAG; rounds: lazy << dense)
   and equal final accuracy.
2. Sharded integration (subprocess with 8 forced host devices): LAQ train
   step on a (4 data x 2 model) mesh — loss decreases, packed wire is
   bit-identical to float wire on both wire backends (the step runs the
   fused backend it was asked for — pinned via ``step.wire_backend``), the
   adaptive fused pass-2 matches the reference adaptive trajectory,
   decode/prefill lower and compile, and the multi-pod (2,2,2)
   hierarchical mode runs.
"""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import CriterionConfig, StrategyConfig, run_gradient_based
from repro.data import classification_dataset, split_workers

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _logreg_setup(M=10):
    X, Y = classification_dataset(jax.random.PRNGKey(0), n_per_class=40)
    Xw, Yw = split_workers(X, Y, M)
    N = X.shape[0]
    lam = 0.01

    def loss_fn(params, data):
        x, y = data
        logits = x @ params["w"].T
        ce = -jnp.sum(y * jax.nn.log_softmax(logits, -1))
        return (ce + 0.5 * lam * jnp.sum(params["w"] ** 2)) / N

    params0 = {"w": jnp.zeros((10, 784))}
    return loss_fn, params0, (Xw, Yw), (X, Y)


def _accuracy(params, X, Y):
    pred = jnp.argmax(X @ params["w"].T, -1)
    return float(jnp.mean((pred == jnp.argmax(Y, -1)).astype(jnp.float32)))


def test_paper_repro_gradient_based_ordering():
    loss_fn, p0, workers, full = _logreg_setup()
    crit = CriterionConfig(D=10, xi=0.8 / 10, t_bar=100)
    out = {}
    for kind in ("gd", "qgd", "lag", "laq"):
        cfg = StrategyConfig(kind=kind, bits=4, criterion=crit)
        out[kind] = run_gradient_based(loss_fn, p0, workers, cfg,
                                       steps=300, alpha=2.0)
    accs = {k: _accuracy(r.params, *full) for k, r in out.items()}
    bits = {k: float(r.cum_bits[-1]) for k, r in out.items()}
    rounds = {k: int(r.cum_uploads[-1]) for k, r in out.items()}
    # Table 2 qualitative claims
    assert bits["laq"] < bits["lag"], (bits)
    assert bits["laq"] < bits["qgd"] < bits["gd"], (bits)
    assert rounds["laq"] < 0.5 * rounds["qgd"], (rounds)
    assert rounds["lag"] < 0.5 * rounds["gd"], (rounds)
    # same accuracy across methods (paper: identical accuracy column)
    assert max(accs.values()) - min(accs.values()) < 0.02, accs
    # linear convergence of the loss residual for LAQ (Theorem 1)
    resid = np.asarray(out["laq"].loss) - float(out["gd"].loss[-1]) + 1e-12
    y = np.log(np.maximum(resid[10:250], 1e-12))
    slope = np.polyfit(np.arange(y.size), y, 1)[0]
    assert slope < -0.005, slope


_SHARDED_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import get_config, smoke_config
from repro.core.adaptive import BitSchedule
from repro.core.strategy import StrategyConfig
from repro.optim import sgd
from repro.launch.train import (make_train_step, train_state_specs,
                                init_train_state)
from repro.launch.serve import serve_specs, make_decode_step
from repro.data import synthetic_lm_batch

out = {}
cfg = smoke_config(get_config("stablelm-1.6b"))
strategy = StrategyConfig(kind="laq", bits=4, per_leaf_radius=True)
opt = sgd()

# --- single-pod flat mode -------------------------------------------------
mesh = jax.make_mesh((4, 2), ("data", "model"))
wa = ("data",)
batch = synthetic_lm_batch(jax.random.PRNGKey(1), 8, 64, cfg.vocab)
batch = jax.device_put(batch, NamedSharding(mesh, P("data", None)))

def fresh(strat=strategy):
    s = init_train_state(jax.random.PRNGKey(0), cfg, mesh, strat, opt, wa)
    sp = train_state_specs(cfg, mesh, strat, opt, wa)
    return jax.tree.map(lambda x, spc: jax.device_put(x, spc.sharding), s, sp)

def max_param_diff(a, b):
    return max(jax.tree.leaves(jax.tree.map(lambda x, y: float(jnp.max(jnp.abs(
        x.astype(jnp.float32) - y.astype(jnp.float32)))), a.params, b.params)))

losses = []
state = fresh()
jstep = jax.jit(make_train_step(cfg, mesh, strategy, opt, lr=1e-2,
                                worker_axes=wa, wire="float"))
for _ in range(6):
    state, m = jstep(state, batch)
    losses.append(float(m.loss))
out["losses"] = losses

s1, s2 = fresh(), fresh()
jp = jax.jit(make_train_step(cfg, mesh, strategy, opt, lr=1e-2,
                             worker_axes=wa, wire="packed"))
for _ in range(3):
    s1, m1 = jstep(s1, batch)
    s2, m2 = jp(s2, batch)
out["packed_max_diff"] = max_param_diff(s1, s2)

# fused wire backend through the sharded step: the step runs the backend
# it was asked for (exposed on the step fn), and on CPU hosts the fused
# backend runs the shared reference expressions, so parity is bitwise
fu = strategy._replace(wire_backend="fused")
step_fu = make_train_step(cfg, mesh, fu, opt, lr=1e-2, worker_axes=wa,
                          wire="packed")
out["fused_resolved_backend"] = step_fu.wire_backend
jff = jax.jit(make_train_step(cfg, mesh, fu, opt, lr=1e-2,
                              worker_axes=wa, wire="float"))
jfp = jax.jit(step_fu)
f1, f2 = fresh(fu), fresh(fu)
for _ in range(3):
    f1, _ = jff(f1, batch)
    f2, _ = jfp(f2, batch)
out["fused_float_max_diff"] = max_param_diff(f1, s1)
out["fused_packed_max_diff"] = max_param_diff(f2, s2)

# adaptive bit-width (A-LAQ): packed wire must stay bit-identical to float
ad = strategy._replace(bit_schedule=BitSchedule(kind="radius", grid=(2, 4, 8),
                                                thresholds=(1e-3, 1e-2)))
a1, a2 = fresh(ad), fresh(ad)
jaf = jax.jit(make_train_step(cfg, mesh, ad, opt, lr=1e-2,
                              worker_axes=wa, wire="float"))
jap = jax.jit(make_train_step(cfg, mesh, ad, opt, lr=1e-2,
                              worker_axes=wa, wire="packed"))
for _ in range(3):
    a1, _ = jaf(a1, batch)
    a2, _ = jap(a2, batch)
out["adaptive_packed_max_diff"] = max_param_diff(a1, a2)

# adaptive + fused: the width-grid-unrolled pass-2 pipeline through the
# sharded packed wire matches the reference adaptive run bitwise
adf = ad._replace(wire_backend="fused")
af = fresh(adf)
jadf = jax.jit(make_train_step(cfg, mesh, adf, opt, lr=1e-2,
                               worker_axes=wa, wire="packed"))
for _ in range(3):
    af, _ = jadf(af, batch)
out["adaptive_fused_packed_max_diff"] = max_param_diff(af, a2)

# constant schedule routes to the fixed-bit path: exact match with bits=4
cs = strategy._replace(bits=7, bit_schedule=BitSchedule(kind="constant", bits=4))
c2 = fresh(cs)
jcp = jax.jit(make_train_step(cfg, mesh, cs, opt, lr=1e-2,
                              worker_axes=wa, wire="packed"))
for _ in range(3):
    c2, _ = jcp(c2, batch)
out["const_packed_max_diff"] = max_param_diff(s2, c2)

# variance-aware lazy rules (core/lazy_rules.py) + scale-free rel-mode
# adaptive anchor: the new CommState fields (lazy estimator state, R_anchor)
# thread through the sharded step on both wires
wk = strategy._replace(lazy_rule="lasg_wk")
ps = strategy._replace(
    lazy_rule="lasg_ps",
    bit_schedule=BitSchedule(kind="radius", grid=(2, 4, 8),
                             threshold_mode="rel", thresholds=(0.05, 0.5)))
w1 = fresh(wk)
jwk = jax.jit(make_train_step(cfg, mesh, wk, opt, lr=1e-2,
                              worker_axes=wa, wire="float"))
wl = []
for _ in range(4):
    w1, m = jwk(w1, batch)
    wl.append(float(m.loss))
out["wk_losses"] = wl
out["wk_sigma_hat_max"] = float(jnp.max(w1.comm.lazy.sigma_hat_sq))
p1 = fresh(ps)
jps = jax.jit(make_train_step(cfg, mesh, ps, opt, lr=1e-2,
                              worker_axes=wa, wire="packed"))
pl = []
for _ in range(4):
    p1, m = jps(p1, batch)
    pl.append(float(m.loss))
out["ps_losses"] = pl
out["ps_anchor_min"] = float(jnp.min(p1.comm.R_anchor))
out["ps_theta_last_set"] = float(max(jax.tree.leaves(jax.tree.map(
    lambda l: float(jnp.max(jnp.abs(l))), p1.comm.lazy.theta_last))))

# wk2 same-sample rule (second backprop at the stale iterate) + streaming
# svrg anchor + 1/t stepsize schedule: the PR-4 CommState fields (svrg) and
# the scheduled lr thread through the mesh on the packed wire
from repro.core.adaptive import EtaSchedule
vr = strategy._replace(lazy_rule="lasg_wk2", grad_mode="svrg", svrg_period=2,
                       eta_schedule=EtaSchedule(kind="inv_t", t0=10.0))
v1 = fresh(vr)
jvr = jax.jit(make_train_step(cfg, mesh, vr, opt, lr=1e-2,
                              worker_axes=wa, wire="packed"))
vl = []
for _ in range(4):
    v1, m = jvr(v1, batch)
    vl.append(float(m.loss))
out["vr_losses"] = vl
out["vr_theta_last_set"] = float(max(jax.tree.leaves(jax.tree.map(
    lambda l: float(jnp.max(jnp.abs(l))), v1.comm.lazy.theta_last))))
out["vr_mu_set"] = float(max(jax.tree.leaves(jax.tree.map(
    lambda l: float(jnp.max(jnp.abs(l))), v1.comm.svrg.mu_anchor))))

# upload defense (PR-7, core/defense.py): DefenseState threads through the
# sharded step on both wires; at fault rate 0 validation+gate must be a
# bitwise no-op vs the undefended run, and float vs packed stay identical
from repro.core.defense import DefenseConfig
df = strategy._replace(defense=DefenseConfig(validate=True, gate_mult=6.0))
d1, d2 = fresh(df), fresh(df)
jdf = jax.jit(make_train_step(cfg, mesh, df, opt, lr=1e-2,
                              worker_axes=wa, wire="float"))
jdp = jax.jit(make_train_step(cfg, mesh, df, opt, lr=1e-2,
                              worker_axes=wa, wire="packed"))
s0 = fresh()
for _ in range(3):
    d1, m = jdf(d1, batch)
    d2, _ = jdp(d2, batch)
    s0, _ = jstep(s0, batch)
out["defense_noop_max_diff"] = max_param_diff(d1, s0)
out["defense_packed_max_diff"] = max_param_diff(d1, d2)
out["defense_rejects"] = int(jnp.sum(d1.comm.defense.rejects))

# partial participation (PR-5 round engine): the replicated cohort mask is
# indexed per shard by the worker-index input
from repro.core.engine import participation_mask
pp = strategy._replace(participation="bernoulli", participation_p=0.5)
p2 = fresh(pp)
jpp = jax.jit(make_train_step(cfg, mesh, pp, opt, lr=1e-2,
                              worker_axes=wa, wire="float"))
pp_ups = []
for _ in range(4):
    p2, m = jpp(p2, batch)
    pp_ups.append(int(m.uploads))
out["pp_uploads"] = pp_ups
out["pp_cohorts"] = [int(participation_mask(pp, k, 4).sum())
                     for k in range(4)]

params_s, cache_s, tokens_s = serve_specs(cfg, mesh, batch=8, seq_len=128)
c = jax.jit(make_decode_step(cfg)).lower(params_s, cache_s, tokens_s).compile()
ca = c.cost_analysis()
out["decode_flops"] = float(ca.get("flops", -1))

# --- multi-pod hierarchical mode -------------------------------------------
mesh2 = jax.make_mesh((2, 2, 2), ("pod", "data", "model"))
wa2 = ("pod",)
specs2 = train_state_specs(cfg, mesh2, strategy, opt, wa2)
state2 = init_train_state(jax.random.PRNGKey(0), cfg, mesh2, strategy, opt, wa2)
state2 = jax.tree.map(lambda x, sp: jax.device_put(x, sp.sharding), state2, specs2)
batch2 = jax.device_put(synthetic_lm_batch(jax.random.PRNGKey(1), 8, 64, cfg.vocab),
                        NamedSharding(mesh2, P(("pod", "data"), None)))
jstep2 = jax.jit(make_train_step(cfg, mesh2, strategy, opt, lr=1e-2,
                                 worker_axes=wa2, wire="packed"))
l2 = []
for _ in range(4):
    state2, m = jstep2(state2, batch2)
    l2.append(float(m.loss))
out["pod_losses"] = l2
out["pod_uploads"] = int(m.uploads)
print("RESULT " + json.dumps(out))
"""


@pytest.mark.slow
def test_sharded_integration_subprocess():
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", _SHARDED_SCRIPT], env=env,
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-4000:]
    line = [l for l in r.stdout.splitlines() if l.startswith("RESULT ")][-1]
    out = json.loads(line[len("RESULT "):])
    assert out["losses"][-1] < out["losses"][0], out["losses"]
    assert out["packed_max_diff"] == 0.0, out
    assert out["adaptive_packed_max_diff"] == 0.0, out
    assert out["const_packed_max_diff"] == 0.0, out
    # fused wire backend on the mesh: the step runs the fused backend, and
    # fused runs are bitwise-identical to the reference wire — fixed-width
    # float and packed, and the adaptive packed trajectory
    assert out["fused_resolved_backend"] == "fused", out
    assert out["fused_float_max_diff"] == 0.0, out
    assert out["fused_packed_max_diff"] == 0.0, out
    assert out["adaptive_fused_packed_max_diff"] == 0.0, out
    # LASG rules on the mesh: runs stay finite and learn; the WK variance
    # estimate was frozen at an upload; the PS stale-iterate snapshot and
    # the rel-mode anchor were populated by the bootstrap round
    assert np.all(np.isfinite(out["wk_losses"])), out["wk_losses"]
    assert out["wk_losses"][-1] < out["wk_losses"][0], out["wk_losses"]
    assert out["wk_sigma_hat_max"] > 0.0, out
    assert np.all(np.isfinite(out["ps_losses"])), out["ps_losses"]
    assert out["ps_anchor_min"] > 0.0, out
    assert out["ps_theta_last_set"] > 0.0, out
    # defense on a clean run through the mesh: bitwise no-op vs undefended,
    # float/packed identical, nothing rejected
    assert out["defense_noop_max_diff"] == 0.0, out
    assert out["defense_packed_max_diff"] == 0.0, out
    assert out["defense_rejects"] == 0, out
    # WK2 + streaming svrg + 1/t schedule on the mesh: finite losses, the
    # stale-iterate snapshot and the svrg anchor's mu were both populated
    assert np.all(np.isfinite(out["vr_losses"])), out["vr_losses"]
    assert out["vr_theta_last_set"] > 0.0, out
    assert out["vr_mu_set"] > 0.0, out
    # participation on the mesh: the bootstrap round uploads exactly the
    # cohort (clocks start at t_bar), later rounds at most the cohort
    assert out["pp_uploads"][0] == out["pp_cohorts"][0], out
    assert all(u <= c for u, c in zip(out["pp_uploads"],
                                      out["pp_cohorts"])), out
    assert out["decode_flops"] > 0
    assert out["pod_losses"][-1] < out["pod_losses"][0], out["pod_losses"]
    assert 0 <= out["pod_uploads"] <= 2
