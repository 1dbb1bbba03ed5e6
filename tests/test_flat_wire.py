"""The whole-leaf packed wire of the sharded step (``launch/train.py``).

Where every mesh axis but the workers' has size 1, each leaf is whole on
its device and the packed wire ships the send kernel's flat payload and
decodes it with ``dequant_acc``; elsewhere, and on the adaptive wire, it
keeps the axis-packed codec.  One subprocess on 8 forced CPU devices runs
a small Mamba2 whose leaves are no multiple of the kernels' BLOCK and
some of whose last dims are odd (three SSD heads), and checks:

* the packed wire is the float wire bitwise after three rounds, for the
  reference backend and the fused backend's jnp lowering, on the gather
  exchange with 4 workers at 2, 4 and 8 bits and with 2 workers at 4;
  and for the fused backend's Pallas kernels (interpret mode) on the
  gather at 4;
* the axis codec (a ``model=2`` mesh) is the float wire bitwise with 2
  workers at 4 bits, for both backends (it gathers at every W);
* the step's layout counter reads every leaf flat on a ``model=1`` mesh,
  and none on a ``model=2`` mesh or with an adaptive schedule;
* at trace time, counters wrapped round the backends' radius and pass-2
  primitives: on the whole-leaf wire, on ``(4, 1)`` and ``(1, 1)``, each
  non-empty leaf gets one radius pass and one pass-2 sweep a round (the
  exchange ships the lazy rule's payload), and the layout's pass count
  reads 1; on the axis codec (``model=2``) it gets two of each, and the
  count reads 2, as it does on the adaptive wire.
"""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

BITS = (2, 4, 8)
BACKENDS = ("reference", "fused")
# (workers, bits): every width with four workers, one with two (where the
# axis codec would swap, the whole-leaf wire gathers too)
MESHES = [(4, b) for b in BITS] + [(2, 4)]
CASES = [(W, backend, b) for W, b in MESHES for backend in BACKENDS]
IDS = [f"W{W}-{backend}-b{b}" for W, backend, b in CASES]
# (workers, model) meshes on which the axis codec's packed wire is held to
# the float wire
AXIS_MESHES = [(2, 2)]
AXIS_CASES = [(W, M, backend) for W, M in AXIS_MESHES for backend in BACKENDS]
AXIS_IDS = [f"W{W}-model{M}-{backend}" for W, M, backend in AXIS_CASES]
# (workers, model) meshes whose traced step the pass counters read
PASS_MESHES = [(4, 1), (1, 1), (4, 2)]

_SCRIPT = r"""
import dataclasses, json, os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import get_config, smoke_config
from repro.core.adaptive import BitSchedule
from repro.core.strategy import StrategyConfig
from repro.core.wire import FusedWire
from repro.data import synthetic_lm_batch
from repro.launch.train import (init_train_state, make_train_step,
                                train_state_specs)
from repro.models import init_params
from repro.optim import sgd

MESHES, AXIS_MESHES, BACKENDS, PASS_MESHES = json.loads(sys.argv[1])
cfg = dataclasses.replace(smoke_config(get_config("mamba2-130m")),
                          n_layers=1, d_model=48, vocab=256)
opt = sgd()
wa = ("data",)
out = {}


def mesh_of(data, model=1):
    return jax.make_mesh((data, model), ("data", "model"),
                         devices=jax.devices()[:data * model])


def run(mesh, strat, wire):
    s = init_train_state(jax.random.PRNGKey(0), cfg, mesh, strat, opt, wa)
    sp = train_state_specs(cfg, mesh, strat, opt, wa)
    s = jax.tree.map(lambda x, spc: jax.device_put(x, spc.sharding), s, sp)
    batch = jax.device_put(
        synthetic_lm_batch(jax.random.PRNGKey(1), 8, 32, cfg.vocab),
        NamedSharding(mesh, P("data", None)))
    step = make_train_step(cfg, mesh, strat, opt, lr=1e-2, worker_axes=wa,
                           wire=wire)
    jstep = jax.jit(step)
    for _ in range(3):
        s, m = jstep(s, batch)
    return s, step


def max_param_diff(a, b):
    return max(jax.tree.leaves(jax.tree.map(
        lambda x, y: float(jnp.max(jnp.abs(x.astype(jnp.float32)
                                           - y.astype(jnp.float32)))),
        a.params, b.params)))


for W, bits in MESHES:
    mesh = mesh_of(W)
    base = StrategyConfig(kind="laq", bits=bits, per_leaf_radius=True)
    want, _ = run(mesh, base, "float")
    for backend in BACKENDS:
        got, step = run(mesh, base._replace(wire_backend=backend), "packed")
        out[f"parity-W{W}-{backend}-b{bits}"] = max_param_diff(want, got)
        out[f"layout-W{W}-{backend}-b{bits}"] = step.wire_layout

# the axis codec: model-sharded leaves, one exchange at every W
for W, M in AXIS_MESHES:
    mesh = mesh_of(W, M)
    base = StrategyConfig(kind="laq", bits=4, per_leaf_radius=True)
    want, _ = run(mesh, base, "float")
    for backend in BACKENDS:
        got, _ = run(mesh, base._replace(wire_backend=backend), "packed")
        out[f"axis-parity-W{W}-model{M}-{backend}"] = max_param_diff(want, got)

# the fused backend's kernels (Pallas interpret mode) in the step: the
# float wire on the same backend is the reference, since its moments are
# the kernels' blockwise sums
pallas = StrategyConfig(kind="laq", bits=4, per_leaf_radius=True,
                        wire_backend=FusedWire(lowering="pallas"))
mesh = mesh_of(4)
want, _ = run(mesh, pallas, "float")
got, _ = run(mesh, pallas, "packed")
out["parity-W4-pallas-b4"] = max_param_diff(want, got)

sizes = [l.size for l in jax.tree.leaves(
    jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg)))]
out["leaves"] = [len(sizes), sum(sizes)]
out["odd_last_dims"] = sum(1 for l in jax.tree.leaves(jax.eval_shape(
    lambda: init_params(jax.random.PRNGKey(0), cfg))) if l.shape[-1] % 2)
fixed = StrategyConfig(kind="laq", bits=4, per_leaf_radius=True)
adaptive = fixed._replace(bit_schedule=BitSchedule(
    kind="radius", grid=(2, 4, 8), thresholds=(1e-3, 1e-2)))
out["layout-model2"] = make_train_step(
    cfg, mesh_of(4, 2), fixed, opt, lr=1e-2, worker_axes=wa,
    wire="packed").wire_layout
out["layout-adaptive"] = make_train_step(
    cfg, mesh_of(4), adaptive, opt, lr=1e-2, worker_axes=wa,
    wire="packed").wire_layout

# how many radius passes and pass-2 sweeps each leaf gets in one traced
# round: counters round the radius primitives (the backends' leaf_absmax,
# the reference's tree innovation) and the pass-2 ones (the per-leaf code
# map, the fused backend's jnp sweep)
import collections
import repro.core.quantize as Q
import repro.core.wire as Wm
counts = collections.Counter()


def counted(kind, fn, arg):
    def wrapped(*a, **k):
        for x in jax.tree.leaves(a[arg]):
            if x.size:
                counts[kind, tuple(x.shape)] += 1
        return fn(*a, **k)
    return wrapped


Wm.WireBackend.leaf_absmax = counted("radius", Wm.WireBackend.leaf_absmax, 1)
Q.innovation = counted("radius", Q.innovation, 0)
Q.quantize_codes = counted("pass2", Q.quantize_codes, 0)
Wm.quantize_codes = counted("pass2", Wm.quantize_codes, 0)
Wm._fused_leaf_jnp = counted("pass2", Wm._fused_leaf_jnp, 0)
leaf_shapes = collections.Counter(
    tuple(l.shape) for l in jax.tree.leaves(jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), cfg))) if l.size)
for data, model in PASS_MESHES:
    mesh = mesh_of(data, model)
    for backend in BACKENDS:
        strat = fixed._replace(wire_backend=backend)
        s = jax.eval_shape(lambda: init_train_state(
            jax.random.PRNGKey(0), cfg, mesh, strat, opt, wa))
        batch = jax.eval_shape(lambda: synthetic_lm_batch(
            jax.random.PRNGKey(1), 8, 32, cfg.vocab))
        step = make_train_step(cfg, mesh, strat, opt, lr=1e-2,
                               worker_axes=wa, wire="packed")
        counts.clear()
        jax.jit(step).trace(s, batch)
        per_leaf = {kind: sorted({counts[kind, shp] / n
                                  for shp, n in leaf_shapes.items()})
                    for kind in ("radius", "pass2")}
        per_leaf["stray"] = sum(v for (_, shp), v in counts.items()
                                if shp not in leaf_shapes)
        per_leaf["quantize_passes"] = step.wire_layout["quantize_passes"]
        out[f"passes-{data}x{model}-{backend}"] = per_leaf
print("RESULT " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def result():
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
               JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run(
        [sys.executable, "-c", _SCRIPT,
         json.dumps([MESHES, AXIS_MESHES, BACKENDS, PASS_MESHES])],
        env=env, capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-4000:]
    line = [l for l in r.stdout.splitlines() if l.startswith("RESULT ")][-1]
    return json.loads(line[len("RESULT "):])


def test_model_has_odd_and_unblocked_leaves(result):
    from repro.kernels.quant_pack import BLOCK
    n_leaves, n_params = result["leaves"]
    assert result["odd_last_dims"] > 0
    assert n_params < BLOCK * n_leaves        # no leaf fills a whole block


@pytest.mark.parametrize("W,backend,bits", CASES, ids=IDS)
def test_packed_equals_float_bitwise(result, W, backend, bits):
    assert result[f"parity-W{W}-{backend}-b{bits}"] == 0.0


@pytest.mark.parametrize("W,model,backend", AXIS_CASES, ids=AXIS_IDS)
def test_axis_codec_equals_float_bitwise(result, W, model, backend):
    assert result[f"axis-parity-W{W}-model{model}-{backend}"] == 0.0


def test_packed_equals_float_on_the_kernels(result):
    assert result["parity-W4-pallas-b4"] == 0.0


@pytest.mark.parametrize("W,backend,bits", CASES, ids=IDS)
def test_every_leaf_flat_on_model_1(result, W, backend, bits):
    layout = result[f"layout-W{W}-{backend}-b{bits}"]
    assert layout == {"flat": result["leaves"], "axis": [0, 0],
                      "quantize_passes": 1}


@pytest.mark.parametrize("case", ["model2", "adaptive"])
def test_no_leaf_flat_elsewhere(result, case):
    assert result[f"layout-{case}"] == {"flat": [0, 0],
                                        "axis": result["leaves"],
                                        "quantize_passes": 2}


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("mesh", PASS_MESHES,
                         ids=[f"{d}x{m}" for d, m in PASS_MESHES])
def test_one_quantize_a_round_on_the_flat_wire(result, mesh, backend):
    """Whole-leaf wire (model=1): each non-empty leaf's radius and pass-2
    sweep run once a round; the axis codec (model=2) runs them twice."""
    data, model = mesh
    passes = 1 if model == 1 else 2
    assert result[f"passes-{data}x{model}-{backend}"] == {
        "radius": [passes], "pass2": [passes], "stray": 0,
        "quantize_passes": passes}
