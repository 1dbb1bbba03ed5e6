"""The sharded step's named spans (``src/repro/core/spans.py``).

Compiles ``make_train_step`` on four CPU devices for the packed wire on
both backends and for the float wire, each with one and two microbatches,
and reads the optimized HLO with the benchmark's own span reduction
(``bench/scopes.py``).  Checks that every span is there, that every
operation that does work on the round's data belongs to one, and that the
spans add no work: with metadata stripped, the program compiled with spans
is the program compiled with ``jax.named_scope`` replaced by
``contextlib.nullcontext``.
"""
import json
import os
import pathlib
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]

CASES = [("packed", "fused", 1), ("packed", "fused", 2),
         ("packed", "reference", 1), ("packed", "reference", 2),
         ("float", "fused", 1), ("float", "fused", 2)]
IDS = [f"{w}-{b}-mb{m}" for w, b, m in CASES]

# the operations that do the round's work; each must belong to a span
WORK = ("fusion", "dot", "convolution", "reduce", "custom-call",
        "all-gather", "all-reduce")

_SCRIPT = r"""
import contextlib, dataclasses, json, os, re, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path.insert(0, sys.argv[1])
import jax
# the persistent cache's key leaves metadata out: it would hand the compile
# without spans the executable compiled with them
jax.config.update("jax_enable_compilation_cache", False)
import scopes
from repro.configs import get_config, smoke_config
from repro.core.spans import SPANS
from repro.core.strategy import StrategyConfig
from repro.launch.mesh import device_mesh
from repro.launch.train import batch_specs, make_train_step, train_state_specs
from repro.optim import adamw

WORK = tuple(json.loads(sys.argv[2]))
cfg = dataclasses.replace(smoke_config(get_config("mamba2-130m")),
                          d_model=64, vocab=256)
mesh = device_mesh(n_devices=4)
opt = adamw()


def compiled_text(wire, backend, mb):
    strategy = StrategyConfig(kind="laq", bits=4, per_leaf_radius=True,
                              wire_backend=backend)
    step = make_train_step(cfg, mesh, strategy, opt, lr=1e-3,
                           worker_axes=("data",), wire=wire, microbatch=mb)
    state = train_state_specs(cfg, mesh, strategy, opt, ("data",))
    return jax.jit(step, donate_argnums=0).lower(
        state, batch_specs(cfg, mesh, 8, 32)).compile().as_text()


def stripped(text):
    # the module line and the computations, without the source-location
    # tables or any instruction's metadata
    lines = text.splitlines()
    body = [l for i, l in enumerate(lines)
            if i == 0 or l.startswith(("%", "ENTRY", " ", "}"))]
    return re.sub(r", metadata=\{[^}]*\}", "", "\n".join(body))


out = {}
for wire, backend, mb in json.loads(sys.argv[3]):
    text = compiled_text(wire, backend, mb)
    named_scope = jax.named_scope
    jax.named_scope = contextlib.nullcontext
    try:
        plain = compiled_text(wire, backend, mb)
    finally:
        jax.named_scope = named_scope
    instrs, comps, _, _ = scopes.parse(text)
    smap = scopes.scope_map(text)
    fused = {c for _, _, c in instrs.values() if c}
    unscoped = [n for c, names in comps.items() if c not in fused
                for n in names
                if instrs[n][1] in WORK and smap[n] is None]
    out[f"{wire}-{backend}-mb{mb}"] = dict(
        spans=sorted({s for s in smap.values() if s in SPANS}),
        unscoped=unscoped, same=stripped(text) == stripped(plain),
        scoped="laq." in text, plain_scoped="laq." in plain)
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def compiled():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(ROOT / "src") + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    env.pop("XLA_FLAGS", None)
    p = subprocess.run(
        [sys.executable, "-c", _SCRIPT, str(ROOT / "bench"),
         json.dumps(WORK), json.dumps(CASES)],
        env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("case", IDS)
def test_every_span_in_compiled_step(compiled, case):
    from repro.core.spans import SHARED_BLOCK, SPANS, WIRE_CODEC
    # the float wire ships f32 innovations: it has no byte layout; Mamba2
    # has no shared block
    want = [s for s in SPANS if (s != WIRE_CODEC or case.startswith("packed"))
            and s != SHARED_BLOCK]
    assert compiled[case]["spans"] == sorted(want)


@pytest.mark.parametrize("case", IDS)
def test_every_working_op_has_a_span(compiled, case):
    assert compiled[case]["unscoped"] == []


@pytest.mark.parametrize("case", IDS)
def test_spans_add_no_work(compiled, case):
    c = compiled[case]
    assert c["scoped"] and not c["plain_scoped"]
    assert c["same"]
