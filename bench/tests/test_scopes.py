"""Device time by span (``bench/scopes.py``): on a scoped trace recorded on
a TPU v5e by ``record_scoped_trace.py``, on synthetic HLO and events, and
on the CPU, where the step lowered again from shapes is the step the
harness compiles."""
import re

import jax
import numpy as np
import pytest

import small
import harness
import scopes
import traces

DATA = harness.BENCH / "tests" / "data"
METRICS = {"fwd_bwd_ms": "laq.fwd_bwd", "lazy_rule_ms": "laq.lazy_rule",
           "wire_quantize_ms": "laq.wire.quantize",
           "wire_codec_ms": "laq.wire.codec", "aggregate_ms": "laq.aggregate",
           "optimizer_ms": "laq.optimizer", "unscoped_ms": None}


def _read(ctx):
    return {m: harness.metric_module(m).read(ctx) for m in METRICS}


@pytest.fixture(scope="module")
def recorded():
    return (traces.load(str(DATA / "scoped.xplane.pb")),
            (DATA / "scoped.hlo.txt").read_text())


@pytest.fixture
def scoped(recorded):
    """A run's ``ctx`` (the readers keep what they compute in it)."""
    tr, hlo = recorded
    return dict(trace=tr, span=tr.window(), planes=sorted(tr.devices),
                rounds=5, hlo=hlo)


def test_recorded_probe_and_matmul(scoped):
    """The probe's events are ``laq.wire.quantize``; the matmul's fusion,
    and the prefetch of its weight (``copy-start``/``copy-done``, no
    metadata: their user's span), ``laq.fwd_bwd``."""
    ms = _read(scoped)
    ops = traces.clip(scoped["trace"].devices["/device:TPU:0"], *scoped["span"])
    own = traces.self_ns(ops)
    probe = sum(t for t, n in zip(own, ops.name) if n.startswith("%wire_probe"))
    matmul = sum(t for t, n in zip(own, ops.name) if n.startswith("%fusion ="))
    assert len(ops.name) == 20 and probe > 0 and matmul > 0
    assert ms["wire_quantize_ms"] == pytest.approx(probe * 1e-6 / 5)
    assert ms["fwd_bwd_ms"] == pytest.approx((own.sum() - probe) * 1e-6 / 5)
    assert ms["fwd_bwd_ms"] > matmul * 1e-6 / 5
    assert ms["lazy_rule_ms"] == 0.0 and ms["unscoped_ms"] == 0.0
    busy = traces.busy_ns(ops) * 1e-6 / 5
    assert sum(ms.values()) == pytest.approx(busy)


def test_no_spans_reads_nothing(scoped):
    bare = re.sub(r"laq\.[\w.]+/", "", scoped["hlo"])
    assert "laq." not in bare
    assert all(v is None for v in _read(dict(scoped, hlo=bare)).values())
    assert all(v is None for v in _read(dict(scoped, trace=None)).values())


HLO = """HloModule jit_step, is_scheduled=true

FileNames
1 "train.py"

%fused_computation (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  %multiply.1 = f32[8]{0} multiply(%param_0, %param_0), metadata={op_name="jit(step)/laq.optimizer/mul"}
  ROOT %bitcast.2 = f32[8]{0} bitcast(%multiply.1)
}

%body (p: (s32[], f32[8])) -> (s32[], f32[8]) {
  %p = (s32[], f32[8]{0}) parameter(0)
  %gte.1 = f32[8]{0} get-tuple-element(%p), index=1
  %dot.7 = f32[8]{0} dot(%gte.1, %gte.1), lhs_contracting_dims={}, rhs_contracting_dims={}
  ROOT %tuple.2 = (s32[], f32[8]{0}) tuple(%gte.1, %dot.7)
}

ENTRY %main (x: f32[8]) -> f32[8] {
  %x = f32[8]{0} parameter(0)
  %fusion.3 = f32[8]{0} fusion(%x), kind=kLoop, calls=%fused_computation
  %while.1 = (s32[], f32[8]{0}) while(%t), condition=%cond, body=%body, metadata={op_name="jit(step)/shard_map/laq.fwd_bwd/while"}
  %custom-call.2 = f32[8]{0} custom-call(%x), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/laq.aggregate/laq.wire.quantize/pallas_call"}
  %reduce.4 = f32[] reduce(%x, %c), dimensions={0}, to_apply=%add, metadata={op_name="jit(step)/transpose(jvp(laq.fwd_bwd))/reduce_sum"}
  %iota_fusion = pred[8]{0} fusion(), kind=kLoop, calls=%fused_iota, metadata={op_name="jit(step)/shard_map/ge"}
  %select.6 = f32[8]{0} select(%iota_fusion, %x, %x), metadata={op_name="jit(step)/laq.lazy_rule/select_n"}
  %convert.9 = bf16[8]{0} convert(%x)
  %dot.10 = f32[] dot(%convert.9, %convert.9), metadata={op_name="jit(step)/laq.fwd_bwd/dot_general"}
  ROOT %add.5 = s32[] add(%x, %x), metadata={op_name="jit(step)/add"}
}
"""


def test_synthetic_scope_map():
    m = scopes.scope_map(HLO)
    assert m["fusion.3"] == "laq.optimizer"        # its fused root's, walked back
    assert m["while.1"] == "laq.fwd_bwd"
    assert m["dot.7"] == "laq.fwd_bwd"             # in the loop's body
    assert m["custom-call.2"] == "laq.wire.quantize"   # the innermost
    assert m["reduce.4"] == "laq.fwd_bwd"          # under AD's transforms
    assert m["iota_fusion"] == "laq.lazy_rule"     # constants: its user's
    assert m["convert.9"] == "laq.fwd_bwd"         # no metadata: its user's
    assert m["add.5"] is None


def _ops(events):
    ev = sorted(events, key=lambda e: (e[0], -e[1]))
    return traces.Ops(np.array([e[0] for e in ev], float),
                      np.array([e[1] for e in ev], float), [e[2] for e in ev])


def test_synthetic_partition():
    ev = [(0, 1000, "%while.1 = (s32[], f32[8]{0}) while(%t), body=%body"),
          (100, 400, "%dot.7 = f32[8]{0} dot(%gte.1, %gte.1)"),
          (1000, 1300, "%fusion.3 = f32[8]{0} fusion(%x), kind=kLoop"),
          (1300, 1500, '%custom-call.2 = f32[8]{0} custom-call(%x), '
                       'custom_call_target="tpu_custom_call"'),
          (1500, 1600, "%add.5 = s32[] add(%x, %x)"),
          (1600, 1650, "%missing.1 = f32[8]{0} copy(%x)"),
          (5000, 6000, "%fusion.3 = f32[8]{0} fusion(%x), kind=kLoop")]
    tr = traces.Trace({"/device:TPU:0": _ops(ev)}, [])
    ctx = dict(trace=tr, span=(0, 2000), planes=["/device:TPU:0"], rounds=2,
               hlo=HLO)
    ms = _read(ctx)
    # the loop's own 700 ns and its body's dot 300 ns, each counted once
    assert ms["fwd_bwd_ms"] == pytest.approx(1000e-6 / 2)
    assert ms["optimizer_ms"] == pytest.approx(300e-6 / 2)   # in the window
    assert ms["wire_quantize_ms"] == pytest.approx(200e-6 / 2)
    assert ms["unscoped_ms"] == pytest.approx(150e-6 / 2)    # add, missing
    assert ms["aggregate_ms"] == 0.0 and ms["wire_codec_ms"] == 0.0
    assert sum(ms.values()) == pytest.approx(1650e-6 / 2)


def test_lowered_again_is_the_harness_step():
    """The step lowered from shapes is the one the harness compiles from
    its state and batch (metadata apart: the Python stack differs)."""
    c = small.small_of("mamba2-130m.laq4.tok2k")
    config, mix = c["config"], c["mix"]
    prog = harness._program()
    built = harness.build(prog, config, mix, 1)
    key = jax.random.PRNGKey(0)
    state = harness.initial_state(prog, built, config, key, 1)
    pool = harness.make_pool(built, dict(mix, pool_rounds=1), config["vocab"],
                             key, 1)
    ran = jax.jit(built["step"], donate_argnums=0).lower(
        state, pool[0]).compile().as_text()
    again = scopes.compile_hlo(dict(config=config, mix=mix, chips=1))

    def instructions(text):
        return re.sub(r", metadata=\{[^}]*\}", "", "\n".join(
            line for line in text.splitlines()
            if line.startswith(("%", "ENTRY", " ", "}"))))

    assert instructions(ran) == instructions(again)
    assert scopes.scope_map(ran) == scopes.scope_map(again)
