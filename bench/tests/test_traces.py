"""The trace reduction, on a small trace recorded on a TPU v5e by
``record_trace.py``: five rounds of a Pallas kernel (``wire_probe``) and a
matmul, each after a 50 ms host sleep in a ``bench.batch`` span."""
import numpy as np
import pytest

import small  # noqa: F401
import harness
import traces


DATA = harness.BENCH / "tests" / "data" / "small.xplane.pb"
PROBE_NS = (6608, 6823, 6624, 6600, 6589)   # wire_probe's five events


@pytest.fixture(scope="module")
def trace():
    return traces.load(str(DATA))


def _ctx(trace):
    t0, t1 = trace.window()
    return dict(trace=trace, span=(t0, t1), planes=sorted(trace.devices),
                rounds=5)


def test_planes_and_spans(trace):
    assert list(trace.devices) == ["/device:TPU:0"]
    assert len(trace.devices["/device:TPU:0"].name) == 20
    names = [n for n, _, _ in trace.spans]
    assert names.count("bench.window") == 1
    assert names.count("bench.batch") == 5
    assert names.count("bench.dispatch") == 5


def test_busy_time_of_one_round(trace):
    ops = traces.clip(trace.devices["/device:TPU:0"], 94_000_000, 95_000_000)
    # copy-start 13 ns, wire_probe and copy-done 6611 ns, the matmul 13345 ns
    assert traces.busy_ns(ops) == 19_969


def test_idle_share(trace):
    idle = harness.metric_module("device_idle_pct").read(_ctx(trace))
    t0, t1 = trace.window()
    busy = traces.busy_ns(traces.clip(trace.devices["/device:TPU:0"], t0, t1))
    assert idle == pytest.approx(100 * (1 - busy / (t1 - t0)))
    assert 99.9 < idle < 100.0          # 5 x ~20 us busy in ~257 ms


def test_wire_kernel_time_per_round(trace):
    ms = harness.metric_module("wire_kernel_ms").read(_ctx(trace))
    assert ms == pytest.approx(sum(PROBE_NS) * 1e-6 / 5)


def test_breakdown(trace):
    t0, t1 = trace.window()
    b = traces.breakdown(trace, t0, t1)
    ops = dict(b["device_ops"])
    probe = [v for k, v in ops.items() if k.startswith("wire_probe")]
    assert probe == [pytest.approx(sum(PROBE_NS) * 1e-9)]
    assert len(b["idle_gaps"]) == 10            # at most ten, longest first
    longest = b["idle_gaps"][:5]
    assert all(name == "bench.batch" for name, _ in longest)
    assert all(0.045 < s < 0.06 for _, s in longest)


def test_own_time_of_nested_ops():
    ops = traces.Ops(np.array([0.0, 10.0, 30.0, 120.0]),
                     np.array([100.0, 20.0, 50.0, 130.0]),
                     ["loop", "a", "b", "c"])
    assert traces.self_ns(ops).tolist() == [70.0, 10.0, 20.0, 10.0]
