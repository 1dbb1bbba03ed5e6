"""Reduce a profiler trace to device busy time, kernel time and idle gaps.

Reads the ``.xplane.pb`` that ``jax.profiler`` writes, with
``jax.profiler.ProfileData`` alone.  Device operations are the events on the
``XLA Ops`` line of each ``/device:TPU:<n>`` plane; the benchmark's own host
spans are the events named ``bench.<what>`` on the host plane.  Both carry
times on the profiler's one clock, in nanoseconds.
"""
from __future__ import annotations

import dataclasses
import glob
import os

import numpy as np

OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."


@dataclasses.dataclass
class Ops:
    """One device's operations: start and end (ns) and name, by start."""
    start: np.ndarray
    end: np.ndarray
    name: list


@dataclasses.dataclass
class Trace:
    devices: dict           # plane name -> Ops
    spans: list             # (name, start_ns, end_ns) of the bench.* spans

    def window(self, name: str = "bench.window"):
        """``(start_ns, end_ns)`` of the host span ``name``."""
        hits = [(s, e) for n, s, e in self.spans if n == name]
        if len(hits) != 1:
            raise ValueError(f"expected one {name!r} span, found {len(hits)}")
        return hits[0]


def find_xplane(directory: str) -> str:
    paths = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise ValueError(f"expected one .xplane.pb under {directory}, "
                         f"found {len(paths)}")
    return paths[0]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices, spans = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                ev = [(e.start_ns, e.end_ns, e.name) for e in line.events]
                ev.sort(key=lambda x: (x[0], -x[1]))    # a parent before its children
                devices[plane.name] = Ops(
                    np.array([e[0] for e in ev], np.float64),
                    np.array([e[1] for e in ev], np.float64),
                    [e[2] for e in ev])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((e.name, e.start_ns, e.end_ns))
    return Trace(devices, spans)


def clip(ops: Ops, t0: float, t1: float) -> Ops:
    """The operations that overlap ``[t0, t1]``, cut to it."""
    keep = (ops.end > t0) & (ops.start < t1)
    idx = np.flatnonzero(keep)
    return Ops(np.maximum(ops.start[idx], t0), np.minimum(ops.end[idx], t1),
               [ops.name[i] for i in idx])


def busy_intervals(ops: Ops):
    """The union of the operations' intervals, as sorted disjoint pairs."""
    out = []
    for s, e in zip(ops.start, ops.end):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def busy_ns(ops: Ops) -> float:
    return float(sum(e - s for s, e in busy_intervals(ops)))


def idle_gaps(ops: Ops, t0: float, t1: float):
    """``(start, end)`` of every stretch of ``[t0, t1]`` with no operation."""
    gaps, cur = [], t0
    for s, e in busy_intervals(ops):
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if cur < t1:
        gaps.append((cur, t1))
    return gaps


def self_ns(ops: Ops) -> np.ndarray:
    """Each operation's own time: its duration less that of the operations
    nested in it (a loop's body ops lie inside the loop's event)."""
    own = ops.end - ops.start
    stack = []
    for i, (s, e) in enumerate(zip(ops.start, ops.end)):
        while stack and ops.end[stack[-1]] <= s:
            stack.pop()
        if stack:
            own[stack[-1]] -= e - s
        stack.append(i)
    return own


def short_name(name: str, width: int = 120) -> str:
    """An operation's HLO text, cut to ``width`` characters."""
    return name.lstrip("%")[:width]


def op_seconds(ops: Ops):
    """Device seconds of own time per operation, largest first."""
    tot = {}
    for t, n in zip(self_ns(ops), ops.name):
        n = short_name(n)
        tot[n] = tot.get(n, 0.0) + t * 1e-9
    return sorted(tot.items(), key=lambda kv: -kv[1])


def span_at(spans, t: float) -> str:
    """The innermost ``bench.*`` span open at ``t`` (the latest started),
    or ``"none"``."""
    best = None
    for n, s, e in spans:
        if s <= t <= e and n != "bench.window" and (best is None or s > best[1]):
            best = (n, s)
    return best[0] if best else "none"


def breakdown(trace: Trace, t0: float, t1: float, n: int = 10):
    """The ``breakdown`` of a result line: the device operations that took
    most time (own time, nested operations apart), and the longest idle
    gaps, each named by the host span open at its middle.  Taken on the
    first device plane.  The device's clock ran about 1.3 ms ahead of the
    host's in the recorded trace under ``bench/tests/data``, so a gap's
    name is the host's activity to within that."""
    plane = sorted(trace.devices)[0]
    ops = clip(trace.devices[plane], t0, t1)
    gaps = sorted(idle_gaps(ops, t0, t1), key=lambda g: g[0] - g[1])[:n]
    return {
        "device_ops": [[k, v] for k, v in op_seconds(ops)[:n]],
        "idle_gaps": [[span_at(trace.spans, (s + e) / 2), (e - s) * 1e-9]
                      for s, e in gaps],
    }
