"""From a profiler trace to the numbers the benchmark reports.

The traced window is the host span the harness opens around its measured
loop.  On each chip, busy time is the union of the intervals in which an
operation of the "XLA Ops" line ran, clipped to that window; idle is the
rest.  Collective time is the union of the collective operations alone.
Each idle gap of the first chip is named by the harness's host span that
overlaps it most, so that the longest idle time says what the host was
doing meanwhile.

``reduce`` works on plain interval lists, so that the tests can hand it a
trace built by hand; ``read`` fills them from an ``.xplane.pb`` file.  On
the TPU an op event is named by its whole HLO instruction, which is where
a collective shows (``all-gather-start(...)``); the breakdown names it by
the instruction's name alone.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from typing import NamedTuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
COLLECTIVE = re.compile(r"all-gather|all-reduce|reduce-scatter|all-to-all|"
                        r"collective-permute|collective-broadcast", re.I)
OP_NAME = re.compile(r"%?([\w.\-]+) = ")
#: host spans the harness opens; the window span bounds the reduction
SPAN_PREFIX = "bench."
WINDOW = "bench.window"
TOP = 10


class Op(NamedTuple):
    start: float        # seconds
    end: float
    name: str
    collective: bool


class Trace(NamedTuple):
    window: tuple[float, float]
    ops: dict            # device index -> [Op]
    spans: list          # [(start, end, name)] host spans of the harness


def read(log_dir: str) -> Trace:
    """The newest ``.xplane.pb`` under ``log_dir`` as interval lists."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return parse(ProfileData.from_file(paths[-1]))


def parse(data) -> Trace:
    """Interval lists from a ``jax.profiler.ProfileData``."""
    ops, spans, window = defaultdict(list), [], None
    for plane in data.planes:
        dev = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if dev is not None and line.name != OPS_LINE:
                continue
            for ev in line.events:
                t0, t1 = ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9
                if dev is not None:
                    ops[int(dev.group(1))].append(Op(
                        t0, t1, op_name(ev.name),
                        bool(COLLECTIVE.search(ev.name))))
                elif ev.name == WINDOW:
                    window = (t0, t1)
                elif ev.name.startswith(SPAN_PREFIX):
                    spans.append((t0, t1, ev.name))
    if window is None:
        raise ValueError(f"the trace holds no {WINDOW!r} span")
    if not ops:
        raise ValueError("the trace holds no device operations: no "
                         f"{OPS_LINE!r} line on a /device:TPU plane")
    return Trace(window, dict(ops), spans)


def op_name(text: str) -> str:
    """The instruction's name from an op event, which on the TPU is named by
    the whole HLO instruction: ``%fusion.3 = f32[...] fusion(...)``."""
    head = OP_NAME.match(text)
    return head.group(1) if head else text


def union(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """Merged, sorted intervals clipped to [lo, hi]."""
    out: list[list[float]] = []
    for a, b in sorted((max(a, lo), min(b, hi)) for a, b in intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def length(merged) -> float:
    return sum(b - a for a, b in merged)


def gaps(merged, lo: float, hi: float) -> list[tuple[float, float]]:
    """The complement of ``merged`` within [lo, hi]."""
    out, t = [], lo
    for a, b in merged:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


def _label(gap, spans) -> str:
    a, b = gap
    best, name = 0.0, "host.unannotated"
    for s0, s1, s_name in spans:
        over = min(b, s1) - max(a, s0)
        if over > best:
            best, name = over, s_name
    return name


def reduce(trace: Trace) -> dict:
    """busy_s and collective_s per chip, window_s, and the breakdown: the
    device operations that took most time (summed over chips) and the idle
    time of the first chip by the host span it fell in."""
    lo, hi = trace.window
    busy, coll, per_op = {}, {}, defaultdict(float)
    for dev, ops in sorted(trace.ops.items()):
        busy[dev] = length(union(((o.start, o.end) for o in ops), lo, hi))
        coll[dev] = length(union(((o.start, o.end) for o in ops
                                  if o.collective), lo, hi))
        for o in ops:
            per_op[o.name] += max(0.0, min(o.end, hi) - max(o.start, lo))
    first = min(trace.ops)
    idle = defaultdict(lambda: [0.0, 0])
    for g in gaps(union(((o.start, o.end) for o in trace.ops[first]), lo, hi),
                  lo, hi):
        slot = idle[_label(g, trace.spans)]
        slot[0] += g[1] - g[0]
        slot[1] += 1
    top_ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:TOP]
    top_idle = sorted(idle.items(), key=lambda kv: -kv[1][0])[:TOP]
    return {
        "window_s": hi - lo,
        "busy_s": [busy[d] for d in sorted(busy)],
        "collective_s": [coll[d] for d in sorted(coll)],
        "device_ops": [[name, s] for name, s in top_ops],
        "idle_gaps": [[f"{name} x{n}", s] for name, (s, n) in top_idle],
    }
