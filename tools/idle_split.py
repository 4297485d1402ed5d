#!/usr/bin/env python3
"""Split a profile's device idle time by the program's own spans.

    PYTHONPATH=src python tools/idle_split.py <dir or .xplane.pb> [--device 0]

Reads the newest ``.xplane.pb`` under the directory (as
``jax.profiler.trace`` writes it).  The window is the ``bench.window``
host span where the trace has one, else the first to the last op of the
device.  Every instant of the window in which the device ran no op is
given to the innermost ``engine.*`` / ``frontend.*`` span open then (the
shortest, on any host thread; see ``repro.obs``), or to ``(none)``.
Prints one JSON object: the window, busy and idle seconds, idle seconds
and gap counts by span, and the number of ``frontend.device`` spans
(microbatches) in the window.
"""
from __future__ import annotations

import argparse
import bisect
import glob
import heapq
import json
import os
import re
import sys
from collections import defaultdict

PROGRAM = re.compile(r"^(engine|frontend)\.")
WINDOW = "bench.window"
OPS_LINE = "XLA Ops"


def newest(path: str) -> str:
    if path.endswith(".xplane.pb"):
        return path
    found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return found[-1]


def load(path: str, device: int):
    """(window, device ops [(start, end)], program spans
    [(start, end, name)]), in seconds."""
    from jax.profiler import ProfileData

    ops, spans, window = [], [], None
    want = re.compile(rf"^/device:[A-Z]+:{device}$")
    for plane in ProfileData.from_file(path).planes:
        on_device = bool(want.match(plane.name))
        for line in plane.lines:
            if on_device and line.name != OPS_LINE:
                continue
            for ev in line.events:
                t0 = ev.start_ns * 1e-9
                t1 = t0 + ev.duration_ns * 1e-9
                if on_device:
                    ops.append((t0, t1))
                elif ev.name == WINDOW:
                    window = (t0, t1)
                elif PROGRAM.match(ev.name):
                    spans.append((t0, t1, ev.name))
    if not ops:
        raise ValueError(f"no {OPS_LINE!r} events on device {device}")
    if window is None:
        window = (min(o[0] for o in ops), max(o[1] for o in ops))
    return window, ops, spans


def union(intervals, lo, hi):
    out = []
    for a, b in sorted((max(a, lo), min(b, hi)) for a, b in intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def gaps(busy, lo, hi):
    out, t = [], lo
    for a, b in busy:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


def split(window, ops, spans) -> dict:
    lo, hi = window
    busy = union(ops, lo, hi)
    idle = gaps(busy, lo, hi)
    starts = [a for a, _ in idle]
    before = [0.0]
    for a, b in idle:
        before.append(before[-1] + b - a)

    def idle_until(t):
        """Idle time in [lo, t]."""
        i = bisect.bisect_right(starts, t)
        if i == 0:
            return 0.0
        a, b = idle[i - 1]
        return before[i - 1] + min(t, b) - a

    # sweep the span boundaries; between two, the innermost open span is
    # the shortest one not yet closed
    points = sorted([(a, (b - a, b, n)) for a, b, n in spans]
                    + [(b, None) for _, b, _ in spans]
                    + [(lo, None), (hi, None)], key=lambda p: p[0])
    segments, heap, t = [], [], lo
    for point, opened in points:
        point = min(max(point, lo), hi)
        while heap and heap[0][1] <= t:
            heapq.heappop(heap)
        if point > t:
            segments.append((t, point, heap[0][2] if heap else "(none)"))
            t = point
        if opened is not None:
            heapq.heappush(heap, opened)
    by_span, counts = defaultdict(float), defaultdict(int)
    for a, b, name in segments:
        by_span[name] += idle_until(b) - idle_until(a)
    seg_starts = [a for a, _, _ in segments]
    for a, b in idle:
        i = bisect.bisect_right(seg_starts, (a + b) / 2) - 1
        counts[segments[i][2] if i >= 0 else "(none)"] += 1
    busy_s = sum(b - a for a, b in busy)
    return {
        "window_s": hi - lo,
        "busy_s": busy_s,
        "idle_s": (hi - lo) - busy_s,
        "microbatches": sum(1 for a, b, n in spans
                            if n == "frontend.device" and lo <= a < hi),
        "idle_by_span": {n: [s, counts[n]] for n, s in
                         sorted(by_span.items(), key=lambda kv: -kv[1])},
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("path")
    ap.add_argument("--device", type=int, default=0)
    args = ap.parse_args(argv)
    print(json.dumps(split(*load(newest(args.path), args.device))))
    return 0


if __name__ == "__main__":
    sys.exit(main())
