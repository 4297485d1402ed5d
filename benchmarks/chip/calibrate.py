#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, on the chip.

    python3 benchmarks/chip/calibrate.py --workload <cell> \\
        --seeds 1,2,...,12 --control-seeds 1,2,3 --seconds 3

For each seed, in one process, a run of the cell as ``run.py`` makes it,
with a short window: set-up, the window, and the comparison of a sample
of the window's answers with the reference.  On the control seeds the
reference computed one precision lower (three bf16 passes) also stands in
the program's place and is compared the same way.  Prints one JSON line
per seed, then the largest reading of the program and the smallest of
the control for each number: the lower and upper readings of its limit.
The benchmark's own runs never run the control.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    control = {int(s) for s in args.control_seeds.split(",") if s}
    sys.path.insert(0, os.path.join(run.ROOT, "src"))
    cell = run.load_cell(args.workload)
    devices = run.chips_for(cell.chips)
    run.use_compile_cache()
    peaks = run.roofline.peak(devices[0].device_kind)
    lower, upper = {}, {}
    for seed in seeds:
        res = run.run_cell(cell, seed, args.seconds, False, devices,
                           t0=time.perf_counter(), peaks=peaks,
                           control=seed in control)
        print(json.dumps({"seed": seed, "program": res["numbers"],
                          "control": res["control"],
                          "values": res["out"].values}), flush=True)
        for name, v in res["numbers"].items():
            lower[name] = max(lower.get(name, v), v)
        for name, v in (res["control"] or {}).items():
            upper[name] = min(upper.get(name, v), v)
    print(json.dumps({"workload": args.workload, "lower": lower,
                      "upper": upper}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
