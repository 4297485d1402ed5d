#!/usr/bin/env python3
"""Find the rate an open-loop cell's engine sustains: one build, then the
cell's open loop at each rate given.

    python3 benchmarks/chip/sweep.py --workload <open-loop cell> --seed <n> \\
        --rates 1500,2000,2500 --seconds 8

Prints one JSON line per rate: offered and answered rate, latency p50 and
p95, front-end occupancy.  The knee is the highest rate at which the
answered rate keeps up with the offered one and p95 stays flat; a cell's
fixed rate is set below it once, from this sweep.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

import run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(run.ROOT, "src"))
    cell = run.load_cell(args.workload)
    if cell.drive is not run.open_loop:
        raise SystemExit(f"{args.workload} is not an open-loop cell")
    devices = run.chips_for(cell.chips)
    run.use_compile_cache()
    import jax

    from types import SimpleNamespace

    from repro.search import SearchEngine
    cfg, traffic = cell.config, dict(cell.traffic)
    db, key_q, mesh = cell.generate(cfg, args.seed, devices)
    pool = np.asarray(run.corpus.make_queries(
        key_q, db, int(traffic["pool"]), near_share=traffic["near_share"],
        near_noise=traffic["near_noise"]))
    eng = SearchEngine.build(db, mesh=mesh, **cfg["build"])
    jax.block_until_ready(eng.index)
    del db
    for rate in (float(r) for r in args.rates.split(",")):
        traffic["rate_per_s"] = rate
        out = run.open_loop(SimpleNamespace(
            eng=eng, pool=pool, traffic=traffic, seconds=args.seconds,
            seed=args.seed, devices=devices, mesh=mesh, ready=lambda: None))
        print(json.dumps({"rate_per_s": rate,
                          "answered_per_s": len(out.answers) / out.elapsed,
                          **out.values, "occupancy": out.occupancy,
                          "at": time.strftime("%H:%M:%S")}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
