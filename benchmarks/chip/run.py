#!/usr/bin/env python3
"""Chip benchmark: one cell, one run, one process.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

A cell is an entry of ``workloads`` in ``BENCHMARK.json`` at the checkout
root: a configuration (``configs/<name>.json``, the corpus and how the
engine is built) under a traffic mix (``traffic/<name>.json``, the
parameters of the closed or open loop below).  A configuration that needs
code keeps it in ``configs/<name>.py`` beside its file, whose
``generate(cfg, seed, devices)`` then makes the corpus; a mix that needs
code keeps it in ``traffic/<name>.py``, whose ``drive(ctx)`` then runs
the window in place of the loop its parameters name.  Each per-layer
metric is read by ``metrics/<name>.py``.  Nothing here names a cell, so a
cell is added by adding those files and entries.

A run generates the corpus and queries on the device from ``--seed``,
builds the engine, warms up the one shape the traffic uses, and measures
for ``--seconds``: everything before the window is ``setup_s``.  A closed
loop sends one batch after another through ``SearchEngine.search`` and
reports ``queries_per_s``; an open loop sends single queries at a fixed
Poisson rate into ``ContinuousBatcher.submit`` and reports the latency
percentiles.  Once the window has closed and the program's state is
freed, a sample of the window's answers, drawn from the seed, is held to
an independent brute force over the regenerated corpus (``reference.py``);
each number compared is printed beside its limit.  ``--trace 1`` runs the
same window under the profiler and reports the per-layer metrics.

The last line of stdout is one JSON object.  A platform other than TPU,
fewer chips than the cell asks for, or a chip missing from ``peaks.json``
ends the run with a non-zero code and no result.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()            # set-up is counted from here

import argparse  # noqa: E402
import asyncio  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import traceback  # noqa: E402
from types import SimpleNamespace  # noqa: E402

import jax  # noqa: E402
import numpy as np  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if HERE not in sys.path:
    sys.path.insert(0, HERE)

import corpus  # noqa: E402
import reference  # noqa: E402
import roofline  # noqa: E402
import tracereduce  # noqa: E402


#: queries per reference call in the check
CHECK_BLOCK = 256


class BenchError(Exception):
    """A run that cannot produce a result."""


def log(msg: str) -> None:
    print(msg, flush=True)


def span(name: str):
    return jax.profiler.TraceAnnotation(name)


# ------------------------------------------------------------------ cells


def load_cell(name: str, root: str = ROOT, chip: str = HERE
              ) -> SimpleNamespace:
    """The cell ``name`` with its configuration, traffic and metrics, as
    ``BENCHMARK.json`` under ``root`` names them, and the code that makes
    its corpus (``generate``) and drives its window (``drive``): the
    configuration's and the mix's own modules where they have one, else
    the general ones here.  ``chip`` is the directory that holds
    ``traffic/``."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    conf_file = os.path.join(root, conf["file"])
    with open(conf_file) as f:
        config = json.load(f)
    mix = os.path.join(chip, "traffic", cell["traffic"])
    with open(mix + ".json") as f:
        traffic = json.load(f)

    def here(metric):
        return "workloads" not in metric or name in metric["workloads"]

    end_to_end = [m for m in bench["end_to_end"] if here(m)]
    moved = {m["name"] for m in end_to_end}
    per_layer = [m for m in bench["per_layer"]
                 if name in m.get("workloads", ())
                 or ("workloads" not in m and m["moves"] in moved)]
    return SimpleNamespace(
        name=name, chips=int(cell["chips"]), config=config, traffic=traffic,
        generate=own_code(os.path.splitext(conf_file)[0] + ".py",
                          "generate", generate),
        drive=own_code(mix + ".py", "drive", LOOPS.get(traffic.get("loop"))),
        end_to_end=end_to_end, per_layer=per_layer)


def load_module(path: str):
    """The Python file at ``path``, imported under a name of its own."""
    name = "bench_" + "".join(c if c.isalnum() else "_" for c in
                              os.path.relpath(path, HERE))
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def own_code(path: str, attr: str, default):
    """``attr`` of the module at ``path`` where that file exists, else
    ``default``; a BenchError where neither is there."""
    if os.path.exists(path):
        return getattr(load_module(path), attr)
    if default is None:
        raise BenchError(f"no {os.path.basename(path)} and no general "
                         f"{attr} for its parameters")
    return default


def read_metric(name: str, run) -> float | None:
    """Run ``metrics/<name>.py``'s ``read`` over the run's record."""
    return load_module(os.path.join(HERE, "metrics", name + ".py")).read(run)


def chips_for(n: int):
    """The first ``n`` TPU chips, or a BenchError."""
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise BenchError(f"no TPU: JAX found platform {devs[0].platform!r}; "
                         f"this benchmark runs only on the chip")
    if len(devs) < n:
        raise BenchError(f"the cell needs {n} chips, JAX found {len(devs)}")
    return devs[:n]


def use_compile_cache() -> str:
    """JAX's persistent compile cache: where the environment names it, else
    the fixed ``.jax_cache/`` at the checkout root."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


# ------------------------------------------------------------------ loops


def closed_loop(ctx) -> SimpleNamespace:
    """One client: a batch from the pool, wait for its answer on the host,
    the next batch.  Runs until ``ctx.seconds`` have passed; the rate
    counts every batch answered over the time to the last answer."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    eng, k, b = ctx.eng, int(ctx.traffic["k"]), int(ctx.traffic["batch"])
    batches = ctx.pool.reshape(-1, b, ctx.pool.shape[1])
    place = (NamedSharding(ctx.mesh, P()) if ctx.mesh is not None
             else ctx.devices[0])
    pool = [jax.device_put(x, place) for x in batches]
    for q in pool[:2]:                              # warm the one shape
        jax.device_get(eng.search(q, k)[:2])
    ctx.ready()
    calls, fracs = [], []
    start = time.perf_counter()
    deadline = start + ctx.seconds
    with span(tracereduce.WINDOW):
        while True:
            i = len(calls) % len(pool)
            with span("bench.search"):
                sims, ids, st = eng.search(pool[i], k)
            with span("bench.fetch"):
                sims, ids, frac = jax.device_get(
                    (sims, ids, st.block_prune_frac))
            calls.append((batches[i], sims, ids))
            fracs.append(float(frac))
            if time.perf_counter() >= deadline:
                break
    elapsed = time.perf_counter() - start
    return SimpleNamespace(answers=calls, prune_fracs=fracs,
                           attempted=len(calls) * b, failed=0,
                           values={"queries_per_s": len(calls) * b / elapsed},
                           calls=len(calls), elapsed=elapsed)


class _Spanned:
    """The engine, with the benchmark's span around each search the front
    end makes (on the front end's device thread)."""

    def __init__(self, eng):
        self.eng = eng

    def search(self, queries, k):
        with span("bench.search"):
            return self.eng.search(queries, k)


def arrivals(rate: float, seconds: float, seed: int) -> np.ndarray:
    """Due times (s from the start) of a Poisson stream at ``rate``: the
    same set of exponential gaps for every seed, in the seed's order."""
    n = max(1, int(round(rate * seconds)))
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate
    gaps = np.random.default_rng(seed).permutation(gaps)
    return np.concatenate([[0.0], np.cumsum(gaps)[:-1]])


async def _serve(batcher, pool, due_offsets):
    loop = asyncio.get_running_loop()
    n = len(due_offsets)
    done = np.full(n, np.nan)
    late = np.zeros(n)
    answers: list = [None] * n
    errors: list = []

    async def one(i):
        try:
            answers[i] = await batcher.submit(pool[i % len(pool)])
        except Exception:                           # noqa: BLE001 - counted
            errors.append(traceback.format_exc())
            return
        done[i] = time.perf_counter()

    tasks = []
    start = time.perf_counter()
    due = start + due_offsets
    with span(tracereduce.WINDOW):
        for i in range(n):
            wait = due[i] - time.perf_counter()
            if wait > 0:
                await asyncio.sleep(wait)
            late[i] = time.perf_counter() - due[i]
            tasks.append(loop.create_task(one(i)))
        await asyncio.gather(*tasks)
    return answers, done - due, late, errors, time.perf_counter() - start


def open_loop(ctx) -> SimpleNamespace:
    """Single queries into ``ContinuousBatcher.submit`` at the traffic's
    fixed rate, timed from when each was due to when its answer reached
    the client."""
    from repro.serve.frontend import ContinuousBatcher

    traffic, pool, seconds = ctx.traffic, ctx.pool, ctx.seconds
    mb = int(traffic["max_batch"])
    due = arrivals(float(traffic["rate_per_s"]), seconds, ctx.seed)

    async def main():
        async with ContinuousBatcher(_Spanned(ctx.eng), int(traffic["k"]),
                                     max_batch=mb) as b:
            for _ in range(2):                      # warm the one shape
                await asyncio.gather(*(b.submit(q) for q in pool[:mb]))
            ctx.ready()
            b0, q0 = b.n_batches, b.n_queries
            out = await _serve(b, pool, due)
            return out, b.n_batches - b0, b.n_queries - q0

    (answers, lat, late, errors, elapsed), nb, nq = asyncio.run(main())
    if errors:
        print(errors[0], file=sys.stderr, flush=True)
    ok = np.isfinite(lat)
    log(f"open loop: {len(due)} queries due over {seconds} s at "
        f"{traffic['rate_per_s']}/s, {int(ok.sum())} answered in {nb} "
        f"microbatches in {elapsed:.3f} s; generator lateness p50 "
        f"{np.percentile(late, 50) * 1e3:.3f} ms, p99 "
        f"{np.percentile(late, 99) * 1e3:.3f} ms, max "
        f"{late.max() * 1e3:.3f} ms")
    lat_ms = lat[ok] * 1e3
    return SimpleNamespace(
        answers=[(pool[i % len(pool)][None], answers[i][0][None],
                  answers[i][1][None]) for i in np.flatnonzero(ok)],
        attempted=len(due), failed=len(errors),
        values={"query_p50_ms": float(np.percentile(lat_ms, 50)),
                "query_p95_ms": float(np.percentile(lat_ms, 95))},
        occupancy=nq / (nb * mb) if nb else None, calls=nb, elapsed=elapsed)


#: the general drivers, by a mix's ``loop``; each takes the run's context
#: (``eng``, the query ``pool`` on the host, ``traffic``, ``seconds``,
#: ``seed``, ``devices``, ``mesh``), warms its shapes, calls ``ready()``
#: once set-up is over, and runs the window
LOOPS = {"closed": closed_loop, "open": open_loop}


# ------------------------------------------------------------------ check


def check(shards, n_rows: int, queries: np.ndarray, sims, ids, k: int, *,
          block: int, control: bool) -> tuple[dict, dict | None]:
    """The numbers compared for ``(sims, ids)``, answers to ``queries``,
    against the reference; with ``control``, also those of the reference
    computed one precision lower (three bf16 passes, as ``Precision.HIGH``)
    in the program's place."""
    m = len(queries)
    pad = -m % block
    q = np.concatenate([queries, np.repeat(queries[:1], pad, axis=0)])
    ref_s, ref_i, c_s, c_i = [], [], [], []
    for a in range(0, len(q), block):
        s, i = reference.brute(shards, q[a:a + block], k + 1)
        ref_s.append(s)
        ref_i.append(i)
        if control:
            s, i = reference.brute(shards, q[a:a + block], k,
                                   three_pass=True)
            c_s.append(s)
            c_i.append(i)
    ref_s, ref_i = np.concatenate(ref_s)[:m], np.concatenate(ref_i)[:m]

    def numbers(s, i):
        own = np.concatenate([reference.exact(shards, q[a:a + block],
                                              _pad(i, len(q))[a:a + block])
                              for a in range(0, len(q), block)])[:m]
        return reference.compare(s, i, ref_s, ref_i, own, k=k, n_rows=n_rows)

    got = numbers(sims, ids)
    if not control:
        return got, None
    return got, numbers(np.concatenate(c_s)[:m], np.concatenate(c_i)[:m])


def _pad(ids, n):
    ids = np.asarray(ids)
    return np.concatenate([ids, np.zeros((n - len(ids), ids.shape[1]),
                                         ids.dtype)])


def sample_answers(out, traffic, seed: int):
    """The seed's sample of the window's answers, each a whole call: a
    batch of a closed loop, a single query of an open one.  Every answer
    is ``(queries, sims, ids)``, one row per query."""
    rng = np.random.default_rng([seed, 1])
    n = int(traffic["check_sample"])
    picks = np.sort(rng.choice(len(out.answers), min(n, len(out.answers)),
                               replace=False))
    return tuple(np.concatenate([np.asarray(out.answers[p][j])
                                 for p in picks]) for j in range(3))


# ------------------------------------------------------------------- cell


def generate(cfg: dict, seed: int, devices):
    """The seed's corpus on ``devices`` (split by rows over them), the key
    for the queries, and the mesh (``None`` on one chip)."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    key_db, key_q = jax.random.split(corpus.seed_key(seed))
    mesh = sharding = None
    if int(cfg["shards"]) > 1:
        mesh = Mesh(np.array(devices), ("data",))
        sharding = NamedSharding(mesh, P("data"))
    else:
        sharding = jax.sharding.SingleDeviceSharding(devices[0])
    db = corpus.make_corpus(key_db, int(cfg["rows"]), int(cfg["dim"]),
                            n_centers=int(cfg["assumed"]["n_centers"]),
                            noise=float(cfg["assumed"]["noise"]),
                            sharding=sharding)
    return db, key_q, mesh


def shards_of(db) -> list:
    """(rows on one device, first global row id) for every shard of db."""
    parts = sorted(db.addressable_shards, key=lambda s: s.index[0].start or 0)
    return [(s.data, s.index[0].start or 0) for s in parts]


def run_cell(cell, seed: int, seconds: float, trace: bool, devices, *,
             t0: float, peaks: dict | None = None,
             control: bool = False) -> dict:
    """One run of ``cell``: set-up, the measured window, the check.
    Returns the result's fields, plus ``control`` numbers when asked;
    ``peaks`` is the chip's entry of the peak table."""
    from repro.search import SearchEngine

    cfg, traffic = cell.config, cell.traffic
    k = int(traffic["k"])
    with span("bench.generate"):
        db, key_q, mesh = cell.generate(cfg, seed, devices)
        qs = corpus.make_queries(
            key_q, db, int(traffic["pool"]),
            near_share=float(traffic["near_share"]),
            near_noise=float(traffic["near_noise"]))
        pool_host = np.asarray(qs)
    with span("bench.build"):
        eng = SearchEngine.build(db, mesh=mesh, **cfg["build"])
        jax.block_until_ready(eng.index)
    del db, qs
    log(f"{cell.name}: {cfg['rows']} x {cfg['dim']} f32 over "
        f"{len(devices)} chip(s), backend {eng.backend_name}, built at "
        f"{time.perf_counter() - t0:.3f} s")

    setup = {}

    def ready():
        settle()
        setup["s"] = time.perf_counter() - t0
        setup["log_dir"] = _start_trace() if trace else None

    ctx = SimpleNamespace(eng=eng, pool=pool_host, traffic=traffic,
                          seconds=seconds, seed=seed, devices=devices,
                          mesh=mesh, ready=ready)
    out = cell.drive(ctx)
    if trace:
        jax.profiler.stop_trace()
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    out.values["setup_s"] = setup["s"]
    out.values["peak_hbm_gb"] = peak / 1e9
    log(f"window: {out.calls} calls in {out.elapsed:.3f} s after "
        f"{setup['s']:.3f} s of set-up; peak HBM {peak / 1e9:.3f} GB")
    reduced = None
    if trace:
        try:
            reduced = tracereduce.reduce(tracereduce.read(setup["log_dir"]))
        finally:
            shutil.rmtree(setup["log_dir"], ignore_errors=True)
        log(f"trace: window {reduced['window_s']:.3f} s, busy "
            f"{reduced['busy_s']} s per chip")

    del eng, ctx
    gc.unfreeze()
    gc.collect()
    q, s, i = sample_answers(out, traffic, seed)
    with span("bench.check"):
        db, _, _ = cell.generate(cfg, seed, devices)
        got, ctl = check(shards_of(db), int(cfg["rows"]), q, s, i, k,
                         block=CHECK_BLOCK, control=control)
        del db
    got["failed"] = out.failed
    run = SimpleNamespace(cell=cell, config=cfg, traffic=traffic,
                          chips=len(devices), out=out, trace=reduced,
                          peaks=peaks)
    return {"out": out, "numbers": got, "control": ctl, "run": run,
            "peak": peak, "trace": reduced, "checked": len(q)}


def settle() -> None:
    """End of set-up: collect, then move every object set-up made into the
    collector's permanent generation, so that no collection in the window
    walks the hundreds of thousands of objects JAX's import leaves (a full
    collection of them stalls the host ~0.1 s)."""
    gc.collect()
    gc.freeze()


def _start_trace() -> str:
    log_dir = tempfile.mkdtemp(prefix="bench-trace-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    return log_dir


def verdict(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """``correct``, and each number that the configuration's ``limits``
    hold beside its limit."""
    missing = sorted(set(limits) - set(numbers))
    if missing:
        raise BenchError(f"limits name numbers no run reads: {missing}")
    shown = {name: {"value": numbers[name], "limit": limit}
             for name, limit in limits.items()}
    return all(v["value"] <= v["limit"] for v in shown.values()), shown


def result_line(cell, res: dict, trace: bool, devices) -> dict:
    """The result's JSON object; the compared numbers come last."""
    correct, shown = verdict(res["numbers"], cell.config["limits"])
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": int(res["peak"])}
    if trace:
        metrics = {}
        for m in cell.per_layer:
            value = read_metric(m["name"], res["run"])
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        t = res["trace"]
        device["busy_s"] = sum(t["busy_s"]) / len(devices)
        device["window_s"] = t["window_s"]
    else:
        metrics = {m["name"]: {"value": res["out"].values[m["name"]],
                               "unit": m["unit"]} for m in cell.end_to_end}
    line = {"correct": correct, "attempted": res["out"].attempted,
            "failed": res["out"].failed, "metrics": metrics,
            "device": device}
    if trace:
        line["breakdown"] = {"device_ops": res["trace"]["device_ops"],
                             "idle_gaps": res["trace"]["idle_gaps"]}
    line["check"] = shown
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        sys.path.insert(0, os.path.join(ROOT, "src"))
        cell = load_cell(args.workload)
        import repro  # noqa: F401  the system under test
        cache = use_compile_cache()
        devices = chips_for(cell.chips)
        log(f"device {devices[0].device_kind} x{len(devices)}, compile cache "
            f"{cache}")
        res = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                       devices, t0=T0,
                       peaks=roofline.peak(devices[0].device_kind))
        line = result_line(cell, res, bool(args.trace), devices)
    except (BenchError, ImportError, KeyError, OSError) as e:
        print(f"run.py: {type(e).__name__}: {e}", file=sys.stderr, flush=True)
        return 1
    log(f"checked {res['checked']} answers: " + ", ".join(
        f"{name} {value!r}" for name, value in res["numbers"].items()))
    for name, v in line["check"].items():
        print(f"check {name}: {v['value']!r} (limit {v['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
