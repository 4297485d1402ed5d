#!/usr/bin/env python3
"""Chip smoke test: exact cosine search end to end on a TPU.

Drives ``SearchEngine`` through the entry points a user calls — build,
``engine.search``, ``ContinuousBatcher.submit`` and ``engine.online()``
insert/delete — at the per-chip share of the MS MARCO passage corpus
(8,841,823 passages of 768-d dense embeddings, a corpus sharded over four
chips): 2,210,456 rows x 768 f32 on one chip.  The corpus is a clustered
mixture generated on the device from ``--seed``; queries are perturbed
corpus rows plus random unit vectors.  Every answer, at k=10 and k=100,
is checked against an independent reference: a blocked f32
``Precision.HIGHEST`` matmul plus ``top_k`` over the stored rows,
compared tie-aware (the similarity profile within ``TOL``, the id sets
equal wherever the gap below the k-th best exceeds ``TOL``).

    python chip_smoke.py                # one chip, the kernel backend
    python chip_smoke.py --four-chips   # the sharded backend over 4 chips

``--four-chips`` runs only the sharded phase and its reference: 4 x
2,210,456 rows, one shard built on each chip, checked against a per-shard
HIGHEST brute force merged across shards.

Timings, compile times and peak device memory print on earlier lines for
information.  The last line of stdout is one JSON object,
``{"ok": true, "device": {...}}``; a failed phase exits non-zero before
printing it.  A platform other than TPU is refused at start-up.
"""
from __future__ import annotations

import argparse
import asyncio
import functools
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))

#: MS MARCO passage ranking corpus, sharded over four chips
MSMARCO_PASSAGES = 8_841_823
ROWS_PER_CHIP = -(-MSMARCO_PASSAGES // 4)          # 2,210,456
DIM = 768
KS = (10, 100)
#: kernel db tile (rows); the index block stays at the default 128
BN = 256
QUERIES = 256        # engine.search batch
SUBMITS = 384        # ContinuousBatcher.submit calls per k
MAX_BATCH = 64       # ContinuousBatcher microbatch width
#: similarity tolerance of the tie-aware comparison (f32 rounding of two
#: differently-blocked HIGHEST matmuls over the same stored rows is ~1e-6)
TOL = 1e-5


def fail(msg: str) -> None:
    print(f"chip_smoke: FAILED: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def check(cond, msg: str) -> None:
    if not cond:
        fail(msg)


def log(msg: str) -> None:
    print(msg, flush=True)


# ---------------------------------------------------------------- data


def make_corpus(key, n: int, *, sharding=None, n_centers: int = 1024,
                noise: float = 0.025):
    """[n, DIM] clustered mixture on the device: unit cluster centres plus
    isotropic noise (within-cluster cosine ~0.67).  Not normalized — the
    index build normalizes, as it would a user's embeddings."""

    def gen(key):
        kc, kl, kn = jax.random.split(key, 3)
        c = jax.random.normal(kc, (n_centers, DIM), jnp.float32)
        c = c / jnp.linalg.norm(c, axis=1, keepdims=True)
        lab = jax.random.randint(kl, (n,), 0, n_centers)
        return c[lab] + noise * jax.random.normal(kn, (n, DIM), jnp.float32)

    return jax.jit(gen, out_shardings=sharding)(key)


def make_queries(key, db, m: int):
    """3/4 perturbed corpus rows, 1/4 random unit vectors ([m, DIM])."""

    @jax.jit
    def gen(key, db):
        k1, k2, k3 = jax.random.split(key, 3)
        n_near = 3 * m // 4
        rows = db[jax.random.randint(k1, (n_near,), 0, db.shape[0])]
        rows = rows / jnp.linalg.norm(rows, axis=1, keepdims=True)
        near = rows + 0.01 * jax.random.normal(k2, rows.shape, jnp.float32)
        far = jax.random.normal(k3, (m - n_near, DIM), jnp.float32)
        q = jnp.concatenate([near, far])
        return q / jnp.linalg.norm(q, axis=1, keepdims=True)

    return gen(key, db)


# ----------------------------------------------------------- reference


def _normalize(x):
    return x / jnp.maximum(jnp.linalg.norm(x, axis=-1, keepdims=True), 1e-12)


@functools.partial(jax.jit, static_argnames=("k", "chunk"))
def _brute_local(db, valid, row_ids, q, *, k: int, chunk: int = 1 << 17):
    """Top-k by a blocked f32 HIGHEST matmul over ``db`` [n, d]: sims and
    row ids, descending, (-inf, -1) past the valid rows."""

    qn = _normalize(q)
    n, m = db.shape[0], q.shape[0]
    chunk = min(chunk, n)

    def body(c, carry):
        best_s, best_i = carry
        start = jnp.minimum(c * chunk, n - chunk)      # last chunk clamps
        rows = jax.lax.dynamic_slice_in_dim(db, start, chunk)
        ok = (jax.lax.dynamic_slice_in_dim(valid, start, chunk)
              & (start + jnp.arange(chunk) >= c * chunk))
        ids = jax.lax.dynamic_slice_in_dim(row_ids, start, chunk)
        s = jnp.dot(qn, rows.T, precision=jax.lax.Precision.HIGHEST)
        s, sel = jax.lax.top_k(jnp.where(ok[None, :], s, -jnp.inf), k)
        cand_s = jnp.concatenate([best_s, s], axis=1)
        cand_i = jnp.concatenate([best_i, ids[sel]], axis=1)
        best_s, sel = jax.lax.top_k(cand_s, k)
        return best_s, jnp.take_along_axis(cand_i, sel, axis=1)

    init = (jnp.full((m, k), -jnp.inf, jnp.float32),
            jnp.full((m, k), -1, jnp.int32))
    return jax.lax.fori_loop(0, -(-n // chunk), body, init)


@functools.partial(jax.jit, static_argnames=("n_ids",))
def _exact_local(db, valid, row_ids, q, ids, *, n_ids: int):
    """HIGHEST similarity of each query to each of its returned ids (-inf
    where the id is not a live row of ``db``)."""

    where = jnp.full((n_ids,), -1, jnp.int32).at[
        jnp.where(valid, row_ids, n_ids)].set(
            jnp.arange(db.shape[0], dtype=jnp.int32), mode="drop")
    pos = jnp.where(ids >= 0, where[jnp.clip(ids, 0, n_ids - 1)], -1)
    rows = db[jnp.maximum(pos, 0)]                     # [m, k, d]
    s = jnp.einsum("md,mkd->mk", _normalize(q), rows,
                   precision=jax.lax.Precision.HIGHEST)
    return jnp.where(pos >= 0, s, -jnp.inf)


def reference(index, q, ids, *, k: int, n_ids: int, mesh=None):
    """(ref sims [m, k+1], ref ids [m, k+1], exact sims of ``ids``) over
    the index's stored rows; per shard and merged across shards when the
    index is shard-stacked."""

    brute = functools.partial(_brute_local, k=k + 1)
    exact = functools.partial(_exact_local, n_ids=n_ids)
    if mesh is None:
        return (*brute(index.db, index.valid, index.row_ids, q),
                exact(index.db, index.valid, index.row_ids, q, ids))
    from jax.sharding import PartitionSpec as P

    from repro.dist.compat import shard_map
    axis = mesh.axis_names

    def body(db, valid, rid, q, ids):
        s, i = brute(db[0], valid[0], rid[0], q)
        s = jax.lax.all_gather(s, axis, axis=1, tiled=True)
        i = jax.lax.all_gather(i, axis, axis=1, tiled=True)
        s, sel = jax.lax.top_k(s, k + 1)
        e = jax.lax.pmax(exact(db[0], valid[0], rid[0], q, ids), axis)
        return s, jnp.take_along_axis(i, sel, axis=1), e

    fn = shard_map(body, mesh=mesh, in_specs=(P(axis),) * 3 + (P(), P()),
                   out_specs=(P(), P(), P()), check_vma=False)
    return jax.jit(fn)(index.db, index.valid, index.row_ids, q, ids)


def compare(tag: str, sims, ids, ref, *, k: int, deleted=()) -> None:
    """Tie-aware check of one answer batch against ``reference``."""

    ref_s, ref_i, exact = (np.asarray(a) for a in ref)
    sims, ids = np.asarray(sims), np.asarray(ids)
    m = sims.shape[0]
    check(sims.shape == (m, k) and ids.shape == (m, k),
          f"{tag}: shapes {sims.shape} {ids.shape}, want ({m}, {k})")
    check(np.isfinite(sims).all() and (ids >= 0).all(),
          f"{tag}: empty slots in a corpus of millions")
    check(all(len(set(r)) == k for r in ids.tolist()),
          f"{tag}: duplicate ids in a row")
    prof = float(np.abs(sims - ref_s[:, :k]).max())
    check(prof <= TOL, f"{tag}: similarity profile off by {prof:.3e}")
    own = float(np.abs(sims - exact).max())
    check(own <= TOL, f"{tag}: returned ids score {own:.3e} away from "
                      f"their reported similarity")
    clear = (ref_s[:, k - 1] - ref_s[:, k]) > TOL
    bad = [r for r in np.flatnonzero(clear)
           if set(ids[r].tolist()) != set(ref_i[r, :k].tolist())]
    check(not bad, f"{tag}: id sets differ from the reference on queries "
                   f"{bad[:8]}")
    gone = set(deleted) & set(ids.ravel().tolist())
    check(not gone, f"{tag}: deleted ids returned: {sorted(gone)[:8]}")
    log(f"  {tag}: {m} queries exact (max |dsim| {prof:.2e}; id sets equal "
        f"on all {int(clear.sum())} with a k-th gap > {TOL:g})")


# ------------------------------------------------------------- phases


def timed(fn, *args, **kw):
    t = time.perf_counter()
    out = jax.block_until_ready(fn(*args, **kw))
    return out, time.perf_counter() - t


def peak_gb(dev) -> str:
    stats = dev.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "not reported" if peak is None else f"{peak / 1e9:.2f} GB"


def search_phase(eng, q, n_ids: int) -> float:
    """engine.search at every k: cold and warm wall time, 0 retraces warm,
    exact answers.  Returns the summed compile estimate (cold - warm)."""
    compile_s = 0.0
    for k in KS:
        (s, i, st), cold = timed(eng.search, q, k)
        check(st.backend == "kernel", f"search ran on {st.backend}")
        walls = []
        for _ in range(3):
            (s, i, st), w = timed(eng.search, q, k)
            check(st.retraces == 0, f"k={k}: warm call retraced "
                                    f"({st.retraces})")
            walls.append(w)
        warm = sorted(walls)[1]
        compile_s += cold - warm
        log(f"search k={k} m={q.shape[0]}: first call {cold:.3f} s, warm "
            f"batch {warm * 1e3:.2f} ms (median of 3), retraces 0, "
            f"block_prune_frac {float(st.block_prune_frac):.3f}")
        compare(f"search k={k}", s, i,
                reference(eng.index, q, i, k=k, n_ids=n_ids), k=k)
    return compile_s


def serve_phase(eng, q, n_ids: int) -> float:
    """A few hundred concurrent ContinuousBatcher.submit calls per k."""
    from repro.serve.frontend import ContinuousBatcher

    async def serve(k):
        async with ContinuousBatcher(eng, k, max_batch=MAX_BATCH) as b:
            outs = await asyncio.gather(*(b.submit(r) for r in qh))
            return outs, b.n_batches, b.occupancy

    qh = np.asarray(q)
    compile_s = 0.0
    for k in KS:
        t = time.perf_counter()
        outs, n_batches, occ = asyncio.run(serve(k))
        wall = time.perf_counter() - t
        sims = np.stack([o[0] for o in outs])
        ids = np.stack([o[1] for o in outs])
        # the batcher pads every microbatch to max_batch: one signature,
        # so a further batch of that shape must not retrace
        (_, _, st), warm = timed(eng.search, qh[:MAX_BATCH], k)
        check(st.retraces == 0, f"batcher k={k}: warm call retraced")
        compile_s += max(0.0, wall - n_batches * warm)
        log(f"batcher k={k}: {len(outs)} submits in {n_batches} microbatches "
            f"(occupancy {occ:.2f}) in {wall:.3f} s; warm microbatch "
            f"{warm * 1e3:.2f} ms")
        compare(f"batcher k={k}", sims, ids,
                reference(eng.index, q, jnp.asarray(ids), k=k, n_ids=n_ids),
                k=k)
    return compile_s


def online_phase(eng, db_rows, key, n_ids: int) -> None:
    """Insert fresh rows, read them back as their own nearest neighbours,
    delete some of them and some corpus rows, and check the deleted ids
    never come back."""
    handle = eng.online()
    new = np.asarray(jax.random.normal(key, (8, DIM), jnp.float32))
    new_ids = handle.insert(new)
    check(len(set(new_ids)) == 8 and min(new_ids) >= n_ids - 8,
          f"insert returned ids {new_ids}")
    k = KS[0]
    s, i, _ = eng.search(jnp.asarray(new), k)
    top = np.asarray(i)[:, 0].tolist()
    check(top == new_ids, f"inserted rows are not their own nearest "
                          f"neighbours: {top} vs {new_ids}")
    check(float(np.asarray(s)[:, 0].min()) > 1 - TOL,
          "inserted rows do not score 1 against themselves")
    compare("online insert", s, i, reference(eng.index, jnp.asarray(new), i,
                                             k=k, n_ids=n_ids), k=k)
    # delete half the inserted rows and the nearest corpus rows of a few
    # corpus-perturbed queries
    q = db_rows
    _, before, _ = eng.search(q, k)
    victims = sorted(set(np.asarray(before)[:, 0].tolist())) + new_ids[:4]
    handle.delete(victims)
    for kk in KS:
        for tag, qq in (("corpus", q), ("inserted", jnp.asarray(new))):
            s, i, _ = eng.search(qq, kk)
            compare(f"online delete k={kk} ({tag} queries)", s, i,
                    reference(eng.index, qq, i, k=kk, n_ids=n_ids), k=kk,
                    deleted=victims)
    log(f"online: inserted {len(new_ids)} rows (read back as their own "
        f"nearest), deleted {len(victims)} ids (never returned)")


def one_chip(seed: int) -> None:
    from repro.search import SearchEngine

    dev = jax.devices()[0]
    key = jax.random.PRNGKey(seed)
    k_db, k_q, k_new = jax.random.split(key, 3)
    n = ROWS_PER_CHIP
    t = time.perf_counter()
    db = jax.block_until_ready(make_corpus(k_db, n))
    gen_s = time.perf_counter() - t
    q = make_queries(k_q, db, QUERIES)
    q_serve = make_queries(jax.random.fold_in(k_q, 1), db, SUBMITS)
    t = time.perf_counter()
    eng = SearchEngine.build(db, bn=BN)
    jax.block_until_ready(eng.index)
    build_s = time.perf_counter() - t
    del db
    log(f"corpus {n} x {DIM} f32 ({n * DIM * 4 / 1e9:.2f} GB) generated in "
        f"{gen_s:.2f} s; build {build_s:.2f} s; peak HBM after build "
        f"{peak_gb(dev)}")
    check(eng.backend_name == "kernel",
          f"auto-selected backend {eng.backend_name!r}, want 'kernel'")
    check(eng.interpret is None and jax.default_backend() == "tpu",
          "the Pallas kernel would run in interpret mode")
    callee = eng.backend.make_fused(eng, KS[0], prune=True,
                                    element_stats=False, donate=False)
    t = time.perf_counter()
    hlo = callee.lower(eng.index, q).compile().as_text()
    check_s = time.perf_counter() - t
    check("tpu_custom_call" in hlo,
          "the compiled search holds no tpu_custom_call (kernel not "
          "compiled for the chip)")
    log(f"backend=kernel, interpret=False, compiled search holds the Pallas "
        f"kernel as a tpu_custom_call (compiled in {check_s:.2f} s)")
    n_ids = n + 8                                     # corpus + inserts
    compile_s = check_s + search_phase(eng, q, n_ids)
    compile_s += serve_phase(eng, q_serve, n_ids)
    online_phase(eng, q[: QUERIES // 2], k_new, n_ids)
    log(f"compile time (first calls minus warm calls, all search "
        f"signatures): {compile_s:.2f} s; peak HBM {peak_gb(dev)}")


def four_chips(seed: int) -> None:
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core.distributed import auto_mesh
    from repro.search import SearchEngine

    devs = jax.devices()
    check(len(devs) == 4, f"--four-chips needs 4 devices, found {len(devs)}")
    mesh = jax.make_mesh((4,), ("data",))       # what a user would pass
    k_db, k_q = jax.random.split(jax.random.PRNGKey(seed))
    n = 4 * ROWS_PER_CHIP
    t = time.perf_counter()
    db = jax.block_until_ready(make_corpus(
        k_db, n, sharding=NamedSharding(auto_mesh(mesh), P("data"))))
    gen_s = time.perf_counter() - t
    q = make_queries(k_q, db, QUERIES)
    t = time.perf_counter()
    eng = SearchEngine.build(db, mesh=mesh)
    jax.block_until_ready(eng.index)
    build_s = time.perf_counter() - t
    del db
    check(eng.backend_name == "sharded",
          f"auto-selected backend {eng.backend_name!r}, want 'sharded'")
    placed = {p.device: p.data.shape for p in eng.index.db.addressable_shards}
    check(len(placed) == 4 and all(s == (1, eng.n_slots // 4, DIM)
                                   for s in placed.values()),
          f"index shards are not one per chip: {placed}")
    log(f"corpus {n} x {DIM} f32 ({n * DIM * 4 / 1e9:.2f} GB) over 4 chips "
        f"generated in {gen_s:.2f} s; build {build_s:.2f} s, one shard of "
        f"{eng.n_slots // 4} rows built on each chip; peak HBM per chip "
        + ", ".join(peak_gb(d) for d in devs))
    for k in KS:
        (s, i, st), cold = timed(eng.search, q, k)
        (s, i, st), warm = timed(eng.search, q, k)
        check(st.retraces == 0, f"k={k}: warm call retraced")
        log(f"sharded search k={k} m={q.shape[0]}: first call {cold:.3f} s, "
            f"warm batch {warm * 1e3:.2f} ms, retraces 0, block_prune_frac "
            f"{float(st.block_prune_frac):.3f}")
        compare(f"sharded k={k}", s, i,
                reference(eng.index, q, i, k=k, n_ids=n, mesh=eng.mesh), k=k)
    log("peak HBM per chip " + ", ".join(peak_gb(d) for d in devs))


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded backend over 4 chips")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the generated corpus and queries")
    args = ap.parse_args(argv)

    check(os.path.isdir(os.path.join(ROOT, "src", "repro")),
          f"the repro package is not next to {__file__}")
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.compile_cache import use_compile_cache
    cache = use_compile_cache()               # before anything compiles

    dev = jax.devices()[0]
    check(dev.platform == "tpu",
          f"no TPU: JAX found platform {dev.platform!r}; this smoke runs "
          f"only on the chip")
    log(f"device {dev.device_kind} x{len(jax.devices())}, jax "
        f"{jax.__version__}, compile cache {cache}")
    if args.four_chips:
        four_chips(args.seed)
    else:
        one_chip(args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)


if __name__ == "__main__":
    main()
