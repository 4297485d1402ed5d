"""The sharded backend runs the kernel search in every shard: exact against
the fp64 brute force at both stored layouts (d=768 row-major, d=96
column-major), k=10 and k=100, pruning on and off; on a corpus whose
length does not split over the mesh (a zero-padded, mesh-split array) no
pad row is ever returned; a warm call retraces nothing; and on one shard
its stats are the one-chip kernel backend's, and over several shards the
psum-weighted sums of each shard's kernel counters.

One subprocess with four virtual CPU devices (the main test process keeps
exactly one) computes every case.  Off the TPU a shard runs the scan
unless the engine asks for the interpreted kernel (``interpret=True``),
as every engine here does, with shards above the brute-force size so that
the kernel is the inner loop.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

_PAYLOAD = """
import json
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.core import ref
from repro.core.distributed import auto_mesh
from repro.search import SearchEngine
from repro.search.backends import kernel_search, prep_queries

out = {}
mesh = jax.make_mesh((4,), ("data",))


def clustered(rng, n, d, noise):
    c = ref.normalize(rng.normal(size=(8, d)))
    return (c[rng.integers(0, 8, n)]
            + noise * rng.normal(size=(n, d))).astype(np.float32)


def check(eng, db, q, k, prune=True):
    s, i, st = eng.search(jnp.asarray(q), k, prune=prune)
    s, i = np.asarray(s), np.asarray(i)
    sref, iref = ref.brute_force_knn(q, db, k)
    return {"sim_err": float(np.abs(s - sref).max()),
            "ids_equal": bool((np.sort(i, 1) == np.sort(iref, 1)).all()),
            "max_id": int(i.max()), "min_id": int(i.min()),
            "inner": eng.shard_inner(k, prune),
            "kernel_stats": st.tile_computed_frac is not None
                            and st.merge_rounds is not None,
            "prune_frac": float(st.block_prune_frac)}


for d in (768, 96):
    rng = np.random.default_rng(d)
    db = clustered(rng, 4 * 600, d, 0.3 / np.sqrt(d))
    q = ref.normalize(db[::150] + 0.01 * rng.normal(size=(16, d))
                      ).astype(np.float32)
    eng = SearchEngine.build(db, n_pivots=8, block_size=128, mesh=mesh,
                             interpret=True)
    for k in (10, 100):
        for prune in (True, False):
            out[f"exact-{d}-{k}-{prune}"] = check(eng, db, q, k, prune)

    # 4 * 600 - 3 rows, every one scoring below 0 against every query: a
    # zero pad row left valid would score 0 and come first
    n = 4 * 600 - 3
    u = ref.normalize(rng.normal(size=(1, d)))
    neg = ref.normalize(-u + 0.3 * rng.normal(size=(n, d))).astype(np.float32)
    qu = ref.normalize(u + 0.05 * rng.normal(size=(8, d))).astype(np.float32)
    padded = np.concatenate([neg, np.zeros((3, d), np.float32)])
    rows = jax.device_put(padded, NamedSharding(auto_mesh(mesh), P("data")))
    eng = SearchEngine.build(rows, n_pivots=8, block_size=128, mesh=mesh,
                             global_rows=n, interpret=True)
    out[f"padded-{d}-n_valid"] = eng.n_valid
    for k in (10, 100):
        out[f"padded-{d}-{k}"] = check(eng, neg, qu, k)

    # the stats are sums of every shard's kernel counters over sums of
    # their denominators (the last shard holds 3 invalid rows)
    _, _, st = eng.search(jnp.asarray(qu), 10, element_stats=True)
    done = tiles = rounds = elem = valid = 0.0
    for s in range(4):
        local = jax.tree.map(lambda x: x[s], eng.index)
        qn, qp = prep_queries(local, jnp.asarray(qu))
        _, _, computed, ep, rd = kernel_search(
            local, qn, qp, 10, warm_start=eng.warm_start,
            best_first=eng.best_first, element_stats=True,
            warm_start_blocks=eng.warm_start_blocks, n_pivots=eng.n_pivots,
            interpret=True)
        done += float(computed.sum()); tiles += computed.size
        rounds += float(rd.sum()); elem += float(ep.sum())
        valid += float(np.asarray(local.valid).sum())
    out[f"psum-{d}"] = [
        [float(st.block_prune_frac), 1.0 - done / tiles],
        [float(st.tile_computed_frac), done / tiles],
        [float(st.merge_rounds), rounds / max(done, 1.0)],
        [float(st.elem_prune_frac), elem / (len(qu) * valid)]]

    # a warm call reuses the compiled callee
    eng.search(jnp.asarray(qu), 10)
    _, _, st = eng.search(jnp.asarray(qu), 10)
    out[f"retraces-{d}"] = st.retraces

# one shard: the stats are those of the one-chip kernel backend
rng = np.random.default_rng(1)
db = clustered(rng, 1000, 96, 0.03)
q = ref.normalize(db[::100] + 0.01 * rng.normal(size=(10, 96))
                  ).astype(np.float32)
one = SearchEngine.build(db, n_pivots=8, block_size=128,
                         mesh=jax.make_mesh((1,), ("data",)), interpret=True)
flat = SearchEngine.build(db, n_pivots=8, block_size=128, backend="kernel")
for k in (10, 100):
    got = [e.search(jnp.asarray(q), k) for e in (one, flat)]
    (s1, i1, a), (s2, i2, b) = got
    out[f"one-shard-{k}"] = {
        "inner": one.shard_inner(k),
        "same_answers": bool((np.asarray(s1) == np.asarray(s2)).all()
                             and (np.asarray(i1) == np.asarray(i2)).all()),
        "stats": [[float(getattr(x, f)) for x in (a, b)]
                  for f in ("block_prune_frac", "tile_computed_frac",
                            "merge_rounds")]}
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def results():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = SRC
    # pin the subprocess to the host platform: with a TPU plugin installed
    # but no TPU attached, backend autodetection stalls in metadata retries
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(_PAYLOAD)],
                         capture_output=True, text=True, env=env,
                         timeout=900)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("prune", [True, False], ids=["prune", "no_prune"])
@pytest.mark.parametrize("k", [10, 100])
@pytest.mark.parametrize("d", [768, 96], ids=["d768_rows", "d96_cols"])
def test_sharded_kernel_search_matches_brute(results, d, k, prune):
    r = results[f"exact-{d}-{k}-{prune}"]
    assert r["inner"] == "kernel" and r["kernel_stats"], r
    assert r["sim_err"] < 3e-5 and r["ids_equal"], r


@pytest.mark.parametrize("k", [10, 100])
@pytest.mark.parametrize("d", [768, 96], ids=["d768_rows", "d96_cols"])
def test_padded_corpus_never_returns_a_pad_row(results, d, k):
    n = 4 * 600 - 3
    assert results[f"padded-{d}-n_valid"] == n
    r = results[f"padded-{d}-{k}"]
    assert r["inner"] == "kernel" and r["kernel_stats"], r
    assert 0 <= r["min_id"] and r["max_id"] < n, r
    assert r["sim_err"] < 3e-5 and r["ids_equal"], r


@pytest.mark.parametrize("d", [768, 96], ids=["d768_rows", "d96_cols"])
def test_sharded_kernel_stats_are_psum_weighted(results, d):
    for got, want in results[f"psum-{d}"]:
        assert got == pytest.approx(want, rel=1e-6, abs=1e-7)


@pytest.mark.parametrize("d", [768, 96])
def test_warm_sharded_call_retraces_nothing(results, d):
    assert results[f"retraces-{d}"] == 0


@pytest.mark.parametrize("k", [10, 100])
def test_one_shard_reports_the_one_chip_kernel_stats(results, k):
    r = results[f"one-shard-{k}"]
    assert r["inner"] == "kernel" and r["same_answers"], r
    for sharded, flat in r["stats"]:
        assert sharded == pytest.approx(flat, rel=1e-6), r


def _stacked(rows: int, d: int, block: int = 128):
    """The shapes ``shard_inner`` reads of a four-shard index."""
    import types

    import jax
    return types.SimpleNamespace(
        db=jax.ShapeDtypeStruct((4, rows, d), "float32"),
        dp_min=jax.ShapeDtypeStruct((4, rows // block, 8), "float32"))


@pytest.mark.parametrize("rows, d, k, kw, want", [
    (2048, 768, 10, {}, "scan"),
    (2048, 768, 10, {"interpret": True}, "kernel"),
    (2048, 96, 100, {"interpret": True}, "kernel"),
    (2048, 768, 300, {"interpret": True}, "scan"),
    (2048, 8192, 10, {"interpret": True}, "scan"),
    (256, 768, 10, {"interpret": True}, "brute"),
    (256 * 128, 96, 10, {}, "tree"),
    (256 * 128, 96, 10, {"prune": False}, "scan"),
    (256 * 128, 96, 10, {"tree_shards": False}, "scan"),
    (2048, 96, 10, {"tree_shards": True}, "tree"),
], ids=["cpu-scan", "interpret-kernel", "interpret-k100", "k-over-tile",
        "too-wide", "tiny-brute", "deep-tree", "deep-no-prune",
        "tree-off", "tree-forced"])
def test_shard_inner_off_the_tpu(rows, d, k, kw, want):
    """Off the TPU a shard takes the rule a flat index takes (the scan or
    the tree), and the interpreted kernel only where ``interpret=True``
    asks for it; a ``k`` wider than the kernel's tile scans."""
    from repro.search.engine import shard_inner
    assert shard_inner(_stacked(rows, d), k, **kw) == want
