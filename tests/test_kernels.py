"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ref as cref
from repro.core.index import build_index
from repro.kernels import ref as kref
from repro.kernels.bound_prune import block_bounds as bp_kernel
from repro.kernels.cosine_topk import (db_layout, pruned_topk, query_tile,
                                      tile_order)
from repro.kernels.tile_prescan import tau_prescan
from repro.search.backends import (kernel_search, map_row_ids, prep_queries,
                                   tau_warm_start)
from tests.conftest import clustered


def _raw_kernel(idx, q, k, **kw):
    """Fixed-policy kernel inner loop (the historical ``ops.search_index``
    surface: no τ warm-start, natural block order) -> (sims, ids,
    mean computed-tile fraction)."""
    qn, qp = prep_queries(idx, jnp.asarray(q))
    sims, pos, computed, _, _ = kernel_search(idx, qn, qp, k, **kw)
    return sims, map_row_ids(idx.row_ids, pos), computed.mean()


@pytest.mark.parametrize("m,nb,p", [(8, 4, 4), (37, 19, 12), (128, 64, 16),
                                    (256, 8, 8), (5, 100, 3)])
@pytest.mark.parametrize("dtype", [np.float32, np.float64])
def test_bound_prune_sweep(m, nb, p, dtype, rng):
    qp = np.clip(rng.normal(0, 0.5, size=(m, p)), -1, 1).astype(dtype)
    lo = np.clip(rng.uniform(-1, 0.5, size=(nb, p)), -1, 1).astype(dtype)
    hi = np.clip(lo + rng.uniform(0, 0.5, size=(nb, p)), -1, 1).astype(dtype)
    got = bp_kernel(jnp.asarray(qp), jnp.asarray(lo), jnp.asarray(hi),
                    bm=32, bb=32, interpret=True)
    want = kref.block_bounds(jnp.asarray(qp), jnp.asarray(lo), jnp.asarray(hi))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5 if dtype == np.float32 else 1e-6)


@pytest.mark.parametrize("n,d,k,bm,bn", [
    (512, 16, 4, 16, 128), (1024, 32, 9, 32, 256), (768, 48, 16, 8, 128),
])
def test_cosine_topk_sweep(n, d, k, bm, bn, rng):
    db = clustered(rng, n, d)
    q = clustered(rng, 40, d)
    idx = build_index(jnp.asarray(db), n_pivots=8, block_size=128)
    s_k, i_k, frac = _raw_kernel(idx, q, k, bm=bm, bn=bn)
    sref, iref = cref.brute_force_knn(q, db, k)
    np.testing.assert_allclose(np.asarray(s_k), sref, atol=3e-5)
    got = np.sort(np.asarray(i_k), 1)
    want = np.sort(iref, 1)
    assert (got == want).mean() > 0.98


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_cosine_topk_dtypes(dtype, rng):
    db = clustered(rng, 512, 32)
    q = clustered(rng, 16, 32)
    idx = build_index(jnp.asarray(db), n_pivots=8, block_size=128)
    idx = idx._replace(db=idx.db.astype(dtype))
    s_k, i_k, _ = _raw_kernel(idx, q, 5, bm=16)
    sref, _ = cref.brute_force_knn(q, db, 5)
    tol = 3e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(s_k), sref, atol=tol)


def test_pruning_engages_and_stays_exact(rng):
    db = clustered(rng, 4096, 32, n_centers=8, noise=0.04)
    # near-datastore queries (the kNN-LM/dedup regime): tau rises fast
    q = db[rng.choice(4096, 128, replace=False)]
    q = (q + 0.02 * rng.normal(size=q.shape).astype(np.float32))
    idx = build_index(jnp.asarray(db), n_pivots=16, block_size=128)
    s_p, i_p, frac_p = _raw_kernel(idx, q, 5, bm=16)
    s_n, i_n, frac_n = _raw_kernel(idx, q, 5, bm=16, prune=False)
    np.testing.assert_allclose(np.asarray(s_p), np.asarray(s_n), atol=1e-6)
    assert float(frac_n) == 1.0
    assert float(frac_p) < 0.9, f"expected pruning, computed {float(frac_p)}"


def test_query_sort_improves_pruning(rng):
    db = clustered(rng, 4096, 32, n_centers=8, noise=0.04)
    q = clustered(rng, 256, 32, n_centers=8, noise=0.04)
    idx = build_index(jnp.asarray(db), n_pivots=16, block_size=128)
    _, _, f_sorted = _raw_kernel(idx, q, 5, bm=16, sort_queries=True)
    _, _, f_unsorted = _raw_kernel(idx, q, 5, bm=16, sort_queries=False)
    assert float(f_sorted) <= float(f_unsorted) + 1e-6


def test_ops_search_index_removed(rng):
    """The deprecated wrapper is a hard error now, with the migration
    hint — it must not silently fall through to a legacy policy."""
    from repro.kernels import ops
    db = clustered(rng, 256, 16)
    idx = build_index(jnp.asarray(db), n_pivots=4, block_size=128)
    with pytest.raises(TypeError, match="SearchEngine"):
        ops.search_index(idx, jnp.asarray(db[:2]), 3)


def test_raw_kernel_interface(rng):
    """Direct pruned_topk call with hand-built intervals."""
    db = cref.normalize(rng.normal(size=(256, 16))).astype(np.float32)
    q = cref.normalize(rng.normal(size=(8, 16))).astype(np.float32)
    piv = db[:4]
    qp = (q @ piv.T).astype(np.float32)
    dp = (db @ piv.T).astype(np.float32)
    bn = 64
    lo = dp.reshape(-1, bn, 4).min(1)
    hi = dp.reshape(-1, bn, 4).max(1)
    s, i, computed, elem, _ = pruned_topk(
        jnp.asarray(q), jnp.asarray(db), jnp.asarray(qp), jnp.asarray(lo),
        jnp.asarray(hi), 256, k=4, bm=8, bn=bn, interpret=True)
    assert elem is None                     # element_stats off by default
    sref, iref = cref.brute_force_knn(q, db, 4)
    np.testing.assert_allclose(np.asarray(s), sref, atol=3e-5)
    assert (np.asarray(i) == iref).mean() > 0.98


def test_raw_kernel_element_counter(rng):
    """element_stats=True: per-tile pruned-element counts are sane and the
    result set is unchanged."""
    db = clustered(rng, 512, 16, n_centers=4, noise=0.05)
    q = db[:8] + 0.01 * rng.normal(size=(8, 16)).astype(np.float32)
    q = cref.normalize(q).astype(np.float32)
    piv = db[:: 512 // 8][:8]
    qp = (q @ piv.T).astype(np.float32)
    dp = (db @ piv.T).astype(np.float32)
    bn = 64
    lo = dp.reshape(-1, bn, 8).min(1)
    hi = dp.reshape(-1, bn, 8).max(1)
    s, i, computed, elem, _ = pruned_topk(
        jnp.asarray(q), jnp.asarray(db), jnp.asarray(qp), jnp.asarray(lo),
        jnp.asarray(hi), 512, dp=jnp.asarray(dp), k=4, bm=8, bn=bn,
        interpret=True, element_stats=True)
    sref, _ = cref.brute_force_knn(q, db, 4)
    np.testing.assert_allclose(np.asarray(s), sref, atol=3e-5)
    elem = np.asarray(elem)
    assert elem.shape == computed.shape
    assert (elem >= 0).all() and (elem <= 8 * bn).all()
    # clustered near-duplicate queries: τ rises fast, some elements prune
    assert elem.sum() > 0


def test_raw_kernel_k_beyond_one_lane_slab(rng):
    """k > 128 spans two 128-lane slabs of the in-kernel top-k block."""
    db = clustered(rng, 512, 16, n_centers=4, noise=0.05)
    q = cref.normalize(db[:8] + 0.01 * rng.normal(size=(8, 16))).astype(
        np.float32)
    piv = db[:4]
    qp = (q @ piv.T).astype(np.float32)
    dp = (db @ piv.T).astype(np.float32)
    bn = 256
    lo = dp.reshape(-1, bn, 4).min(1)
    hi = dp.reshape(-1, bn, 4).max(1)
    s, i, _, _, _ = pruned_topk(
        jnp.asarray(q), jnp.asarray(db), jnp.asarray(qp), jnp.asarray(lo),
        jnp.asarray(hi), 512, k=150, bm=8, bn=bn, interpret=True)
    sref, iref = cref.brute_force_knn(q, db, 150)
    np.testing.assert_allclose(np.asarray(s), sref, atol=3e-5)
    assert (np.sort(np.asarray(i), 1) == np.sort(iref, 1)).mean() > 0.98


def _replay_merge_rounds(qn, db, valid, k, bm, bn):
    """The kernel's merge rule in NumPy, every tile computed in natural
    order from ``-inf`` seeds: each round moves every row's best remaining
    tile score into its k slots while any row's best remaining score beats
    its k-th best.  Returns rounds summed over tiles / tiles."""
    scores = np.where(valid[None, :], qn @ db.T, -np.inf)
    rounds = tiles = 0
    for a in range(0, len(qn), bm):
        top = np.full((min(bm, len(qn) - a), k), -np.inf, np.float32)
        for b in range(0, db.shape[0], bn):
            s = scores[a:a + bm, b:b + bn].copy()
            tiles += 1
            while True:
                best, kth = s.max(axis=1), top.min(axis=1)
                take = np.flatnonzero(best > kth)
                if not len(take):
                    break
                rounds += 1
                for r in take:
                    top[r, np.argmin(top[r])] = best[r]
                    s[r, np.argmax(s[r])] = -np.inf
    return rounds / tiles


@pytest.mark.parametrize("k", [1, 10, 48])
def test_merge_rounds_match_a_replay_of_the_merge_rule(k, rng):
    """``SearchStats.merge_rounds`` counts the kernel's merge rounds: with
    pruning, warm start, best-first order and the query sort off, every
    tile is merged from empty slots in natural order, which NumPy replays
    (1000 rows: the last tile holds padding rows; 20 queries: the last
    query tile holds padding queries)."""
    from repro.search import SearchEngine

    db = clustered(rng, 1000, 16)
    q = clustered(rng, 20, 16)
    eng = SearchEngine.build(db, n_pivots=4, block_size=128,
                             backend="kernel", bm=8, bn=256,
                             warm_start=False, best_first=False,
                             sort_queries=False)
    _, _, stats = eng.search(jnp.asarray(q), k, prune=False)
    qn, _ = prep_queries(eng.index, jnp.asarray(q))
    want = _replay_merge_rounds(np.asarray(qn), np.asarray(eng.index.db),
                                np.asarray(eng.index.valid), k, 8, 256)
    assert want > 0
    assert float(stats.merge_rounds) == pytest.approx(want, rel=1e-6)


@pytest.mark.parametrize("dead", ["scattered", "sparse_tiles"])
@pytest.mark.parametrize("k", [10, 100])
@pytest.mark.parametrize("d", [96, 100])
def test_column_layout_is_exact(d, k, dead, rng):
    """A width that is not a multiple of 128 is read as ``db.T``: the
    τ prescan kernel gives ``tau_warm_start``'s seeds for the same tiles,
    and the kernel run on ``[D, N]`` blocks returns what it returns on
    ``[N, D]`` blocks, which is brute force over the valid rows.  Rows are
    tombstoned in place (scattered, or all but 60 of three of the four
    tiles, so that at k=100 a query's one prescanned tile can hold fewer
    than k valid rows: τ -inf); 20 queries leave the last query tile
    padded; d=100 widens the prescan to two tiles; the kernel visits the
    tiles in a shuffled order."""
    n, m, p, bm, bn = 1024, 20, 8, 8, 256
    nt, n_pre = n // bn, 1 if d == 96 else 2
    assert db_layout(d) == "cols"
    db = clustered(rng, n, d)
    db = db[np.argsort(db @ db[0])]        # tiles of differing bounds
    q = cref.normalize(db[rng.choice(n, m, replace=False)]
                       + 0.02 * rng.normal(size=(m, d))).astype(np.float32)
    valid = np.ones(n, bool)
    if dead == "scattered":
        valid[rng.choice(n, n // 10, replace=False)] = False
    else:
        for t in range(nt - 1):
            valid[t * bn + 60:(t + 1) * bn] = False
    piv = db[rng.choice(n, p, replace=False)]
    qp, dp = q @ piv.T, db @ piv.T
    lo = dp.reshape(nt, bn, p).min(1)
    hi = dp.reshape(nt, bn, p).max(1)
    qn, dbj, vj = jnp.asarray(q), jnp.asarray(db), jnp.asarray(valid)
    ub = kref.block_bounds(jnp.asarray(qp), jnp.asarray(lo), jnp.asarray(hi))

    want_tau = np.asarray(tau_warm_start(
        qn, dbj.reshape(nt, bn, d), vj.reshape(nt, bn), ub, k, n_pre))
    tau = np.asarray(tau_prescan(qn, dbj.T, vj, ub, k=k, n_pre=n_pre, bn=bn,
                                 interpret=True))
    np.testing.assert_array_equal(np.isneginf(tau), np.isneginf(want_tau))
    fin = np.isfinite(want_tau)
    np.testing.assert_allclose(tau[fin], want_tau[fin], rtol=0, atol=1e-6)
    if dead == "sparse_tiles" and k == 100 and n_pre == 1:
        assert np.isneginf(tau).any()
    # fewer candidates than k in the whole prescan: no seed
    assert np.isneginf(np.asarray(tau_prescan(
        qn, dbj.T, vj, ub, k=n_pre * bn + 1, n_pre=n_pre, bn=bn,
        interpret=True))).all()

    # any visiting order is exact; a shuffled one checks the tile ids
    order = jnp.asarray(np.stack([rng.permutation(nt) for _ in range(3)]),
                        jnp.int32)
    out = {}
    for layout, operand in (("rows", dbj), ("cols", dbj.T)):
        s, i, _, _, _ = pruned_topk(
            qn, operand, jnp.asarray(qp), jnp.asarray(lo), jnp.asarray(hi),
            int(valid.sum()), tau_init=jnp.asarray(tau), block_order=order,
            row_valid=vj, k=k, bm=bm, bn=bn, interpret=True,
            db_layout=layout)
        out[layout] = np.asarray(s), np.asarray(i)
    ref = np.where(valid[None, :], q.astype(np.float64) @ db.T.astype(
        np.float64), -np.inf)
    ref_i = np.argsort(-ref, axis=1)[:, :k]
    ref_s = np.take_along_axis(ref, ref_i, axis=1)
    s_cols, i_cols = out["cols"]
    np.testing.assert_array_equal(i_cols, out["rows"][1])
    np.testing.assert_allclose(s_cols, out["rows"][0], rtol=0, atol=1e-6)
    np.testing.assert_array_equal(np.sort(i_cols, 1), np.sort(ref_i, 1))
    np.testing.assert_allclose(s_cols, ref_s, rtol=0, atol=1e-6)


@pytest.mark.parametrize("bm", [32, 128])
@pytest.mark.parametrize("m", [1, 5, 64, 100, 130, 256])
def test_query_tile_follows_the_batch(m, bm, rng):
    """``bm`` is the largest query tile: a call of ``m`` rows runs
    ``ceil(m / bm)`` tiles of ``round_up(ceil(m / tiles), 8)`` rows.  The
    kernel, called directly and through ``kernel_search``, returns brute
    force's answers with the tile count's shapes; at m=64, bm=128 it
    returns what the same call with the queries padded by hand to one
    128-row tile returns, ids and counters alike (the sims within f32
    rounding: the CPU's dot may block a different M differently)."""
    tiles = -(-m // bm)
    rows = query_tile(m, bm)
    want = {(256, 128): 128, (64, 128): 64, (130, 128): 72, (5, 128): 8}
    assert rows == want.get((m, bm), rows)
    assert rows % 8 == 0 and rows <= bm and (tiles - 1) * rows < m <= (
        tiles * rows)

    n, d, k, bn, p = 1024, 16, 10, 128, 8
    nt = n // bn
    db = clustered(rng, n, d)
    q = cref.normalize(db[rng.choice(n, m)]
                       + 0.02 * rng.normal(size=(m, d))).astype(np.float32)
    ref = q.astype(np.float64) @ db.T.astype(np.float64)
    ref_i = np.sort(np.argsort(-ref, axis=1)[:, :k], 1)
    ref_s = -np.sort(-ref, axis=1)[:, :k]

    idx = build_index(jnp.asarray(db), n_pivots=p, block_size=128)
    qn, qp = prep_queries(idx, jnp.asarray(q))
    s, pos, computed, _, rounds = kernel_search(
        idx, qn, qp, k, bm=bm, bn=bn, warm_start=True, best_first=True)
    assert computed.shape == (tiles, nt) and rounds.shape == (tiles,)
    np.testing.assert_allclose(np.asarray(s), ref_s, rtol=0, atol=1e-5)
    np.testing.assert_array_equal(
        np.sort(np.asarray(map_row_ids(idx.row_ids, pos)), 1), ref_i)

    # the raw kernel over hand-built intervals, τ seeds and best-first order
    piv = db[:p]
    qp, dp = jnp.asarray(q @ piv.T), db @ piv.T
    lo = jnp.asarray(dp.reshape(nt, bn, p).min(1))
    hi = jnp.asarray(dp.reshape(nt, bn, p).max(1))
    qn, dbj = jnp.asarray(q), jnp.asarray(db)
    ub = kref.block_bounds(qp, lo, hi)
    tau = tau_warm_start(qn, dbj.reshape(nt, bn, d),
                         jnp.ones((nt, bn), bool), ub, k, 1)
    order = tile_order(ub, bm)
    assert order.shape == (tiles, nt)
    out = pruned_topk(qn, dbj, qp, lo, hi, n, tau_init=tau,
                      block_order=order, k=k, bm=bm, bn=bn, interpret=True)
    s, i, computed, _, rounds = map(np.asarray, out)
    assert computed.shape == (tiles, nt) and rounds.shape == (tiles,)
    np.testing.assert_allclose(s, ref_s, rtol=0, atol=1e-5)
    np.testing.assert_array_equal(np.sort(i, 1), ref_i)

    if (m, bm) == (64, 128):
        def pad(a, v):
            return jnp.pad(a, ((0, 128 - m),) + ((0, 0),) * (a.ndim - 1),
                           constant_values=v)
        whole = pruned_topk(
            pad(qn, 0.0), dbj, pad(qp, 1.0), lo, hi, n, m_valid=m,
            tau_init=pad(tau, -np.inf),
            block_order=tile_order(pad(ub, -np.inf), 128), k=k, bm=128,
            bn=bn, interpret=True)
        s_w, i_w, computed_w, _, rounds_w = map(np.asarray, whole)
        np.testing.assert_array_equal(i, i_w[:m])
        np.testing.assert_array_equal(computed, computed_w)
        np.testing.assert_array_equal(rounds, rounds_w)
        np.testing.assert_allclose(s, s_w[:m], rtol=0, atol=1e-6)
