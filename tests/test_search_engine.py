"""SearchEngine: every backend x policy returns the brute-force result set;
auto-selection, stats shape, and the pruning wins of warm-start/best-first."""
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import ref
from repro.core.index import build_index
from repro.search import SearchEngine, SearchStats, available_backends
from tests.conftest import clustered

LOCAL_BACKENDS = ["scan", "kernel", "brute"]   # sharded needs a multi-dev mesh


def _sets_equal(ids, iref):
    return (np.sort(np.asarray(ids), 1) == np.sort(iref, 1)).mean()


def test_registry_has_all_backends():
    assert {"scan", "kernel", "sharded", "brute"} <= set(available_backends())


def test_auto_selection_cpu(rng):
    small = build_index(jnp.asarray(rng.normal(size=(100, 8)).astype(np.float32)),
                        n_pivots=4, block_size=32)
    big = build_index(jnp.asarray(rng.normal(size=(2000, 8)).astype(np.float32)),
                      n_pivots=4, block_size=64)
    assert SearchEngine(small).backend_name == "brute"
    assert SearchEngine(big).backend_name == "scan"   # CPU: no Mosaic


@pytest.mark.parametrize("backend", LOCAL_BACKENDS)
@pytest.mark.parametrize("warm_start,best_first",
                         [(False, False), (True, False), (False, True),
                          (True, True)])
def test_backends_match_brute_random(backend, warm_start, best_first, rng):
    db = rng.normal(size=(900, 24)).astype(np.float32)
    q = rng.normal(size=(17, 24)).astype(np.float32)
    idx = build_index(jnp.asarray(db), n_pivots=8, block_size=64)
    eng = SearchEngine(idx, backend=backend, warm_start=warm_start,
                       best_first=best_first, bm=8)
    s, i, stats = eng.search(jnp.asarray(q), 7)
    sref, iref = ref.brute_force_knn(q, db, 7)
    np.testing.assert_allclose(np.asarray(s), sref, atol=3e-5)
    assert _sets_equal(i, iref) > 0.98                # ties only
    assert isinstance(stats, SearchStats) and stats.backend == backend


def _adversarial(rng, n, d):
    """Tight duplicate-heavy clusters plus a thin uniform background: ties
    and near-ties everywhere a wrong bound, a stale τ seed, or a lossy
    merge would actually change the result set."""
    n_dup = n // 3
    base = clustered(rng, n - n_dup, d, n_centers=4, noise=0.01)
    dup = base[rng.integers(0, len(base), n_dup)] + 1e-4 * rng.normal(
        size=(n_dup, d)).astype(np.float32)
    x = np.concatenate([base, dup])
    return (x / np.linalg.norm(x, axis=1, keepdims=True)).astype(np.float32)


def _fp64_profile(q, db, ids):
    """Exact fp64 similarity profile of a returned id set, sorted desc.

    Two result sets are equivalent top-k answers iff their profiles are
    identical — this is tie-safe where raw id comparison is not."""
    qn = q.astype(np.float64)
    qn /= np.linalg.norm(qn, axis=1, keepdims=True)
    dbn = db.astype(np.float64)
    dbn /= np.linalg.norm(dbn, axis=1, keepdims=True)
    sims = np.einsum("md,mkd->mk", qn, dbn[np.maximum(np.asarray(ids), 0)])
    sims = np.where(np.asarray(ids) >= 0, sims, -np.inf)
    return -np.sort(-sims, axis=1)


@settings(max_examples=10, deadline=None)
@given(st.integers(60, 400), st.integers(3, 24), st.integers(1, 12),
       st.integers(0, 10_000))
def test_cross_backend_equivalence_property(n, d, k, seed):
    """THE cross-backend contract, one property: the same corpus through
    scan / kernel / tree / sharded (flat and per-shard tree) / brute
    returns identical scores and indices (indices compared exactly when
    the fp64 profile is tie-free, by profile equality otherwise).  This
    replaces the old per-backend pairwise checks — any backend diverging
    from any other fails here by transitivity through the fp64 oracle."""
    import jax
    rng = np.random.default_rng(seed)
    kind = seed % 3
    if kind == 0:
        db = rng.normal(size=(n, d)).astype(np.float32)
    elif kind == 1:
        db = clustered(rng, n, d)
    else:
        db = _adversarial(rng, n, d)
    k = min(k, n)
    q = rng.normal(size=(4, d)).astype(np.float32)
    idx = build_index(jnp.asarray(db), n_pivots=min(4, n), block_size=32)
    sref, iref = ref.brute_force_knn(q, db, k)          # fp64 oracle
    # a query's id set is uniquely determined iff its profile is tie-free
    # and strictly separated from the (k+1)-th best
    if k < n:
        s_next = ref.brute_force_knn(q, db, k + 1)[0][:, -1]
        sep = sref[:, -1] > s_next + 1e-9
    else:
        sep = np.ones(len(q), bool)
    tie_free = sep & (np.diff(sref, axis=1) < -1e-9).all(axis=1) \
        if k > 1 else sep

    mesh = jax.make_mesh((1,), ("data",))
    from repro.core.distributed import build_sharded_index, place_sharded_index
    sidx = place_sharded_index(
        build_sharded_index(db, 1, n_pivots=min(4, n), block_size=32), mesh)
    runs = {
        "brute": SearchEngine(idx, backend="brute"),
        "scan": SearchEngine(idx, backend="scan"),
        "kernel": SearchEngine(idx, backend="kernel", bm=8),
        "tree": SearchEngine(idx, backend="tree", bm=8),
        "sharded": SearchEngine(sidx, mesh=mesh, tree_shards=False),
        "sharded_tree": SearchEngine(sidx, mesh=mesh, tree_shards=True),
    }
    for name, eng in runs.items():
        s, i, _ = eng.search(jnp.asarray(q), k)
        msg = f"{name} n={n} d={d} k={k} seed={seed}"
        np.testing.assert_allclose(np.asarray(s), sref, atol=5e-5,
                                   err_msg=msg)
        np.testing.assert_allclose(_fp64_profile(q, db, i), sref,
                                   rtol=0, atol=1e-12, err_msg=msg)
        ids = np.sort(np.asarray(i), axis=1)
        np.testing.assert_array_equal(ids[tie_free],
                                      np.sort(iref, axis=1)[tie_free],
                                      err_msg=msg)


def test_warm_start_and_best_first_improve_pruning(rng):
    """The lifted kernel-only optimizations now help the scan backend too."""
    db = clustered(rng, 4096, 32, n_centers=8, noise=0.04)
    q = db[rng.choice(4096, 64, replace=False)]
    q = jnp.asarray(q + 0.02 * rng.normal(size=q.shape).astype(np.float32))
    idx = build_index(jnp.asarray(db), n_pivots=16, block_size=64)
    base = SearchEngine(idx, backend="scan", warm_start=False,
                        best_first=False)
    eng = SearchEngine(idx, backend="scan")
    _, _, st0 = base.search(q, 5)
    _, _, st1 = eng.search(q, 5)
    assert st1.block_prune_frac > st0.block_prune_frac, (
        st0.block_prune_frac, st1.block_prune_frac)

    kern0 = SearchEngine(idx, backend="kernel", bm=16, warm_start=False,
                         best_first=False)
    kern1 = SearchEngine(idx, backend="kernel", bm=16)
    _, _, kt0 = kern0.search(q, 5)
    _, _, kt1 = kern1.search(q, 5)
    assert kt1.tile_computed_frac <= kt0.tile_computed_frac + 1e-6


def test_warm_start_engages_beyond_block_size(rng):
    """k > block_size: the multi-block prescan seeds τ instead of the old
    auto-disable, results stay exact, and pruning measurably improves."""
    db = clustered(rng, 2048, 24, n_centers=6, noise=0.05)
    q = db[::256] + 0.01 * rng.normal(size=(8, 24)).astype(np.float32)
    idx = build_index(jnp.asarray(db), n_pivots=8, block_size=32)
    k = 48                                     # > block_size = 32
    sref, iref = ref.brute_force_knn(np.asarray(q), db, k)
    cold = SearchEngine(idx, backend="scan", warm_start=False,
                        best_first=False)
    warm = SearchEngine(idx, backend="scan", warm_start=True,
                        best_first=False)
    _, _, st0 = cold.search(jnp.asarray(q), k)
    s, i, st1 = warm.search(jnp.asarray(q), k)
    np.testing.assert_allclose(np.asarray(s), sref, atol=3e-5)
    assert _sets_equal(i, iref) > 0.98
    assert st1.block_prune_frac > st0.block_prune_frac, (
        st0.block_prune_frac, st1.block_prune_frac)


def test_warm_start_multiblock_seed_is_finite(rng):
    """The prescan covers ceil(k/bs) blocks, so every query gets a real
    τ seed even when k exceeds the block size."""
    from repro.kernels import ref as kref
    from repro.search.backends import (prep_queries, prescan_blocks,
                                       tau_warm_start)
    db = clustered(rng, 512, 16)
    idx = build_index(jnp.asarray(db), n_pivots=4, block_size=32)
    qn, qp = prep_queries(idx, jnp.asarray(db[:5]))
    nb, bs = idx.n_blocks, idx.block_size
    ub = kref.block_bounds(qp, idx.dp_min, idx.dp_max)
    db_blocks = idx.db.reshape(nb, bs, -1)
    valid_blocks = idx.valid.reshape(nb, bs)
    k = 3 * bs + 1
    n_pre = prescan_blocks(k, bs, nb)
    assert n_pre == 4                          # ceil(k / bs)
    tau = tau_warm_start(qn, db_blocks, valid_blocks, ub, k, n_pre)
    assert np.isfinite(np.asarray(tau)).all()
    # and each seed is a true lower bound on the final kth-best similarity
    sref, _ = ref.brute_force_knn(db[:5], db, k)
    assert (np.asarray(tau) <= sref[:, -1] + 1e-6).all()


def test_warm_start_blocks_widens_prescan(rng):
    """warm_start_blocks only ever widens: tighter or equal seeds, exact
    results."""
    db = clustered(rng, 2048, 24, n_centers=6, noise=0.05)
    q = db[::256] + 0.01 * rng.normal(size=(8, 24)).astype(np.float32)
    idx = build_index(jnp.asarray(db), n_pivots=8, block_size=64)
    sref, _ = ref.brute_force_knn(np.asarray(q), db, 10)
    narrow = SearchEngine(idx, backend="scan", best_first=False)
    wide = SearchEngine(idx, backend="scan", best_first=False,
                        warm_start_blocks=4)
    _, _, st_n = narrow.search(jnp.asarray(q), 10)
    s, _, st_w = wide.search(jnp.asarray(q), 10)
    np.testing.assert_allclose(np.asarray(s), sref, atol=3e-5)
    assert st_w.block_prune_frac >= st_n.block_prune_frac - 1e-6


def test_elem_prune_frac_scan_kernel_agree(rng):
    """Backend-uniform element stats: with matched granularity (bn = index
    block size, one query tile) the scan and kernel backends report the
    same elem_prune_frac on clustered data."""
    db = clustered(rng, 2048, 32, n_centers=6, noise=0.05)
    q = db[::64] + 0.01 * rng.normal(size=(32, 32)).astype(np.float32)
    idx = build_index(jnp.asarray(db), n_pivots=16, block_size=64)
    scan = SearchEngine(idx, backend="scan")
    kern = SearchEngine(idx, backend="kernel", bm=32, bn=64)
    _, _, st_s = scan.search(jnp.asarray(q), 10, element_stats=True)
    _, _, st_k = kern.search(jnp.asarray(q), 10, element_stats=True)
    es, ek = float(st_s.elem_prune_frac), float(st_k.elem_prune_frac)
    assert es > 0.3, es                        # clustered data must prune
    assert abs(es - ek) < 0.02, (es, ek)


def test_elem_prune_frac_reported_by_all_backends(rng):
    """element_stats=True yields a [0, 1] elem_prune_frac from every local
    backend (sharded covered in test_distributed.py), via the engine-level
    knob as well as the per-call override."""
    db = clustered(rng, 1024, 16)
    idx = build_index(jnp.asarray(db), n_pivots=8, block_size=64)
    for backend in LOCAL_BACKENDS:
        eng = SearchEngine(idx, backend=backend, bm=8, element_stats=True)
        _, _, stats = eng.search(jnp.asarray(db[:4]), 5)
        assert stats.elem_prune_frac is not None, backend
        assert 0.0 <= float(stats.elem_prune_frac) <= 1.0, backend
        # per-call override wins over the engine default
        _, _, off = eng.search(jnp.asarray(db[:4]), 5, element_stats=False)
        assert off.elem_prune_frac is None, backend


def test_stats_dict_compat(rng):
    db = clustered(rng, 1000, 16)
    idx = build_index(jnp.asarray(db), n_pivots=8, block_size=64)
    eng = SearchEngine(idx, backend="scan")
    _, _, stats = eng.search(jnp.asarray(db[:4]), 3, element_stats=True)
    assert stats["block_prune_frac"] == stats.block_prune_frac
    assert "elem_prune_frac" in stats.keys()
    d = stats.as_dict()
    assert d["backend"] == "scan" and 0.0 <= d["block_prune_frac"] <= 1.0
    with pytest.raises(KeyError):
        stats["nope"]


def test_stats_fraction_invariants(rng):
    """Every *_prune_frac / *_eval_frac / *_computed_frac is either None
    (the stage did not run) or a fraction in [0, 1]; a stage that did not
    run reports None, never a silent 0 — so dashboards can't mistake
    "not run" for "pruned nothing"."""
    db = clustered(rng, 1500, 16)
    idx = build_index(jnp.asarray(db), n_pivots=8, block_size=32)
    frac_fields = ("block_prune_frac", "tile_computed_frac",
                   "elem_prune_frac", "tree_prune_frac",
                   "tree_node_eval_frac")
    for backend in LOCAL_BACKENDS + ["tree"]:
        eng = SearchEngine(idx, backend=backend, bm=8)
        _, _, stats = eng.search(jnp.asarray(db[:5]), 6, element_stats=True)
        for name in frac_fields:
            v = getattr(stats, name)
            assert v is None or 0.0 <= float(v) <= 1.0, (backend, name, v)
        if backend != "tree":
            # absent tree stage: None, not 0.0
            assert stats.tree_prune_frac is None, backend
            assert stats.tree_node_eval_frac is None, backend
        else:
            assert stats.tree_prune_frac is not None
            assert stats.tree_node_eval_frac is not None
        if backend != "kernel":
            assert stats.tile_computed_frac is None, backend
            assert stats.merge_rounds is None, backend
        else:
            assert float(stats.merge_rounds) >= 0.0
        # element stats off: None, not 0.0 (brute reports 0.0 when ON —
        # the stage ran and pruned nothing, by definition)
        _, _, off = eng.search(jnp.asarray(db[:5]), 6, element_stats=False)
        assert off.elem_prune_frac is None, backend
        # prune=False: the descent never runs, so the tree fracs must be
        # None even on the tree backend — not a silent 0.0
        _, _, noprune = eng.search(jnp.asarray(db[:5]), 6, prune=False)
        assert noprune.tree_prune_frac is None, backend
        assert noprune.tree_node_eval_frac is None, backend
        # never-mutated engine: the online fields are None, not 0 — an
        # engine that HAS an online handle reports real host numbers
        assert stats.generation is None and stats.decay_estimate is None
        eng.online(auto_reoptimize=False).insert(db[:1])
        _, _, onl = eng.search(jnp.asarray(db[:5]), 6)
        assert onl.generation == 1 and 0.0 < onl.decay_estimate <= 1.0


def test_engine_build_convenience(rng):
    db = clustered(rng, 500, 16)
    eng = SearchEngine.build(db, n_pivots=8, block_size=32)
    s, i, stats = eng.search(jnp.asarray(db[:6]), 4)
    sref, iref = ref.brute_force_knn(db[:6], db, 4)
    np.testing.assert_allclose(np.asarray(s), sref, atol=3e-5)


def test_k_exceeds_valid_rows(rng):
    db = rng.normal(size=(40, 8)).astype(np.float32)
    idx = build_index(jnp.asarray(db), n_pivots=4, block_size=16)
    # (kernel excluded: it requires k <= bn, a documented tile constraint)
    for backend in ["scan", "brute"]:
        eng = SearchEngine(idx, backend=backend, bm=8)
        s, i, _ = eng.search(jnp.asarray(db[:2]), 40)
        sref, _ = ref.brute_force_knn(db[:2], db, 40)
        np.testing.assert_allclose(np.asarray(s), sref, atol=3e-5,
                                   err_msg=backend)


def test_unknown_backend_raises(rng):
    db = rng.normal(size=(64, 8)).astype(np.float32)
    idx = build_index(jnp.asarray(db), n_pivots=4, block_size=32)
    with pytest.raises(ValueError, match="unknown search backend"):
        SearchEngine(idx, backend="mosaic-gpu")


def test_sharded_backend_requires_mesh(rng):
    db = rng.normal(size=(64, 8)).astype(np.float32)
    idx = build_index(jnp.asarray(db), n_pivots=4, block_size=32)
    # a flat 2D index can't serve the sharded backend at all — the engine
    # now rejects the pairing at construction (clear error instead of an
    # opaque reshape TypeError mid-trace; tests/test_backend_edges.py has
    # the mesh-supplied variant of this regression)
    with pytest.raises(ValueError, match="mesh"):
        SearchEngine(idx, backend="sharded")
