"""Online mutation (DESIGN.md §3.9): random interleavings of
insert/delete/search stay tie-aware brute-equal on the *live* corpus for
every backend, through the block-tail-full -> new-block transition and
across full reoptimizes.  The correctness argument under test is
conservative widening: inserts only loosen intervals (bounds stay true
upper bounds), tombstones mask per row before top-k."""
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.search import SearchEngine

BACKENDS = ["scan", "brute", "tree", "kernel"]
ATOL = 3e-5


def _norm64(x):
    x = np.asarray(x, np.float64)
    return x / np.linalg.norm(x, axis=-1, keepdims=True)


def _check_live_exact(eng, live, q, k):
    """Engine results == fp64 brute force over exactly the live rows.

    Tie-aware: similarities must match the sorted brute values, and every
    returned id must be live with a true similarity equal to the reported
    one (so any permutation of exact ties passes, but a tombstoned or
    hallucinated id never does)."""
    sims, ids, _ = eng.search(jnp.asarray(q), k)
    sims = np.asarray(sims, np.float64)
    ids = np.asarray(ids)
    live_ids = np.array(sorted(live))
    rows = _norm64(np.stack([live[i] for i in live_ids]))
    qn = _norm64(q)
    s = qn @ rows.T                                     # [m, n_live]
    kk = min(k, len(live_ids))
    want = -np.sort(-s, axis=1)[:, :kk]
    np.testing.assert_allclose(sims[:, :kk], want, atol=ATOL)
    assert (ids[:, kk:] == -1).all(), "past-the-corpus slots must pad -1"
    pos_of = {int(i): p for p, i in enumerate(live_ids)}
    for r in range(q.shape[0]):
        for c in range(kk):
            i = int(ids[r, c])
            assert i in pos_of, f"returned id {i} is not live"
            true = s[r, pos_of[i]]
            assert abs(true - sims[r, c]) < ATOL, (i, true, sims[r, c])


def _build(rows, backend, **kw):
    kw.setdefault("block_size", 32)
    kw.setdefault("n_pivots", 4)
    return SearchEngine.build(rows, backend=backend, **kw)


@settings(max_examples=8, deadline=None)
@given(st.sampled_from(BACKENDS), st.integers(0, 10_000))
def test_interleaved_mutations_stay_exact(backend, seed):
    rng = np.random.default_rng(seed)
    n, d, k = 220, 12, 6
    rows = rng.normal(size=(n, d)).astype(np.float32)
    eng = _build(rows, backend)
    h = eng.online(auto_reoptimize=False)
    live = {i: rows[i] for i in range(n)}
    q = rng.normal(size=(5, d)).astype(np.float32)
    _check_live_exact(eng, live, q, k)    # warm call (tree builds here)
    for _ in range(4):
        op = int(rng.integers(0, 3))
        if op == 0 or len(live) < k + 8:
            new = rng.normal(size=(int(rng.integers(1, 9)), d)).astype(
                np.float32)
            for i, r in zip(h.insert(new), new):
                live[i] = r
        elif op == 1:
            dead = rng.choice(sorted(live), size=5, replace=False)
            h.delete([int(x) for x in dead])
            for x in dead:
                del live[int(x)]
        else:
            h.reoptimize()
        _check_live_exact(eng, live, q, k)
    assert h.generation == 4


@pytest.mark.parametrize("backend", BACKENDS)
def test_tail_full_to_new_block_transition(backend, rng):
    """n a multiple of block_size -> zero free padded slots: the very
    first insert must append a fresh block (shape change, epoch bump) and
    stay exact; filling that block's tail exactly and inserting once more
    crosses the boundary again."""
    n, d, bs = 128, 8, 32
    rows = rng.normal(size=(n, d)).astype(np.float32)
    eng = _build(rows, backend, block_size=bs)
    h = eng.online(auto_reoptimize=False)
    live = {i: rows[i] for i in range(n)}
    q = rng.normal(size=(3, d)).astype(np.float32)
    _check_live_exact(eng, live, q, 4)
    assert not h._free, "a full index must have no free slots"

    epoch0 = eng.index_epoch
    one = rng.normal(size=(1, d)).astype(np.float32)
    live[h.insert(one)[0]] = one[0]
    assert eng.index_epoch == epoch0 + 1          # grew by one block
    assert eng.n_slots == (n // bs + 1) * bs
    _check_live_exact(eng, live, q, 4)

    tail = rng.normal(size=(bs - 1, d)).astype(np.float32)
    for i, r in zip(h.insert(tail), tail):        # fills the block exactly
        live[i] = r
    assert eng.index_epoch == epoch0 + 1          # shape-stable fills
    over = rng.normal(size=(2, d)).astype(np.float32)
    for i, r in zip(h.insert(over), over):        # crosses into block n+2
        live[i] = r
    assert eng.index_epoch == epoch0 + 2
    _check_live_exact(eng, live, q, 4)


@pytest.mark.parametrize("backend", BACKENDS)
def test_search_after_delete_of_former_topk_member(backend, rng):
    """Tombstone a row that was just returned as the top-1 neighbor: it
    must vanish from the next result set immediately (no rebuild), with
    the runner-up promoted — on every backend."""
    n, d = 160, 8
    rows = rng.normal(size=(n, d)).astype(np.float32)
    eng = _build(rows, backend)
    h = eng.online(auto_reoptimize=False)
    live = {i: rows[i] for i in range(n)}
    q = rows[17][None] + np.float32(0.01) * rng.normal(size=(1, d)).astype(
        np.float32)
    sims, ids, _ = eng.search(jnp.asarray(q), 3)
    top1 = int(np.asarray(ids)[0, 0])
    assert top1 == 17
    h.delete([top1])
    del live[top1]
    sims2, ids2, _ = eng.search(jnp.asarray(q), 3)
    assert top1 not in np.asarray(ids2)
    _check_live_exact(eng, live, q, 3)


def test_reoptimize_preserves_ids_and_repacks(rng):
    n, d, bs = 96, 8, 32
    rows = rng.normal(size=(n, d)).astype(np.float32)
    eng = _build(rows, "scan", block_size=bs)
    h = eng.online(auto_reoptimize=False)
    live = {i: rows[i] for i in range(n)}
    extra = rng.normal(size=(80, d)).astype(np.float32)
    for i, r in zip(h.insert(extra), extra):
        live[i] = r
    dead = list(range(0, n, 2))
    h.delete(dead)
    for x in dead:
        del live[x]
    slots_before = eng.n_slots
    assert h.decay_estimate > 0.5
    h.reoptimize()
    assert h.decay_estimate == 0.0
    assert eng.n_slots <= slots_before            # tombstones reclaimed
    assert h.n_live == len(live)
    q = rng.normal(size=(4, d)).astype(np.float32)
    _check_live_exact(eng, live, q, 5)            # same external ids
    # ids minted after a reoptimize continue the sequence, no reuse
    new = rng.normal(size=(1, d)).astype(np.float32)
    (nid,) = h.insert(new)
    assert nid == n + 80
    live[nid] = new[0]
    _check_live_exact(eng, live, q, 5)


def test_auto_reoptimize_triggers_at_threshold(rng):
    n, d = 64, 8
    rows = rng.normal(size=(n, d)).astype(np.float32)
    eng = _build(rows, "scan")
    h = eng.online(reoptimize_threshold=0.25)
    epoch0 = eng.index_epoch
    h.insert(rng.normal(size=(n // 4 + 1, d)).astype(np.float32))
    assert h.decay_estimate == 0.0                # rebuild already ran
    assert eng.index_epoch > epoch0
    assert eng.n_valid == n + n // 4 + 1


def test_delete_unknown_id_raises_before_any_change(rng):
    n, d = 64, 8
    rows = rng.normal(size=(n, d)).astype(np.float32)
    eng = _build(rows, "scan")
    h = eng.online()
    with pytest.raises(KeyError, match="not in the live set"):
        h.delete([3, 99999])
    assert 3 in h and h.n_live == n               # nothing was applied
    with pytest.raises(KeyError, match="duplicate"):
        h.delete([5, 5])
    assert 5 in h


def test_online_handle_is_singleton(rng):
    rows = rng.normal(size=(64, 8)).astype(np.float32)
    eng = _build(rows, "scan")
    h = eng.online(auto_reoptimize=False)
    assert eng.online() is h
    with pytest.raises(ValueError, match="first call"):
        eng.online(auto_reoptimize=True)


def test_appended_block_records_exact_interval(rng):
    """Satellite regression (PR 9): appended blocks used to seed
    ``dp_min = dp_max = 0`` and the insert's scatter-min/max anchored the
    interval at zero forever.  With the empty-interval sentinel the first
    rows record their EXACT per-pivot min/max."""
    n, d, bs = 64, 8, 32
    rows = rng.normal(size=(n, d)).astype(np.float32)
    eng = _build(rows, "scan", block_size=bs)
    h = eng.online(auto_reoptimize=False)
    live = {i: rows[i] for i in range(n)}
    assert not h._free, "a full index must have no free slots"

    # rows clustered on pivot 0: their similarity to it is ~1, so the
    # appended block's true dp_min for that pivot is strictly positive —
    # the zero anchor of the pre-fix code is unambiguously wrong here
    piv0 = np.asarray(eng.index.pivots)[0]
    part = (piv0[None] + 0.01 * rng.normal(size=(3, d))).astype(np.float32)
    for i, r in zip(h.insert(part), part):
        live[i] = r
    idx = eng.index
    dp_tail = np.asarray(idx.dp)[n:n + 3]         # the 3 inserted rows
    np.testing.assert_array_equal(np.asarray(idx.dp_min)[-1],
                                  dp_tail.min(axis=0))
    np.testing.assert_array_equal(np.asarray(idx.dp_max)[-1],
                                  dp_tail.max(axis=0))
    assert np.asarray(idx.dp_min)[-1, 0] > 0.5    # the anchor bug's tell

    # fill the block exactly; the interval must stay the exact min/max
    fill = (piv0[None] + 0.01 * rng.normal(size=(bs - 3, d))).astype(
        np.float32)
    for i, r in zip(h.insert(fill), fill):
        live[i] = r
    idx = eng.index
    dp_tail = np.asarray(idx.dp)[n:n + bs]
    np.testing.assert_array_equal(np.asarray(idx.dp_min)[-1],
                                  dp_tail.min(axis=0))
    np.testing.assert_array_equal(np.asarray(idx.dp_max)[-1],
                                  dp_tail.max(axis=0))
    q = rng.normal(size=(3, d)).astype(np.float32)
    _check_live_exact(eng, live, q, 5)


@pytest.mark.parametrize("backend", BACKENDS)
def test_delete_all_reoptimize_insert_round_trip(backend, rng):
    """Satellite regression (PR 9): an empty-live-set ``reoptimize()``
    returned before ``_apply_mutation``, so the engine kept its stale
    widened tree / dispatch caches and ``index_epoch`` never bumped.  The
    rebuild path is now uniform; the round trip must stay exact on every
    backend."""
    n, d, k = 96, 8, 4
    rows = rng.normal(size=(n, d)).astype(np.float32)
    eng = _build(rows, backend)
    h = eng.online(auto_reoptimize=False)
    q = rng.normal(size=(3, d)).astype(np.float32)
    _check_live_exact(eng, {i: rows[i] for i in range(n)}, q, k)  # warm
    h.delete(list(range(n)))
    epoch0 = eng.index_epoch
    h.reoptimize()
    assert eng.index_epoch == epoch0 + 1, \
        "empty reoptimize must bump the epoch like every other rebuild"
    assert eng._tree_index is None and not eng._fn_cache
    assert h.n_live == 0 and h.decay_estimate == 0.0
    sims, ids, _ = eng.search(jnp.asarray(q), k)
    assert (np.asarray(ids) == -1).all()
    assert np.all(np.asarray(sims) == -np.inf)
    new = rng.normal(size=(10, d)).astype(np.float32)
    live = {i: r for i, r in zip(h.insert(new), new)}
    _check_live_exact(eng, live, q, k)


def test_sharded_interleaved_mutations_stay_exact():
    """The tentpole, single-process: 8 virtual devices, random
    insert/delete/reoptimize interleavings on ``sharded`` and
    ``sharded_tree`` engines stay tie-aware brute-equal on the live
    corpus, and the id → (shard, slot) mirror matches the device
    ``row_ids`` across reoptimize."""
    from tests.test_distributed import _run
    _run("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.search import SearchEngine
        from repro.core.distributed import replicated_row_ids

        ATOL = 3e-5

        def norm64(x):
            x = np.asarray(x, np.float64)
            return x / np.linalg.norm(x, axis=-1, keepdims=True)

        def check(eng, live, q, k):
            sims, ids, st = eng.search(jnp.asarray(q), k)
            sims = np.asarray(sims, np.float64)
            ids = np.asarray(ids)
            live_ids = np.array(sorted(live))
            s = norm64(q) @ norm64(np.stack([live[i] for i in live_ids])).T
            kk = min(k, len(live_ids))
            want = -np.sort(-s, axis=1)[:, :kk]
            np.testing.assert_allclose(sims[:, :kk], want, atol=ATOL)
            assert (ids[:, kk:] == -1).all()
            pos = {int(i): p for p, i in enumerate(live_ids)}
            for r in range(q.shape[0]):
                for c in range(kk):
                    i = int(ids[r, c])
                    assert i in pos, f"returned id {i} is not live"
                    assert abs(s[r, pos[i]] - sims[r, c]) < ATOL
            return st

        mesh = jax.make_mesh((8,), ("data",))
        for tree_shards in (False, True):
            for seed in (0, 1):
                rng = np.random.default_rng(seed)
                n, d, k = 603, 16, 7
                rows = rng.normal(size=(n, d)).astype(np.float32)
                eng = SearchEngine.build(rows, mesh=mesh, n_pivots=4,
                                         block_size=16,
                                         tree_shards=tree_shards)
                assert eng.backend_name == "sharded"
                h = eng.online(auto_reoptimize=False)
                live = {i: rows[i] for i in range(n)}
                q = rng.normal(size=(4, d)).astype(np.float32)
                check(eng, live, q, k)          # warm: compile the closure
                for _ in range(5):
                    op = int(rng.integers(0, 3))
                    if op == 0 or len(live) < k + 16:
                        m = int(rng.integers(1, 12))
                        new = rng.normal(size=(m, d)).astype(np.float32)
                        for i, r in zip(h.insert(new), new):
                            live[i] = r
                    elif op == 1:
                        dead = rng.choice(sorted(live), size=7,
                                          replace=False)
                        h.delete([int(x) for x in dead])
                        for x in dead:
                            del live[int(x)]
                    else:
                        h.reoptimize()
                        rid = replicated_row_ids(eng.index, mesh)
                        want = {int(r): (s2, sl)
                                for s2 in range(rid.shape[0])
                                for sl, r in enumerate(rid[s2]) if r >= 0}
                        assert want == h._id_pos, "id map drifted"
                    check(eng, live, q, k)
                assert h.generation == 5
        print("OK")
    """)


def test_sharded_shape_stable_mutations_run_at_zero_retraces():
    """Shape-stable sharded mutations must keep the cached sharded
    executables (index flows as an argument): the search right after a
    tail insert or a tombstone delete reports ``retraces == 0``, on both
    the flat per-shard scan and the per-shard tree descent.  Growing a
    block bumps the epoch instead."""
    from tests.test_distributed import _run
    _run("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.search import SearchEngine
        rng = np.random.default_rng(7)
        n, d, k = 500, 16, 6
        rows = rng.normal(size=(n, d)).astype(np.float32)
        mesh = jax.make_mesh((8,), ("data",))
        for tree_shards in (False, True):
            eng = SearchEngine.build(rows, mesh=mesh, n_pivots=4,
                                     block_size=16,
                                     tree_shards=tree_shards)
            h = eng.online(auto_reoptimize=False)
            q = rng.normal(size=(3, d)).astype(np.float32)
            eng.search(q, k)                       # compile
            _, _, st = eng.search(q, k)
            assert st.retraces == 0
            epoch0 = eng.index_epoch
            ids = h.insert(rng.normal(size=(4, d)).astype(np.float32))
            assert eng.index_epoch == epoch0       # free tail slots exist
            _, _, st = eng.search(q, k)
            assert st.retraces == 0, (tree_shards, "insert", st.retraces)
            h.delete(ids[:2])
            _, _, st = eng.search(q, k)
            assert st.retraces == 0, (tree_shards, "delete", st.retraces)
            # exhaust every free slot -> the next insert appends one block
            # on every shard and must bump the epoch (one retrace after)
            free = sum(len(f) for f in h._free)
            h.insert(rng.normal(size=(free + 1, d)).astype(np.float32))
            assert eng.index_epoch == epoch0 + 1
            _, _, st = eng.search(q, k)
            assert st.retraces >= 1
            _, _, st = eng.search(q, k)
            assert st.retraces == 0
        print("OK")
    """)


def test_sharded_tree_online_prunes_at_least_flat():
    """Per-shard descent pruning stays a superset of the flat per-shard
    pruning after mutations: apply the SAME mutation sequence to a flat
    sharded engine and a tree_shards one; the tree engine's block-prune
    fraction must be >= the flat engine's on every following search."""
    from tests.test_distributed import _run
    _run("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.search import SearchEngine
        rng = np.random.default_rng(3)
        n, d, k = 640, 16, 5
        centers = rng.normal(size=(8, d))
        rows = (centers[rng.integers(0, 8, n)]
                + 0.05 * rng.normal(size=(n, d))).astype(np.float32)
        mesh = jax.make_mesh((8,), ("data",))
        engs = [SearchEngine.build(rows, mesh=mesh, n_pivots=4,
                                   block_size=16, tree_shards=ts)
                for ts in (False, True)]
        hs = [e.online(auto_reoptimize=False) for e in engs]
        q = (centers[rng.integers(0, 8, 4)]
             + 0.05 * rng.normal(size=(4, d))).astype(np.float32)
        new = (centers[rng.integers(0, 8, 20)]
               + 0.05 * rng.normal(size=(20, d))).astype(np.float32)
        dead = list(range(0, 40, 2))
        for e, h in zip(engs, hs):
            e.search(q, k)
            ids = h.insert(new)
            h.delete(dead)
            assert h._id_pos == hs[0]._id_pos      # identical placement
        stats = [e.search(q, k)[2] for e in engs]
        # the flat per-shard stage in the tree's unit, (query, index block)
        # pairs: each shard's scan over the flat engine's mutated index
        # (its 80-row shards take brute force, which counts no blocks)
        from repro.core.distributed import make_sharded_search
        f = engs[0]
        scan = make_sharded_search(
            f.mesh, with_stats=True, inner="scan", warm_start=f.warm_start,
            best_first=f.best_first, warm_start_blocks=f.warm_start_blocks,
            margin=f.margin, n_pivots=f.n_pivots)
        flat_blk = float(scan(f.index, jnp.asarray(q), k)[2]
                         ["block_prune_frac"])
        assert flat_blk > 0.0, flat_blk
        tree_blk = float(stats[1].block_prune_frac)
        assert stats[1].tree_prune_frac is not None
        assert tree_blk >= flat_blk - 1e-6, (tree_blk, flat_blk)
        print("OK")
    """)
