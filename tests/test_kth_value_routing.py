"""Regression: every warm-start τ path keeps the TopkRewriter guard.

The PR 6 latency bug: ``lax.top_k(x, k)[0][:, -1]`` folds into a
``[k-1:k]`` slice, XLA's TopkRewriter no longer matches, and the line
silently lowers to a full O(n log n) sort (~10x at [64, 128]).  The
sanctioned guard is ``repro.kernels.ref.kth_value`` (barrier, then
slice); ``search/tree.py`` and ``dist/collectives.py`` carry the same
barrier inline at their tuple-unpack sites because they need the whole
[m, k] block, not just its k-th column.

repro-lint R001 catches the *syntactic* pattern; these tests pin the
*semantic* property — each warm-start path's jaxpr still contains the
``optimization_barrier`` that keeps the rewrite alive, and the flat prescan
still routes through ``kth_value`` itself — so a refactor cannot drop
the guard while keeping the naive slice out of R001's sight.
"""
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.index import build_index
from repro.dist.collectives import global_tau_merge
from repro.dist.compat import shard_map
from repro.kernels import ref as kref
from repro.search import backends, build_tree
from repro.search.backends import prep_queries
from repro.search.tree import tree_warm_start

K = 8


def _jaxpr_has_barrier(fn, *args) -> bool:
    return "optimization_barrier" in str(jax.make_jaxpr(fn)(*args))


def _small_tree(seed=0, n=256, d=8):
    rng = np.random.default_rng(seed)
    db = rng.normal(size=(n, d)).astype(np.float32)
    idx = build_index(jnp.asarray(db), n_pivots=4, block_size=32)
    return idx, build_tree(idx)


def test_kth_value_keeps_barrier():
    scores = jnp.ones((4, 64), jnp.float32)
    assert _jaxpr_has_barrier(lambda s: kref.kth_value(s, K), scores)


def test_tree_warm_start_keeps_barrier():
    idx, tree = _small_tree()
    qn, qp = prep_queries(idx, jnp.ones((3, idx.db.shape[1]), jnp.float32))
    assert _jaxpr_has_barrier(
        lambda a, b: tree_warm_start(tree, a, b, K, width=2), qn, qp)


def test_global_tau_merge_keeps_barrier():
    mesh = Mesh(np.array(jax.devices("cpu")[:1]), ("shards",))
    merged = shard_map(
        lambda s, v: global_tau_merge(s, v, K, "shards"),
        mesh=mesh, in_specs=(P(), P()), out_specs=P(), check_vma=False)
    sims = jnp.linspace(0.0, 1.0, 3 * K).reshape(3, K)
    valid = jnp.ones((3, K), bool)
    assert _jaxpr_has_barrier(merged, sims, valid)
    # and the merge is still exact about real-candidate counts
    tau = merged(sims, valid)
    np.testing.assert_allclose(np.asarray(tau),
                               np.asarray(jnp.sort(sims, axis=1)[:, 0]))


def test_flat_prescan_routes_through_kth_value(monkeypatch):
    idx, _ = _small_tree()
    calls = []
    real = kref.kth_value

    def counting(scores, k):
        calls.append((scores.shape, k))
        return real(scores, k)

    # backends.py does `from repro.kernels import ref as kref`: patching
    # the module attribute is seen by tau_warm_start at call time
    monkeypatch.setattr(backends.kref, "kth_value", counting)
    nb, bs = idx.n_blocks, idx.block_size
    qn, qp = prep_queries(idx, jnp.ones((3, idx.db.shape[1]), jnp.float32))
    ub = jnp.ones((3, nb), jnp.float32)
    db_blocks = idx.db.reshape(nb, bs, -1)
    valid_blocks = idx.valid.reshape(nb, bs)
    tau = backends.tau_warm_start(qn, db_blocks, valid_blocks, ub, K,
                                  n_pre=2)
    assert calls, "tau_warm_start no longer routes through kref.kth_value"
    assert tau.shape == (3,)
