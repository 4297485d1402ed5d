"""Block-pruned exact cosine kNN — the TPU-native adaptation of the paper.

The metric indexes the paper targets (VP-tree, LAESA, M-tree, ...) prune one
candidate at a time while walking pointer-based trees.  On TPU we keep the
*insight* — the Eq. 13 upper bound over cached pivot similarities proves that
a candidate cannot enter the top-k — but apply it at **block granularity** so
the surviving work stays dense and MXU-shaped (see DESIGN.md §2):

  build:   normalize db, pick P pivots, cache ``dp = db @ pivots.T`` and the
           per-block per-pivot interval ``[dp_min, dp_max]``.
  search:  stream blocks with ``lax.scan``; per (query, block) evaluate the
           interval upper bound; blocks below the running k-th-best τ are
           pruned.  Survivors get the exact ``q @ block.T`` matmul and a
           top-k merge.

Exactness: Eq. 13 is a true upper bound, and the interval maximum over a
block dominates every member's bound, so a pruned block provably contains no
true neighbor.  A ``margin`` (few ulps) guards fp32 rounding; the property
tests check bit-exact agreement of the result *set* with the fp64 oracle.

In this pure-JAX module the pruned matmul is still *computed* and masked
(XLA has no data-dependent skip) — the pruning statistics report what a real
TPU run skips; :mod:`repro.kernels.cosine_topk` is the Pallas kernel that
actually skips the work via ``@pl.when``.

The search entry points here are deprecated shims: the inner loops now live
behind :class:`repro.search.SearchEngine` (one backend-dispatched API with
τ warm-start and best-first block ordering); this module keeps the index
*structure* (build, bounds, reorder).
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import Array

from repro.core.bounds import joint_row_upper_bound, ub_mult
from repro.core.pivots import (normalize, orthonormal_pivot_basis,
                               select_pivots_maxmin, select_pivots_random)

__all__ = ["BlockIndex", "build_index", "search", "search_brute",
           "interval_upper_bound", "block_upper_bound", "reorder_perm",
           "multipivot_block_cap"]


class BlockIndex(NamedTuple):
    """Immutable search structure (a pytree of arrays; shapes are static).

    ``db`` is padded to a multiple of the block size; ``valid`` masks padding.
    ``dp_min/dp_max`` are the per-block pivot-similarity intervals
    ``[n_blocks, P]``; ``block_size = db.shape[0] // dp_min.shape[0]``.
    """

    db: Array        # [n_pad, d]  normalized, padded database
    dp: Array        # [n_pad, P]  database-to-pivot similarities
    pivots: Array    # [P, d]      normalized pivot vectors
    dp_min: Array    # [n_blocks, P]
    dp_max: Array    # [n_blocks, P]
    valid: Array     # [n_pad]     bool, False on padding rows
    row_ids: Array   # [n_pad]     original row id of each (possibly reordered) row
    # Joint multi-pivot bound tables (None on indexes built before PR 7; every
    # field defaults so old pytree shapes keep unflattening).  ``ortho`` is the
    # orthonormalized pivot basis U = R^-1 Z; beta = db @ U.T; beta_nsq the
    # cumulative squared prefix norms, so one table serves every n_pivots <= P.
    ortho: Array | None = None     # [P, d]
    beta: Array | None = None      # [n_pad, P]
    beta_nsq: Array | None = None  # [n_pad, P]  cumsum(beta**2, axis=1)

    @property
    def n_blocks(self) -> int:
        return self.dp_min.shape[0]

    @property
    def block_size(self) -> int:
        return self.db.shape[0] // self.n_blocks

    @property
    def n_pivots(self) -> int:
        return self.pivots.shape[0]

    @property
    def bound_table_width(self) -> int:
        """Max usable ``n_pivots`` for the joint bound (0 = no table)."""
        return 0 if self.ortho is None else self.ortho.shape[-2]


def build_index(
    db: Array,
    *,
    n_pivots: int = 16,
    block_size: int = 128,
    pivot_method: str = "maxmin",
    reorder: bool = True,
    seed: int = 0,
) -> BlockIndex:
    """Build the block index.  ``block_size`` should be a multiple of 128 on
    real TPU (MXU alignment); any value works functionally.

    ``reorder`` (beyond-paper optimization): permute rows so that each block
    is angularly coherent — rows group by their nearest pivot, descending
    similarity within the group.  Tight per-block pivot intervals are what
    turn the paper's per-point bound into an effective per-*block* bound;
    with natural (shuffled) order the intervals span nearly [-1, 1] and no
    block can ever be pruned.  Search results are returned in original ids
    via ``row_ids``.

    Every corpus-sized step runs on the device holding ``db`` inside a
    jitted call, and none keeps more than one corpus-sized buffer beside
    its input: the normalized, padded, reordered rows are produced by one
    gather, so the build peaks near twice the corpus and the host never
    holds a copy.
    """
    db = jnp.asarray(db, jnp.float32)
    n = db.shape[0]
    # More pivots than points is degenerate-but-reachable (tiny corpora /
    # shards): clamp so selection and the joint-bound tables stay defined.
    n_pivots = max(1, min(int(n_pivots), n))
    if pivot_method == "maxmin":
        piv_idx = select_pivots_maxmin(db, n_pivots)
    elif pivot_method == "random":
        piv_idx = select_pivots_random(n, n_pivots, seed)
    else:
        raise ValueError(f"unknown pivot_method {pivot_method!r}")
    index = _build_arrays(db, piv_idx, block_size=block_size,
                          reorder=reorder)
    # Joint multi-pivot bound tables: the basis is a float64 host
    # factorization of the [P, d] pivots; the [n_pad, P] coordinates are an
    # f32 HIGHEST matmul on the *reordered* rows, so beta[i] matches db[i]
    # (its rounding is absorbed by JOINT_SLACK).  maxmin selection is
    # nested, so prefix slices of these tables are exactly the tables a
    # shallower index would have built.
    ortho = jax.device_put(
        jnp.asarray(orthonormal_pivot_basis(index.pivots), jnp.float32),
        index.db.sharding)
    beta, beta_nsq = _joint_tables(index.db, ortho)
    return index._replace(ortho=ortho, beta=beta, beta_nsq=beta_nsq)


_HIGHEST = jax.lax.Precision.HIGHEST


@functools.partial(jax.jit, static_argnames=("block_size", "reorder"))
def _build_arrays(db: Array, piv_idx: Array, *, block_size: int,
                  reorder: bool) -> BlockIndex:
    """Normalize, pad, reorder and summarize ``db`` in one device call."""
    n = db.shape[0]
    n_pad = -(-n // block_size) * block_size
    # normalize()'s own arithmetic, applied row by row inside the gather
    # below instead of to a full-size copy
    norm = jnp.maximum(jnp.linalg.norm(db, axis=-1), 1e-12)
    pivots = db[piv_idx] / norm[piv_idx, None]               # [P, d] unit
    p = pivots.shape[0]
    if reorder:
        dp0 = jnp.dot(db, pivots.T, precision=_HIGHEST) / norm[:, None]
        dp0 = jnp.pad(dp0, ((0, n_pad - n), (0, 0)))
        perm = reorder_perm(dp0, jnp.arange(n_pad) < n, p)
    else:
        perm = jnp.arange(n_pad)
    valid = perm < n                                         # padding: zero rows
    dbn = (jnp.take(db, perm, axis=0, mode="fill", fill_value=0.0)
           / jnp.take(norm, perm, mode="fill", fill_value=1.0)[:, None])
    row_ids = jnp.where(valid, perm, -1).astype(jnp.int32)
    # the stored similarities are those of the stored rows, at the same
    # precision every search path scores with
    dp = jnp.dot(dbn, pivots.T, precision=_HIGHEST)         # [n_pad, P]
    # Padding rows are zero vectors => dp = 0; exclude them from the block
    # intervals so they can't loosen the bound.
    nb = n_pad // block_size
    dp_min = jnp.where(valid[:, None], dp, jnp.inf).reshape(
        nb, block_size, -1).min(axis=1)
    dp_max = jnp.where(valid[:, None], dp, -jnp.inf).reshape(
        nb, block_size, -1).max(axis=1)
    # A fully-padded block keeps the +inf/-inf identity of the masked
    # reduce: the *empty-interval sentinel*.  Every bound path maps an
    # inverted interval (lo > hi) to a -inf upper bound, so empty blocks
    # prune unconditionally, and — critically for the online path — an
    # insert's scatter-min/max against the sentinel records the new row's
    # EXACT interval instead of anchoring it at a neutral value.
    return BlockIndex(dbn, dp, pivots, dp_min, dp_max, valid, row_ids)


@jax.jit
def _joint_tables(dbn: Array, ortho: Array):
    beta = jnp.dot(dbn, ortho.T, precision=_HIGHEST)         # [n_pad, P]
    return beta, jnp.cumsum(beta * beta, axis=1)


def reorder_perm(dp: Array, valid: Array, n_pivots: int) -> Array:
    """Row permutation making blocks angularly coherent.

    Sorts by (nearest pivot asc, similarity to it desc), padding last —
    lexicographically, with the integer group key kept integer.  The old
    float key ``nearest * 4.0 - near_sim`` packed both into one fp32: at
    ``n_pivots = 64`` the key magnitude (~256) costs 8 bits of the
    similarity's mantissa, so within-group sims closer than ~3e-5 collapsed
    and the within-group descending order broke (regression-tested in
    tests/test_index.py).
    """
    nearest = jnp.argmax(dp, axis=1).astype(jnp.int32)
    near_sim = jnp.max(dp, axis=1)
    group = jnp.where(valid, nearest, n_pivots)   # padding after every group
    # lexsort: last key is primary
    return jnp.lexsort((-near_sim, group))


def interval_upper_bound(qp: Array, lo: Array, hi: Array) -> Array:
    """Max of Eq. 13 over ``b in [lo, hi]``, elementwise.

    ``ub(a, b) = cos(|arccos a − arccos b|)`` is maximal (=1) when ``b = a``
    is reachable; otherwise at the nearer interval end.  Shapes broadcast;
    the pivot axis is NOT reduced here.
    """
    at_ends = jnp.maximum(ub_mult(qp, lo), ub_mult(qp, hi))
    inside = (qp >= lo) & (qp <= hi)
    ub = jnp.where(inside, 1.0, at_ends)
    # inverted interval (lo > hi): the empty-block sentinel (+inf/-inf)
    # written for all-padding blocks — no reachable similarity, bound -inf.
    # (Raw ±inf through ub_mult yields NaN/+inf; jnp.where never leaks the
    # unselected branch, so the sentinel is mapped before anyone reduces.)
    return jnp.where(lo > hi, -jnp.inf, ub)


def block_upper_bound(qp: Array, dp_min: Array, dp_max: Array) -> Array:
    """Tightest block bound over pivots.

    qp: [m, P] query-pivot sims;  dp_min/dp_max: [P] one block's intervals.
    Returns [m]: ``min_p max_{b in [lo_p, hi_p]} ub_mult(qp_p, b)``.
    """
    per_pivot = interval_upper_bound(qp, dp_min[None, :], dp_max[None, :])
    return per_pivot.min(axis=-1)


def multipivot_block_cap(index: BlockIndex, qn: Array, *, n_pivots: int) -> Array:
    """Per-(query, block) joint multi-pivot upper bound ("cap").

    Projects the queries onto the first ``n_pivots`` rows of the index's
    orthonormalized pivot basis and takes, per block, the max of the joint
    row bound over the block's valid rows — a valid block bound because the
    max over members dominates each member (same argument as the interval
    bound).  Shrinks monotonically as ``n_pivots`` grows; at ``n_pivots = d``
    it equals the exact block max score.

    Args:
      index: a :class:`BlockIndex` with joint tables (``ortho is not None``).
      qn: [M, d] normalized queries.
      n_pivots: prefix depth ``1 <= n_pivots <= index.bound_table_width``.

    Returns [M, n_blocks] float32.
    """
    if index.ortho is None:
        raise ValueError("index has no joint bound tables (ortho is None)")
    j = int(n_pivots)
    if not 1 <= j <= index.bound_table_width:
        raise ValueError(
            f"n_pivots={j} outside [1, {index.bound_table_width}]")
    alpha = jnp.dot(qn.astype(jnp.float32), index.ortho[:j].T,
                    precision=_HIGHEST)                         # [M, j]
    row_ub = joint_row_upper_bound(
        alpha, index.beta[:, :j], index.beta_nsq[:, j - 1])     # [M, n_pad]
    row_ub = jnp.where(index.valid[None, :], row_ub, -jnp.inf)
    m = row_ub.shape[0]
    return row_ub.reshape(m, index.n_blocks, -1).max(axis=-1)


def search(*args, **kwargs):
    """Removed: use :class:`repro.search.SearchEngine`.

    This was the pre-engine entry point; it then spent one release as a
    DeprecationWarning shim over the ``scan`` backend and is now a hard
    error — silently executing with a legacy default policy (natural
    block order, no τ warm-start) made benchmark numbers incomparable
    with the engine's.  The migration table is in docs/search-api.md.
    """
    raise TypeError(
        "repro.core.index.search() was removed. Use "
        "repro.search.SearchEngine: "
        "eng = SearchEngine(index, backend='scan'); "
        "sims, ids, stats = eng.search(queries, k). The migration table "
        "is in docs/search-api.md.")


@functools.partial(jax.jit, static_argnames=("k",))
def search_brute(index: BlockIndex, queries: Array, k: int):
    """Brute-force exact top-k (baseline; also the correctness oracle shape)."""
    qn = normalize(jnp.asarray(queries, jnp.float32))
    scores = jnp.dot(qn, index.db.T, precision=_HIGHEST)
    scores = jnp.where(index.valid[None, :], scores, -jnp.inf)
    sims, idx = jax.lax.top_k(scores, k)
    idx = jnp.where(idx >= 0, index.row_ids[jnp.maximum(idx, 0)], -1)
    return sims, idx.astype(jnp.int32)
