"""Pivot-tree exact search: the paper's tree-index claim, realized on arrays.

The paper's central promise (§4) is that its cosine triangle inequality
makes Cosine usable with *hierarchical* metric indexes — VP-trees, M-trees
— where the bound is applied **transitively**: one Eq. 13 evaluation at an
internal node prunes an entire subtree, not just one block.  After the
flat block engine (DESIGN.md §2) this module closes that gap with a
TPU-shaped tree:

* **Leaves are the block index's blocks.**  ``build_index`` already groups
  rows by nearest pivot (angularly coherent blocks with tight per-pivot
  similarity intervals); consecutive blocks are therefore angularly close,
  so a balanced binary tree over consecutive block *ranges* gives every
  internal node a meaningful interval.
* **Array encoding, not pointers.**  The tree is a heap: node 1 is the
  root, node ``i`` has children ``2i`` / ``2i+1``, leaves occupy slots
  ``[nl, 2nl)`` with ``nl`` the block count padded to a power of two.
  Per-node caches are two ``[2·nl, P]`` arrays (``node_lo`` / ``node_hi``,
  the union of descendant pivot intervals) plus a validity mask — build
  and batched descent are pure `jnp` and stay ``jit``-compatible.
* **Transitive pruning.**  A node's interval contains every descendant's
  interval, so its Eq. 13 interval bound dominates every descendant
  similarity: ``ub(node) < τ`` proves the whole subtree empty of top-k
  candidates.  The descent is level-synchronous (a boolean frontier per
  query), so it is one masked vector op per level instead of a pointer
  walk — DESIGN.md §3.5.
* **Leaves reuse the flat engine.**  Surviving leaves are handed to the
  existing inner loops: the ``scan`` loop (via its ``leaf_mask`` /
  ``ub_all`` / ``tau0`` hooks) or the Pallas kernel via the leaf-gather
  entry point (:mod:`repro.kernels.leaf_gather`), so τ warm-start,
  best-first ordering and element-stats plumbing all carry over.

Exactness: τ₀ seeds are true lower bounds on each query's final k-th best
(k-th best of *real* scored candidates), the node bound dominates every
descendant similarity, and the leaf stage is the already-property-tested
flat engine — so ``backend="tree"`` returns the identical result set to
brute force (tests/test_tree.py pins this with hypothesis sweeps).
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import Array

from repro.core.index import (BlockIndex, interval_upper_bound,
                              multipivot_block_cap)
from repro.kernels import ref as kref
from repro.search import backends as _bk

__all__ = ["TreeIndex", "ShardTreeArrays", "build_tree", "build_shard_trees",
           "tree_warm_start", "tree_warm_start_topk", "tree_descend",
           "tree_search", "widen_tree", "widen_shard_trees"]


class TreeIndex(NamedTuple):
    """Array-encoded balanced pivot tree over a :class:`BlockIndex`.

    Heap layout: node 1 is the root, node ``i`` has children ``2i`` and
    ``2i+1``; leaves sit at ``[nl, 2·nl)`` where ``nl`` is the block count
    rounded up to a power of two (leaf slot ``s`` = index block ``s`` for
    ``s < n_blocks``, invalid padding after).  ``node_lo`` / ``node_hi``
    cache the union of descendant per-pivot similarity intervals — the
    transitive Eq. 13 bound is evaluated on them exactly like a block
    bound.  A pytree of arrays: nests inside ``jit`` like the index does.
    """

    index: BlockIndex
    node_lo: Array     # [2*nl, P] union-of-descendants interval lower ends
    node_hi: Array     # [2*nl, P] union-of-descendants interval upper ends
    node_valid: Array  # [2*nl]    bool, True iff the subtree holds a real row

    @property
    def n_leaf_slots(self) -> int:
        return self.node_valid.shape[0] // 2

    @property
    def n_levels(self) -> int:
        """Tree depth: leaves live ``n_levels`` below the root."""
        return self.n_leaf_slots.bit_length() - 1

    @property
    def n_blocks(self) -> int:
        return self.index.n_blocks

    @property
    def block_size(self) -> int:
        return self.index.block_size

    @property
    def n_valid_nodes(self) -> int:
        """Host int: nodes whose subtree holds a real row (for stats)."""
        return int(np.asarray(self.node_valid).sum())


def _next_pow2(x: int) -> int:
    return 1 << (x - 1).bit_length() if x > 1 else 1


@functools.partial(jax.jit, static_argnames=("nl",))
def _tree_arrays(dp_min: Array, dp_max: Array, block_valid: Array, *, nl: int):
    """Bottom-up interval union into heap-ordered node arrays."""
    nb, p = dp_min.shape
    lo = jnp.full((2 * nl, p), jnp.inf, jnp.float32)
    hi = jnp.full((2 * nl, p), -jnp.inf, jnp.float32)
    valid = jnp.zeros((2 * nl,), bool)
    lo = lo.at[nl:nl + nb].set(
        jnp.where(block_valid[:, None], dp_min, jnp.inf))
    hi = hi.at[nl:nl + nb].set(
        jnp.where(block_valid[:, None], dp_max, -jnp.inf))
    valid = valid.at[nl:nl + nb].set(block_valid)
    sz = nl // 2
    while sz >= 1:
        c_lo = lo[2 * sz:4 * sz].reshape(sz, 2, p)
        c_hi = hi[2 * sz:4 * sz].reshape(sz, 2, p)
        c_va = valid[2 * sz:4 * sz].reshape(sz, 2)
        lo = lo.at[sz:2 * sz].set(c_lo.min(axis=1))
        hi = hi.at[sz:2 * sz].set(c_hi.max(axis=1))
        valid = valid.at[sz:2 * sz].set(c_va.any(axis=1))
        sz //= 2
    # empty subtrees keep the ±inf identity of the masked reduce — the same
    # empty-interval sentinel build_index writes for all-padding blocks.
    # Bound paths map an inverted interval to -inf (and node_valid masks
    # these nodes anyway), while widen_tree's scatter-min/max records the
    # first insert's EXACT interval instead of re-anchoring it at zero.
    return lo, hi, valid


def build_tree(index: BlockIndex) -> TreeIndex:
    """Build the balanced pivot tree over ``index``'s blocks.

    Cost is one min/max reduce per level over the cached block intervals —
    negligible next to ``build_index`` itself.  Shard-stacked indexes are
    not supported (the ``sharded`` backend owns those).
    """
    if index.db.ndim != 2:
        raise ValueError("build_tree needs a single-shard BlockIndex; "
                         "shard-stacked indexes are served by the 'sharded' "
                         "backend")
    nb, bs = index.n_blocks, index.block_size
    block_valid = index.valid.reshape(nb, bs).any(axis=1)
    nl = _next_pow2(nb)
    lo, hi, valid = _tree_arrays(index.dp_min, index.dp_max, block_valid,
                                 nl=nl)
    return TreeIndex(index, lo, hi, valid)


def widen_tree(tree: TreeIndex, index: BlockIndex, blocks: Array,
               dp_rows: Array) -> TreeIndex:
    """Conservatively widen the node interval caches along the root-to-leaf
    paths of freshly inserted rows (the online mutation path, DESIGN.md
    §3.9).

    Args:
      tree: the current :class:`TreeIndex` (its heap shape must match
        ``index`` — shape-changing mutations rebuild the tree instead).
      index: the post-insert :class:`BlockIndex` the widened tree serves.
      blocks: [r] i32 block id of each inserted row.
      dp_rows: [r, P] the inserted rows' pivot similarities.

    Every node on an affected path has its ``[node_lo, node_hi]`` union
    interval widened to contain the new rows' pivot similarities and is
    marked valid.  Widening only ever *loosens* intervals, so every Eq. 13
    node bound stays a true upper bound over its (grown) subtree — pruning
    degrades gracefully, exactness is untouched.  Scatter-min/max handles
    several inserts landing in the same block in one shot.
    """
    nl = tree.n_leaf_slots
    lo, hi, valid = tree.node_lo, tree.node_hi, tree.node_valid
    node = blocks.astype(jnp.int32) + nl
    for _ in range(tree.n_levels + 1):        # leaf ... root, inclusive
        lo = lo.at[node].min(dp_rows)
        hi = hi.at[node].max(dp_rows)
        valid = valid.at[node].set(True)
        node = node // 2
    return TreeIndex(index, lo, hi, valid)


def widen_shard_trees(tree: "ShardTreeArrays", blocks: Array,
                      dp_rows: Array, mask: Array) -> "ShardTreeArrays":
    """Per-shard :func:`widen_tree`: conservatively widen every shard's
    node caches along the root-to-leaf paths of its freshly inserted rows
    (the sharded online mutation path, DESIGN.md §3.10).

    Args:
      tree: shard-stacked node caches ``[S, 2·nl, P]`` / ``[S, 2·nl]``.
      blocks: [S, R] i32 per-shard block ids of the inserted rows, padded
        to a uniform width R across shards.
      dp_rows: [S, R, P] the rows' LOCAL pivot similarities (each shard's
        own pivots — the quantities its intervals cache).
      mask: [S, R] bool, False for the padding entries of short shards.

    Masked entries scatter to the out-of-range sentinel node ``2·nl`` and
    are dropped, so shards receiving fewer (or zero) rows this call stay
    untouched.  The widening argument is the flat one, applied per shard:
    every affected node's union interval grows to contain the new rows'
    similarities, so each shard's transitive Eq. 13 bounds stay true upper
    bounds over its (grown) subtrees.  Run under ``jit`` with the tree's
    own ``out_shardings`` so each device widens only its local tree.
    """
    two_nl = tree.node_valid.shape[1]
    nl = two_nl // 2
    levels = nl.bit_length() - 1

    def one(lo, hi, valid, blk, dp, mk):
        node = jnp.where(mk, blk.astype(jnp.int32) + nl, two_nl)
        for _ in range(levels + 1):        # leaf ... root, inclusive
            lo = lo.at[node].min(dp, mode="drop")
            hi = hi.at[node].max(dp, mode="drop")
            valid = valid.at[node].set(True, mode="drop")
            node = jnp.where(mk, node // 2, two_nl)
        return lo, hi, valid

    lo, hi, valid = jax.vmap(one)(tree.node_lo, tree.node_hi,
                                  tree.node_valid, blocks, dp_rows, mask)
    return ShardTreeArrays(lo, hi, valid)


class ShardTreeArrays(NamedTuple):
    """Per-shard tree node caches for the ``sharded`` backend.

    The same heap layout as :class:`TreeIndex` with a leading shard axis
    ``[S, ...]`` — one independent tree per shard, built over that shard's
    *local* pivots and blocks.  Kept separate from :class:`TreeIndex` so
    the shard_map closure can take ``(index, queries, tree_arrays)``
    without duplicating the index inside the tree pytree; inside the shard
    body the two recombine into a local :class:`TreeIndex`.
    """

    node_lo: Array     # [S, 2*nl, P]
    node_hi: Array     # [S, 2*nl, P]
    node_valid: Array  # [S, 2*nl]


def build_shard_trees(index: BlockIndex) -> ShardTreeArrays:
    """Build one pivot tree per shard of a stacked :class:`BlockIndex`.

    ``index`` must carry the leading shard axis produced by
    ``build_sharded_index`` (all shards share static shapes, so every
    shard's heap has the same ``nl`` and the result is one stacked array
    per cache).  Pure ``vmap`` over the per-shard interval caches — place
    the result with the same ``NamedSharding`` as the index so each device
    materializes only its own tree.  The ``sharded`` backend does this by
    calling the build under ``jit`` with explicit ``out_shardings``, which
    also makes it legal on a multi-host index (whose leaves are not
    addressable outside jit) with each host computing only its own
    shards' trees.
    """
    if index.db.ndim != 3:
        raise ValueError("build_shard_trees needs a shard-stacked BlockIndex "
                         "(leading [S, ...] axis from build_sharded_index); "
                         "single-shard indexes are served by build_tree")
    s, n_pad, _ = index.db.shape
    nb = index.dp_min.shape[1]
    bs = n_pad // nb
    block_valid = index.valid.reshape(s, nb, bs).any(axis=2)
    nl = _next_pow2(nb)
    lo, hi, valid = jax.vmap(
        lambda a, b, c: _tree_arrays(a, b, c, nl=nl))(
            index.dp_min, index.dp_max, block_valid)
    return ShardTreeArrays(lo, hi, valid)


def _gathered_bounds(qp: Array, lo: Array, hi: Array) -> Array:
    """Eq. 13 interval bound for per-query node gathers.

    qp: [m, P]; lo/hi: [m, W, P] -> [m, W].
    """
    per_pivot = interval_upper_bound(qp[:, None, :], lo, hi)
    return per_pivot.min(axis=-1)


def tree_warm_start_topk(tree: TreeIndex, qn: Array, qp: Array, k: int,
                         width: int):
    """Beam-descend to ``width`` best-bound leaves; return the candidate
    top-k, not just its k-th value.

    The flat engine's prescan (DESIGN.md §3.4) ranks *all* block bounds to
    pick its candidates; here the candidate leaves are found the way a
    metric tree finds them — a best-first descent.  A beam of ``width``
    nodes starts at the root; each level expands to the ``2·width``
    children and keeps the ``width`` highest Eq. 13 interval bounds, so
    only ``2·width·depth`` bounds are evaluated instead of ``n_blocks``.
    The reached leaves are exact-scored in one batched gather+matmul.

    Returns ``(scores [m, k], valid [m, k])``: the k highest exact
    similarities among the reached real candidates, descending, padded
    with ``-inf`` / ``valid=False`` when fewer than k real candidates were
    reached.  Single-device callers reduce this to a τ seed with
    :func:`tree_warm_start`; the ``sharded`` backend instead all-gathers
    the per-shard candidate lists and takes the k-th best of the *union*,
    which is what makes the broadcast τ a valid global bound even when
    individual shards hold fewer than k candidates (DESIGN.md §3.6).
    """
    idx = tree.index
    m = qp.shape[0]
    nl, depth = tree.n_leaf_slots, tree.n_levels
    nb, bs = idx.n_blocks, idx.block_size
    w = max(1, min(width, nb))
    # node id 0 is the empty sentinel (node_valid[0] is False)
    beam = jnp.zeros((m, w), jnp.int32).at[:, 0].set(1)
    for _ in range(depth):
        left = jnp.where(beam > 0, 2 * beam, 0)
        right = jnp.where(beam > 0, 2 * beam + 1, 0)
        cand = jnp.concatenate([left, right], axis=1)         # [m, 2w]
        ub = _gathered_bounds(qp, tree.node_lo[cand], tree.node_hi[cand])
        ok = tree.node_valid[cand] & (cand > 0)
        ub = jnp.where(ok, ub, -jnp.inf)
        _, sel = jax.lax.top_k(ub, w)
        beam = jnp.where(jnp.take_along_axis(ok, sel, axis=1),
                         jnp.take_along_axis(cand, sel, axis=1), 0)
    blocks = beam - nl                                        # leaf slot = block
    okb = (beam >= nl) & (blocks < nb)
    blocks = jnp.clip(blocks, 0, nb - 1)
    db_blocks = idx.db.reshape(nb, bs, -1)
    valid_blocks = idx.valid.reshape(nb, bs)
    blk = db_blocks[blocks].reshape(m, w * bs, -1)
    vb = (valid_blocks[blocks] & okb[:, :, None]).reshape(m, w * bs)
    scores = jnp.einsum("md,mcd->mc", qn, blk,
                        precision=jax.lax.Precision.HIGHEST)
    scores = jnp.where(vb, scores, -jnp.inf)
    kk = min(k, w * bs)
    # barrier: single-device callers immediately slice the k-th column
    # (tree_warm_start), which would fold into top_k's internal sort+slice
    # and break XLA's TopkRewriter — a silent full-sort lowering (~10x on
    # CPU; see repro.kernels.ref.kth_value).  Pinning the [m, k] values
    # here protects every caller.
    from repro.dist.compat import optimization_barrier

    top_s, sel = jax.lax.top_k(scores, kk)
    top_s = optimization_barrier(top_s)
    top_v = jnp.take_along_axis(vb, sel, axis=1)
    if kk < k:                                 # shard smaller than k: pad
        top_s = jnp.pad(top_s, ((0, 0), (0, k - kk)),
                        constant_values=-jnp.inf)
        top_v = jnp.pad(top_v, ((0, 0), (0, k - kk)))
    return top_s, top_v


def tree_warm_start(tree: TreeIndex, qn: Array, qp: Array, k: int,
                    width: int) -> Array:
    """Tree-native τ seeding: the k-th best beam candidate, or -inf.

    Exactness does not depend on the beam finding the true best leaves:
    the k-th best of *any* set of real candidates is a valid lower bound
    on the final k-th best.  Queries whose reached leaves hold < k valid
    rows get -inf (no seed), mirroring ``tau_warm_start``.
    """
    m = qp.shape[0]
    w = max(1, min(width, tree.n_blocks))
    if w * tree.block_size < k:
        # fewer candidates than k even over the whole beam: no seed
        return jnp.full((m,), -jnp.inf, jnp.float32)
    scores, valid = tree_warm_start_topk(tree, qn, qp, k, width)
    return jnp.where(valid[:, -1], scores[:, -1], -jnp.inf)


def tree_descend(tree: TreeIndex, qp: Array, tau0: Array,
                 margin: float = 4e-7):
    """Level-synchronous transitive-bound descent (DESIGN.md §3.5).

    Per query a boolean frontier walks the heap top-down: a node is
    *evaluated* when its parent survived, and survives when its Eq. 13
    interval bound (+ fp ``margin``) reaches τ₀.  Because the node
    interval contains every descendant interval, a cut node provably
    excludes its whole subtree — the paper's bound applied transitively.

    Returns ``(leaf_alive [m, nb] bool, leaf_ub [m, nb], n_evals scalar)``:
    the surviving-leaf mask, the leaf-level bound matrix (identical to
    what the flat engine would have computed — reused by the leaf stage),
    and the number of (query, node) bound evaluations actually needed — a
    pointer implementation's cost, which the dense masked form models
    (this repo computes-and-masks; the statistic is what a scalar host or
    a scalar-prefetch kernel skips).
    """
    m = qp.shape[0]
    nl, depth, nb = tree.n_leaf_slots, tree.n_levels, tree.n_blocks
    alive = jnp.ones((m, 1), bool) & tree.node_valid[1]       # root frontier
    evals = jnp.full((), float(m), jnp.float32)               # root bound
    ub = None
    for level in range(1, depth + 1):
        base = 1 << level
        lo = tree.node_lo[base:2 * base]                      # [2^l, P]
        hi = tree.node_hi[base:2 * base]
        va = tree.node_valid[base:2 * base]
        ub = kref.block_bounds(qp, lo, hi)                    # [m, 2^l]
        evaluated = jnp.repeat(alive, 2, axis=1) & va[None, :]
        alive = evaluated & (ub + margin >= tau0[:, None])
        evals = evals + evaluated.sum().astype(jnp.float32)
    if depth == 0:                                            # single block
        ub = kref.block_bounds(qp, tree.node_lo[1:2], tree.node_hi[1:2])
        alive = alive & (ub + margin >= tau0[:, None])
    return alive[:, :nb], ub[:, :nb], evals


def _seed_and_descend(tree: TreeIndex, qn: Array, qp: Array, k: int, *,
                      warm_start: bool, warm_start_blocks: int | None,
                      margin: float, tau_merge=None):
    """Beam seed → transitive descent → flat reseed, the one sequence every
    leaf stage shares (exactness-critical; keep it in one place — the
    sharded per-shard stage runs it too, see ``core/distributed.py``).

    Returns ``(tau0 [m] or None, leaf_alive [m, nb], leaf_ub [m, nb],
    n_evals)``.  The flat reseed is a *second* prescan gather+matmul on
    top of the beam's — a deliberate cost (O(k·d) per query, vs the
    O(n·d) leaf stage): scoring the flat top-bound blocks too is what
    guarantees τ₀ ≥ the scan backend's seed, hence the tree's pruned set
    ⊇ the scan's (DESIGN.md §3.5).  It reuses the descent's leaf-level
    bound matrix, so no bounds are re-evaluated.

    ``tau_merge`` turns the beam's candidate list into the descent's τ
    seed.  Default: the local k-th best (:func:`tree_warm_start`'s
    semantics).  The sharded backend passes the mask-carrying all-gather
    reduction instead, so the seed becomes the k-th best of the union of
    every shard's candidates — the broadcast global τ of DESIGN.md §3.6
    (any k-th-best-of-real-candidates is a valid lower bound, so the
    exactness argument is unchanged; the flat reseed below then only ever
    raises it further).
    """
    idx = tree.index
    m = qn.shape[0]
    nb, bs = idx.n_blocks, idx.block_size
    tau0 = jnp.full((m,), -jnp.inf, jnp.float32)
    n_pre = _bk.prescan_blocks(k, bs, nb, warm_start_blocks)
    if warm_start:
        if tau_merge is None:
            tau0 = tree_warm_start(tree, qn, qp, k, n_pre)
        else:
            cand_s, cand_v = tree_warm_start_topk(tree, qn, qp, k, n_pre)
            tau0 = tau_merge(cand_s, cand_v)
    leaf_alive, leaf_ub, evals = tree_descend(tree, qp, tau0, margin)
    if warm_start:
        tau_flat = _bk.tau_warm_start(
            qn, idx.db.reshape(nb, bs, -1), idx.valid.reshape(nb, bs),
            leaf_ub, k, n_pre)
        tau0 = jnp.maximum(tau0, tau_flat)
    return (tau0 if warm_start else None), leaf_alive, leaf_ub, evals


@functools.partial(
    jax.jit,
    static_argnames=("k", "prune", "warm_start", "best_first", "element_stats",
                     "warm_start_blocks", "n_pivots"),
)
def tree_search(
    tree: TreeIndex,
    qn: Array,
    qp: Array,
    k: int,
    *,
    prune: bool = True,
    margin: float = 4e-7,
    warm_start: bool = True,
    best_first: bool = True,
    element_stats: bool = False,
    warm_start_blocks: int | None = None,
    n_pivots: int = 0,
):
    """Full tree search with the scan leaf stage, one jitted unit.

    Beam warm start → transitive descent → flat leaf stage over the
    survivors.  The leaf stage receives the descent's leaf-level bound
    matrix (no re-evaluation), the surviving-leaf mask, and a τ₀ that is
    the max of the beam seed and the flat prescan seed computed from that
    same bound matrix — both are true lower bounds, and taking the max
    guarantees the tree's running τ never starts below the scan
    backend's, so its pruned set is a superset of the scan's.

    Returns ``(top_s, pos, blk_pruned, elem_pruned, tree_pruned,
    node_evals)`` — the first four exactly as :func:`scan_search`, plus
    the count of (query, block) pairs the descent alone excluded and the
    number of (query, node) bound evaluations the descent needed.
    """
    idx = tree.index

    if prune:
        tau0, leaf_alive, leaf_ub, evals = _seed_and_descend(
            tree, qn, qp, k, warm_start=warm_start,
            warm_start_blocks=warm_start_blocks, margin=margin)
        if n_pivots > 0:
            # eq13_multi at the leaf level: tighten the descent's leaf
            # bound matrix with the joint projection cap before the leaf
            # scan consumes it.  The descent itself (and tree_prune_frac)
            # stays interval-only — the caps are leaf-granular tables.
            leaf_ub = jnp.minimum(
                leaf_ub, multipivot_block_cap(idx, qn, n_pivots=n_pivots))
    else:
        tau0, leaf_alive, leaf_ub = None, None, None
        evals = jnp.zeros((), jnp.float32)

    top_s, pos, blk_pruned, elem_pruned = _bk.scan_search(
        idx, qn, qp, k, prune=prune, margin=margin, warm_start=False,
        best_first=best_first, element_stats=element_stats,
        tau0=tau0, ub_all=leaf_ub, leaf_mask=leaf_alive)
    tree_pruned = ((~leaf_alive).sum().astype(jnp.float32) if prune
                   else jnp.zeros((), jnp.float32))
    return top_s, pos, blk_pruned, elem_pruned, tree_pruned, evals


@_bk.register_backend("tree")
class TreeBackend:
    """Hierarchical pivot-tree backend (``backend="tree"``).

    Builds (and caches on the engine) a :class:`TreeIndex` over the
    engine's ``BlockIndex`` on first use.  The leaf stage is selected by
    ``SearchEngine(leaf_eval=...)``: ``"scan"`` (portable, traceable
    inside an outer jit), ``"kernel"`` (compacts the union of surviving
    leaves with :mod:`repro.kernels.leaf_gather` and runs the fused Pallas
    kernel over just those rows — host-orchestrated, so not callable from
    inside an outer jit), or ``"auto"`` (kernel on TPU, scan elsewhere).
    The kernel leaf stage requires ``k <= block_size`` and pruning on;
    otherwise it falls back to the scan leaf stage.
    """

    name = "tree"

    def _tree(self, eng) -> TreeIndex:
        tree = getattr(eng, "_tree_index", None)
        if tree is None:
            tree = build_tree(eng.index)
            eng._tree_index = tree
            # constant per tree; cache the host sync so per-call stats stay
            # lazy jnp scalars (the engine may be traced inside a decode jit)
            eng._tree_valid_nodes = tree.n_valid_nodes
        return tree

    @staticmethod
    def _resolve_leaf_eval(eng) -> str:
        if eng.leaf_eval != "auto":
            return eng.leaf_eval
        # same VMEM guard as the flat kernel's auto-selection: the
        # Pallas kernel keeps the whole feature dim resident
        return ("kernel" if jax.default_backend() == "tpu"
                and eng.index.db.shape[-1] <= 4096 else "scan")

    def make_fused(self, eng, k, *, prune, element_stats, donate):
        """One-dispatch callee: prep + beam seed + descent + leaf scan +
        id map in one jit.  ``None`` for the kernel-leaf configuration —
        that stage is host-orchestrated (data-dependent compaction) and
        keeps the legacy multi-dispatch path."""
        leaf_eval = self._resolve_leaf_eval(eng)
        if leaf_eval == "kernel" and prune and k <= eng.index.block_size:
            return None
        self._tree(eng)                 # host-side build, outside the jit
        note = eng._note_trace
        margin, warm_start = eng.margin, eng.warm_start
        best_first, wsb = eng.best_first, eng.warm_start_blocks
        n_piv = eng.n_pivots

        @jax.jit
        def fused(index, tree, queries):
            note()
            qn, qp = _bk.prep_queries(index, queries)
            m, nb = qn.shape[0], tree.n_blocks
            top_s, pos, blk_pruned, elem_pruned, tree_pruned, evals = \
                tree_search(
                    tree, qn, qp, k, prune=prune, margin=margin,
                    warm_start=warm_start, best_first=best_first,
                    element_stats=element_stats, warm_start_blocks=wsb,
                    n_pivots=n_piv)
            ids = _bk.map_row_ids(index.row_ids, pos)
            raw = {
                "block_prune_frac": blk_pruned / (m * nb),
                "tree_levels": tree.n_levels,
            }
            if prune:
                # denominators traced, not captured: online mutation widens
                # the tree / flips validity without retracing this callee
                n_valid_nodes = jnp.maximum(tree.node_valid.sum(), 1)
                raw["tree_prune_frac"] = tree_pruned / (m * nb)
                raw["tree_node_eval_frac"] = evals / (m * n_valid_nodes)
            if element_stats:
                n_valid_rows = jnp.maximum(index.valid.sum(), 1)
                raw["elem_prune_frac"] = elem_pruned / (m * n_valid_rows)
            return top_s, ids, raw

        # the tree is fetched PER CALL (not bound at make time): a
        # shape-stable mutation swaps eng._tree_index for a widened twin
        # with identical array shapes, so the cached executable is reused
        # with the fresh arrays — no retrace, no stale intervals
        return lambda index, queries: fused(index, self._tree(eng), queries)

    def run(self, eng, queries, k, *, prune=True, element_stats=False):
        tree = self._tree(eng)
        qn, qp = _bk.prep_queries(eng.index, queries)
        m, nb = qn.shape[0], tree.n_blocks

        leaf_eval = self._resolve_leaf_eval(eng)
        if leaf_eval == "kernel" and prune and k <= tree.block_size:
            return self._run_kernel_leaves(eng, tree, qn, qp, k,
                                           element_stats=element_stats)

        top_s, pos, blk_pruned, elem_pruned, tree_pruned, evals = tree_search(
            tree, qn, qp, k, prune=prune, margin=eng.margin,
            warm_start=eng.warm_start, best_first=eng.best_first,
            element_stats=element_stats,
            warm_start_blocks=eng.warm_start_blocks,
            n_pivots=eng.n_pivots)
        ids = _bk.map_row_ids(eng.index.row_ids, pos)
        raw = {
            "block_prune_frac": blk_pruned / (m * nb),
            "tree_levels": tree.n_levels,
        }
        if prune:
            # absent-stage contract: with prune off the descent never ran,
            # so the tree fracs stay None (engine raw.get), never 0.0
            raw["tree_prune_frac"] = tree_pruned / (m * nb)
            raw["tree_node_eval_frac"] = evals / (
                m * max(1, eng._tree_valid_nodes))
        if element_stats:
            raw["elem_prune_frac"] = elem_pruned / (m * max(1, eng.n_valid))
        return top_s, ids, raw

    def _run_kernel_leaves(self, eng, tree: TreeIndex, qn, qp, k, *,
                           element_stats: bool):
        """Descent, then the Pallas kernel over the compacted survivors."""
        from repro.kernels import leaf_gather

        idx = tree.index
        m, nb, bs = qn.shape[0], tree.n_blocks, tree.block_size
        tau0, leaf_alive, _, evals = _seed_and_descend(
            tree, qn, qp, k, warm_start=eng.warm_start,
            warm_start_blocks=eng.warm_start_blocks, margin=eng.margin)
        # tree_prune_frac stays descent-only: snapshot before any cap
        # refinement below changes the compaction mask
        tree_pruned = (~leaf_alive).sum().astype(jnp.float32)
        if eng.n_pivots > 0 and tau0 is not None and not element_stats:
            # eq13_multi refinement of the compaction: leaves whose joint
            # cap cannot reach the τ seed never enter the kernel grid.
            # Skipped under element_stats — that statistic's non-kept-block
            # accounting relies on every compacted-away row being provably
            # under its *interval* bound, which the cap does not imply.
            cap = multipivot_block_cap(idx, qn, n_pivots=eng.n_pivots)
            leaf_alive = leaf_alive & (cap + eng.margin >= tau0[:, None])

        # host-side compaction: the union over the query batch of surviving
        # leaves is the data-dependent part, so the kernel grid shrinks to
        # the blocks that can still matter (ascending order keeps valid
        # rows a prefix — build_index places padding rows last)
        union = np.asarray(jax.device_get(leaf_alive.any(axis=0)))
        keep_np = np.nonzero(union)[0].astype(np.int32)
        if keep_np.size == 0:
            keep_np = np.zeros((1,), np.int32)                # degenerate
        keep = jnp.asarray(keep_np)
        if eng.sort_queries:
            # angularly coherent query tiles: the tile-level skip is an OR
            # over the bm queries, so nearest-pivot grouping lets it fire
            perm = _bk.query_sort_perm(qp)
            qn, qp = qn[perm], qp[perm]
            if tau0 is not None:
                tau0 = tau0[perm]
        sims, pos, computed, elem = leaf_gather.gathered_topk(
            idx, keep, qn, qp, tau0,
            n_keep=int(keep_np.size), k=k, bm=eng.bm, margin=eng.margin,
            interpret=(jax.default_backend() == "cpu" if eng.interpret is None
                       else eng.interpret),
            element_stats=element_stats, best_first=eng.best_first)
        if eng.sort_queries:
            inv = jnp.argsort(perm)
            sims, pos = sims[inv], pos[inv]
        ids = _bk.map_row_ids(idx.row_ids, pos)

        m_tiles = computed.shape[0]
        computed_sum = computed.astype(jnp.float32).sum()
        raw = {
            # over the FULL (query tile, block tile) grid: compacted-away
            # tiles were never dispatched, which is the whole point
            "block_prune_frac": 1.0 - computed_sum / (m_tiles * nb),
            "tile_computed_frac": computed_sum / (m_tiles * nb),
            "tree_prune_frac": tree_pruned / (m * nb),
            "tree_node_eval_frac": evals / (m * max(1, eng._tree_valid_nodes)),
            "tree_levels": tree.n_levels,
        }
        if element_stats:
            # rows in never-kept blocks were proven prunable by the descent
            # (their individual Eq. 13 bound sits under the node bound < τ0)
            valid_counts = idx.valid.reshape(nb, bs).sum(axis=1)
            nonkept = valid_counts.sum() - valid_counts[keep].sum()
            total = elem.astype(jnp.float32).sum() + m * nonkept
            raw["elem_prune_frac"] = total / (m * max(1, eng.n_valid))
        return sims, ids, raw
