"""Leaf-gather entry point: run the fused Pallas kernel over a block subset.

The tree backend's descent (:mod:`repro.search.tree`, DESIGN.md §3.5)
proves most blocks irrelevant *before* any kernel is dispatched.  This
module is the bridge from that data-dependent survivor set to the
fixed-shape Pallas kernel: gather the surviving blocks into a contiguous
compact database (one static-shape gather — the TPU analogue of a
pointer-chased leaf visit) and hand it to
:func:`repro.kernels.cosine_topk.pruned_topk` with the kernel tile pinned
to the index block size, so per-block pivot intervals are reused directly
(no coarsening) and the kernel grid shrinks from ``n_blocks`` to
``n_keep`` tiles.

Shape contract: ``keep`` must be sorted ascending (stable tile order for
the best-first permutation and the position mapping).  The compacted
per-row ``valid`` vector rides along as ``pruned_topk``'s ``row_valid``
operand, so validity need not be a prefix — tombstoned rows of a mutable
index (:mod:`repro.core.online`) are masked per row exactly like padding.
Exactness: the caller guarantees the kept set contains every block any
query in the batch still needs; the kernel's own per-tile bound check
then skips kept tiles that a risen τ has since invalidated.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import Array

from repro.core.index import BlockIndex
from repro.kernels import cosine_topk
from repro.kernels import ref as kref

__all__ = ["gathered_topk"]


@functools.partial(
    jax.jit,
    static_argnames=("n_keep", "k", "bm", "margin", "interpret",
                     "element_stats", "best_first"),
)
def gathered_topk(
    index: BlockIndex,
    keep: Array,
    qn: Array,
    qp: Array,
    tau0: Array | None,
    *,
    n_keep: int,
    k: int,
    bm: int = cosine_topk.DEFAULT_BM,
    margin: float = 4e-7,
    interpret: bool = False,
    element_stats: bool = False,
    best_first: bool = True,
):
    """Fused pruned top-k over the ``keep`` subset of index blocks.

    Args:
      index: the (single-shard) :class:`BlockIndex`.
      keep: [n_keep] i32 block ids, sorted ascending (see module doc).
      qn / qp: normalized queries and their pivot similarities.
      tau0: [m] τ warm-start seeds or ``None``.
      n_keep: static length of ``keep`` (host-known survivor count).
      k: top-k; must satisfy ``k <= block_size`` (kernel tile constraint).
      best_first: per-query-tile bound-descending visit order over the
        kept tiles (scalar-prefetched, as in the flat kernel backend).

    Returns ``(sims [m, k], pos [m, k] positions into the ORIGINAL padded
    db, computed [m_tiles, n_keep] i32, elem [m_tiles, n_keep] i32 or
    None)`` — positions are mapped back through ``keep`` so callers can
    use the usual ``map_row_ids``.
    """
    nb, bs = index.n_blocks, index.block_size
    d = index.db.shape[1]
    assert k <= bs, "kernel leaf stage needs k <= block_size"

    db_c = index.db.reshape(nb, bs, d)[keep].reshape(n_keep * bs, d)
    valid_c = index.valid.reshape(nb, bs)[keep].reshape(n_keep * bs)
    lo_c = index.dp_min[keep]                                  # [n_keep, P]
    hi_c = index.dp_max[keep]
    n_valid = valid_c.sum().astype(jnp.int32)

    block_order = None
    if best_first:
        ub = kref.block_bounds(qp, lo_c, hi_c)                 # [m, n_keep]
        block_order = cosine_topk.tile_order(ub, bm)

    dp_c = None
    if element_stats:
        dp_c = index.dp.reshape(nb, bs, -1)[keep].reshape(n_keep * bs, -1)

    sims, pos, computed, elem, _ = cosine_topk.pruned_topk(
        qn, db_c, qp, lo_c, hi_c, n_valid,
        tau_init=tau0, block_order=block_order, dp=dp_c, row_valid=valid_c,
        k=k, bm=bm, bn=bs, margin=margin, prune=True, interpret=interpret,
        element_stats=element_stats)

    # compact positions -> original padded-db positions (−1 stays −1)
    blk = jnp.clip(pos // bs, 0, n_keep - 1)
    orig = jnp.where(pos >= 0, keep[blk] * bs + pos % bs, -1)
    return sims, orig.astype(jnp.int32), computed, elem
