"""Search backends: the bare inner loops behind :class:`SearchEngine`.

Every backend implements::

    make_fused(engine, k, *, prune, element_stats, donate)
        -> callee(index, queries)
        -> (sims [m, k] f32, ids [m, k] i32 original row ids, raw stats dict)

the one jitted dispatch the engine caches per call signature, and all but
``sharded`` also ``run(engine, queries, k, *, prune, element_stats)``, the
same search as separate dispatches (the engine's fallback where
``make_fused`` gives ``None``).  Each registers itself under a name with
:func:`register_backend`.  The
engine owns everything else — query normalization, pivot-similarity
computation, τ warm-start policy, best-first ordering policy, id mapping,
and :class:`~repro.search.stats.SearchStats` assembly — so a backend is
only its compute strategy:

  ``scan``    pure-JAX ``lax.scan`` over blocks (masked matmuls; portable)
  ``kernel``  fused Pallas kernel (``@pl.when``-skipped tiles; TPU-native)
  ``sharded`` each shard's own inner loop + tiny all-gather top-k merge
              (mesh required)
  ``brute``   full matmul + top-k (baseline / tiny datastores)

The shared helpers here (τ warm-start seeding, best-first block
permutation) are what the refactor lifted out of the kernel-only path so
that *every* backend benefits — DESIGN.md §3.1 (warm-start), §3.2
(best-first), §3.3 (the backend contract), §3.4 (the multi-block
warm-start schedule and its exactness argument).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import Array

from repro.core.bounds import ub_mult
from repro.core.index import (BlockIndex, block_upper_bound,
                              multipivot_block_cap)
from repro.core.pivots import normalize
from repro.kernels import cosine_topk, tile_prescan
from repro.kernels import ref as kref

__all__ = [
    "register_backend", "get_backend", "available_backends",
    "prep_queries", "map_row_ids", "scan_search", "kernel_search",
    "brute_search", "tau_warm_start", "prescan_blocks", "coarsen_intervals",
    "query_sort_perm",
]

_REGISTRY: dict[str, object] = {}

#: every score and pivot-similarity matmul runs at f32 precision (an f32
#: dot on the TPU MXU otherwise takes one bf16 pass; DESIGN.md §3.12)
_HIGHEST = jax.lax.Precision.HIGHEST


def register_backend(name: str):
    """Class decorator: register a backend under ``name`` (instantiated)."""
    def deco(cls):
        _REGISTRY[name] = cls()
        return cls
    return deco


def get_backend(name: str):
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown search backend {name!r}; "
            f"registered: {available_backends()}") from None


def available_backends() -> list[str]:
    return sorted(_REGISTRY)


# ---------------------------------------------------------------------------
# shared jitted pieces (engine-owned plumbing)
# ---------------------------------------------------------------------------

@jax.jit
def prep_queries(index: BlockIndex, queries: Array):
    """Normalize queries and compute query-pivot similarities once."""
    qn = normalize(jnp.asarray(queries, jnp.float32))
    return qn, jnp.dot(qn, index.pivots.T, precision=_HIGHEST)


@jax.jit
def map_row_ids(row_ids: Array, pos: Array) -> Array:
    """Padded/reordered positions -> original row ids (-1 stays -1)."""
    return jnp.where(pos >= 0, row_ids[jnp.maximum(pos, 0)], -1)


def coarsen_intervals(dp_min: Array, dp_max: Array, factor: int):
    """Merge ``factor`` consecutive index blocks into one kernel tile."""
    nb, p = dp_min.shape
    assert nb % factor == 0, (nb, factor)
    lo = dp_min.reshape(nb // factor, factor, p).min(axis=1)
    hi = dp_max.reshape(nb // factor, factor, p).max(axis=1)
    return lo, hi


def prescan_blocks(k: int, block_rows: int, n_blocks: int,
                   warm_start_blocks: int | None = None) -> int:
    """Static prescan width: how many bound-ranked blocks τ seeding scores.

    The floor ``ceil(k / block_rows)`` is the fewest blocks that can hold k
    candidates — this is what lets warm-start engage for every ``k`` instead
    of auto-disabling when ``k`` exceeds the block size (DESIGN.md §3.4).
    ``warm_start_blocks`` only ever *widens* the prescan (a tighter seed at
    the cost of a larger gather); the result is clamped to ``n_blocks``.
    """
    n_pre = -(-k // max(1, block_rows))
    if warm_start_blocks is not None:
        n_pre = max(n_pre, warm_start_blocks)
    return max(1, min(n_pre, n_blocks))


def tau_warm_start(qn: Array, db_blocks: Array, valid_blocks: Array,
                   ub: Array, k: int, n_pre: int = 1) -> Array:
    """Seed each query's running k-th-best from its ``n_pre`` best-bound blocks.

    One batched ``[m, n_pre * bs] x d`` matmul: gather the ``n_pre`` blocks
    whose Eq. 13 upper bounds are highest for each query (bound-ranked via
    ``top_k``), exact-score them together, and take the k-th best of the
    merged candidate set.  The seed is a true lower bound on the final τ
    *achieved by k real candidates of those blocks*, so seeding every top-k
    slot with it (minus an ulp so ties displace seeds) cannot evict a true
    neighbor (DESIGN.md §3.4).  Queries whose prescanned blocks hold < k
    valid rows get -inf (no seeding).

    ``n_pre`` is static; size it with :func:`prescan_blocks` so that
    ``n_pre * bs >= k`` whenever the database allows.  ``ub`` is [m, nb] at
    the same block granularity as ``db_blocks`` [nb, bs, d].
    """
    m = qn.shape[0]
    nb, bs, d = db_blocks.shape
    n_pre = max(1, min(n_pre, nb))
    if n_pre * bs < k:
        # fewer candidates than k even over the whole prescan: no seed
        return jnp.full((m,), -jnp.inf, jnp.float32)
    best = jax.lax.top_k(ub, n_pre)[1]                  # [m, n_pre]
    blk = db_blocks[best].reshape(m, n_pre * bs, d)
    vb = valid_blocks[best].reshape(m, n_pre * bs)
    scores = jnp.einsum("md,mcd->mc", qn, blk, precision=_HIGHEST)
    scores = jnp.where(vb, scores, -jnp.inf)
    # kth_value, not top_k(...)[0][:, -1]: the naive slice breaks XLA's
    # TopkRewriter and this line becomes a full sort (~10x, see kref)
    tau = kref.kth_value(scores, k)
    return jnp.where(jnp.isfinite(tau), tau, -jnp.inf)


def query_sort_perm(qp: Array) -> Array:
    """Permutation grouping queries by nearest pivot (desc sim within group).

    The kernel paths skip a db tile only when *no* query in the BM-row
    tile needs it — angularly coherent query tiles are what let that OR
    fire.  Shared by the flat kernel backend and the tree backend's
    kernel leaf stage so the two paths can never diverge in grouping.
    """
    return jnp.lexsort((-jnp.max(qp, axis=1), jnp.argmax(qp, axis=1)))


def best_first_order(ub: Array) -> Array:
    """Blocks permuted by descending upper bound, aggregated over queries.

    ``ub`` [m, nb] -> [nb] i32 visiting order.  Aggregation is ``max`` over
    the query tile: the block *any* query still needs comes first, which is
    what drives every query's τ up fastest (DESIGN.md §3.2).
    """
    return jnp.argsort(-ub.max(axis=0)).astype(jnp.int32)


# ---------------------------------------------------------------------------
# scan backend inner loop
# ---------------------------------------------------------------------------

@functools.partial(
    jax.jit,
    static_argnames=("k", "prune", "warm_start", "best_first", "element_stats",
                     "warm_start_blocks", "n_pivots"),
)
def scan_search(
    index: BlockIndex,
    qn: Array,
    qp: Array,
    k: int,
    *,
    prune: bool = True,
    margin: float = 4e-7,
    warm_start: bool = False,
    best_first: bool = False,
    element_stats: bool = False,
    warm_start_blocks: int | None = None,
    n_pivots: int = 0,
    tau0: Array | None = None,
    ub_all: Array | None = None,
    leaf_mask: Array | None = None,
    db_scratch: Array | None = None,
):
    """Pure-JAX block scan (the portable backend; DESIGN.md §2 for the block
    granularity, §3.3 for the backend contract this implements).

    Returns ``(top_s [m,k], pos [m,k] padded-row positions, blk_pruned,
    elem_pruned)`` — id mapping and stats normalization happen in the
    engine.  Pruned matmuls are computed-and-masked (XLA has no
    data-dependent skip); the kernel backend actually skips them.
    ``warm_start_blocks`` widens the τ prescan beyond the ``ceil(k / bs)``
    floor (DESIGN.md §3.4).  ``n_pivots`` > 0 intersects the joint
    multi-pivot projection cap into the block bound matrix before the
    scan (the ``eq13_multi`` provider, DESIGN.md §3.8) — it tightens the
    warm-start seed, the best-first order, and the per-block prune test.

    The three optional arrays let a hierarchical caller (the ``tree``
    backend, DESIGN.md §3.5) reuse this loop as its leaf stage: ``tau0``
    [m] overrides the internal τ warm-start seed (must be a true lower
    bound on each query's final k-th best, or -inf), ``ub_all`` [m, nb]
    supplies an already-computed block bound matrix (the descent's last
    level) so it is not re-evaluated here, and ``leaf_mask`` [m, nb] marks
    blocks a caller has *proven* prunable (mask False ⇒ skipped and
    counted in ``blk_pruned``; exactness is the caller's obligation).

    ``db_scratch`` [nb, bs, d] (``best_first`` only) is an engine-owned
    recycled buffer for the per-call best-first database permutation —
    the one large per-call allocation this loop makes.  When supplied,
    the permuted blocks are routed through it and returned as an extra
    trailing output, so a caller that donates the buffer (the engine's
    fused dispatch cache does) lets XLA write the gather in place and
    cycle the same memory call over call.
    """
    m = qn.shape[0]
    nb, bs = index.n_blocks, index.block_size
    db_blocks = index.db.reshape(nb, bs, -1)
    dp_blocks = index.dp.reshape(nb, bs, -1)
    valid_blocks = index.valid.reshape(nb, bs)
    base_idx = (jnp.arange(nb)[:, None] * bs
                + jnp.arange(bs)[None, :]).astype(jnp.int32)

    if ub_all is None and (warm_start or best_first
                           or (prune and n_pivots > 0)):
        ub_all = kref.block_bounds(qp, index.dp_min, index.dp_max)  # [m, nb]
    if prune and n_pivots > 0:
        # eq13_multi: intersect the joint n_pivots-deep projection cap —
        # min of valid upper bounds is a valid upper bound (DESIGN.md §3.8)
        ub_all = jnp.minimum(
            ub_all, multipivot_block_cap(index, qn, n_pivots=n_pivots))

    if tau0 is None:
        tau0 = jnp.full((m,), -jnp.inf, jnp.float32)
        if warm_start:
            n_pre = prescan_blocks(k, bs, nb, warm_start_blocks)
            tau0 = tau_warm_start(qn, db_blocks, valid_blocks, ub_all, k,
                                  n_pre)

    # when the bound matrix already exists (warm start / best-first / a tree
    # descent), feed it through the scan instead of re-evaluating Eq. 13 per
    # block
    reuse_ub = prune and ub_all is not None
    has_mask = leaf_mask is not None
    xs = (db_blocks, dp_blocks, valid_blocks, base_idx,
          index.dp_min, index.dp_max)
    if reuse_ub:
        xs = xs + (ub_all.T,)                                 # [nb, m]
    if has_mask:
        xs = xs + (leaf_mask.T,)                              # [nb, m]
    perm_db = None
    if best_first:
        order = best_first_order(ub_all)
        xs = tuple(a[order] for a in xs)
        if db_scratch is not None:
            # route the permuted db through the caller's scratch: the
            # .set is the gather's destination, so a donated buffer is
            # written in place instead of freshly allocated per call
            perm_db = db_scratch.at[:].set(xs[0])
            xs = (perm_db,) + xs[1:]

    init = (
        jnp.tile((tau0 - 1e-6)[:, None], (1, k)),             # seeded top sims
        jnp.full((m, k), -1, jnp.int32),                      # top positions
        jnp.zeros((), jnp.float32),                           # pruned pairs
        jnp.zeros((), jnp.float32),                           # prunable elems
    )

    def step(carry, x):
        top_s, top_i, blk_pruned, elem_pruned = carry
        blk, dpb, vb, bidx, lo, hi = x[:6]
        rest = x[6:]
        if reuse_ub:
            ub, rest = rest[0], rest[1:]                      # [m]
        else:
            ub = block_upper_bound(qp, lo, hi) if prune else None
        lmask = rest[0] if has_mask else None                 # [m] bool
        tau = top_s[:, -1]                                    # running kth best
        if prune:
            needed = ub + margin >= tau
        else:
            needed = jnp.ones((m,), bool)
        if has_mask:
            needed = needed & lmask
        scores = jnp.dot(qn, blk.T, precision=_HIGHEST)       # [m, bs]
        scores = jnp.where(vb[None, :], scores, -jnp.inf)
        scores = jnp.where(needed[:, None], scores, -jnp.inf)
        cand_s = jnp.concatenate([top_s, scores], axis=1)
        cand_i = jnp.concatenate(
            [top_i, jnp.broadcast_to(bidx[None, :], (m, bs))], axis=1)
        new_s, sel = jax.lax.top_k(cand_s, k)
        new_i = jnp.take_along_axis(cand_i, sel, axis=1)
        blk_pruned = blk_pruned + (~needed).sum().astype(jnp.float32)
        if element_stats:
            eub = jnp.min(ub_mult(qp[:, None, :], dpb[None, :, :]), axis=-1)
            elem_pruned = elem_pruned + (
                ((eub + margin < tau[:, None]) & vb[None, :])
                .sum().astype(jnp.float32))
        return (new_s, new_i, blk_pruned, elem_pruned), None

    (top_s, top_i, blk_pruned, elem_pruned), _ = jax.lax.scan(step, init, xs)
    if perm_db is not None:
        return top_s, top_i, blk_pruned, elem_pruned, perm_db
    return top_s, top_i, blk_pruned, elem_pruned


# ---------------------------------------------------------------------------
# kernel backend wrapper
# ---------------------------------------------------------------------------

def _resolve_bn(index: BlockIndex, bn: int | None) -> int:
    """Kernel tile size: a multiple of the index block size dividing n_pad
    (of one shard, for a shard-stacked index)."""
    n_pad = index.db.shape[-2]
    ibs = n_pad // index.dp_min.shape[-2]
    if bn is None:
        bn = ibs if ibs % 128 == 0 else ibs * max(1, -(-128 // ibs))
    while n_pad % bn or bn % ibs:
        bn //= 2
        if bn < ibs:
            bn = ibs
            break
    return bn


@functools.partial(
    jax.jit,
    static_argnames=("k", "bm", "bn", "prune", "sort_queries", "warm_start",
                     "best_first", "margin", "interpret", "element_stats",
                     "warm_start_blocks", "n_pivots"),
)
def kernel_search(
    index: BlockIndex,
    qn: Array,
    qp: Array,
    k: int,
    *,
    bm: int = cosine_topk.DEFAULT_BM,
    bn: int | None = None,
    prune: bool = True,
    sort_queries: bool = True,
    warm_start: bool = False,
    best_first: bool = False,
    margin: float = 4e-7,
    interpret: bool | None = None,
    element_stats: bool = False,
    warm_start_blocks: int | None = None,
    n_pivots: int = 0,
):
    """Fused Pallas backend (see :mod:`repro.kernels.cosine_topk`).

    Returns ``(sims [m,k], pos [m,k] padded-row positions, computed
    [m_tiles, n_tiles], elem_pruned)`` — ``elem_pruned`` is the [m_tiles,
    n_tiles] per-tile count of (query, row) pairs whose individual Eq. 13
    bound prunes them, or ``None`` unless ``element_stats``.
    ``sort_queries`` groups queries by nearest pivot so BM-row tiles are
    angularly coherent (the kernel prunes a db tile only when *no* query in
    the tile needs it); results are unsorted before returning.
    ``best_first`` hands the kernel a per-query-tile block visiting order
    (scalar-prefetched index map).  ``warm_start_blocks`` widens the τ
    prescan beyond ``ceil(k / bn)`` kernel tiles (DESIGN.md §3.4); the
    prescan granularity here is the *kernel tile* (bn rows), not the index
    block.  ``n_pivots`` > 0 computes the joint multi-pivot cap at index
    block granularity, coarsens it to kernel tiles (max over merged
    blocks — still a valid tile bound), and hands it to the kernel as the
    extra per-(query-tile, db-tile) bound operand.

    Where the runtime stores the corpus column-major (a width that is not a
    multiple of 128, :func:`cosine_topk.db_layout`), the kernel and the τ
    prescan (:mod:`repro.kernels.tile_prescan`) read ``index.db.T``, a
    bitcast of the stored buffer, so no call lays the corpus out again.
    """
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    layout = cosine_topk.db_layout(index.db.shape[-1])
    db = index.db.T if layout == "cols" else index.db
    bn = _resolve_bn(index, bn)
    factor = bn // index.block_size
    lo, hi = coarsen_intervals(index.dp_min, index.dp_max, factor)
    m = qn.shape[0]
    if sort_queries:
        with jax.named_scope("query_sort"):
            perm = query_sort_perm(qp)
            qn, qp = qn[perm], qp[perm]
    n_valid = index.valid.sum().astype(jnp.int32)

    ub_cap = None
    ub = None
    with jax.named_scope("bound"):
        if prune and n_pivots > 0:
            cap = multipivot_block_cap(index, qn, n_pivots=n_pivots)
            ub_cap = cap.reshape(m, lo.shape[0], -1).max(axis=-1)  # [m, nt]
        if warm_start or best_first:
            ub = kref.block_bounds(qp, lo, hi)                # [m, n_tiles]
            if ub_cap is not None:
                ub = jnp.minimum(ub, ub_cap)
    tau_init = None
    if warm_start:
        with jax.named_scope("prescan"):
            n_pre = prescan_blocks(k, bn, lo.shape[0], warm_start_blocks)
            if layout == "cols":
                tau_init = tile_prescan.tau_prescan(
                    qn, db, index.valid, ub, k=k, n_pre=n_pre, bn=bn,
                    interpret=interpret)
            else:
                tau_init = tau_warm_start(
                    qn, index.db.reshape(-1, bn, index.db.shape[-1]),
                    index.valid.reshape(-1, bn), ub, k, n_pre)
    block_order = None
    if best_first:
        with jax.named_scope("order"):
            block_order = cosine_topk.tile_order(ub, bm)

    with jax.named_scope("pruned_topk"):
        sims, pos, computed, elem, rounds = cosine_topk.pruned_topk(
            qn, db, qp, lo, hi, n_valid,
            tau_init=tau_init, block_order=block_order,
            dp=index.dp if element_stats else None, ub_cap=ub_cap,
            row_valid=index.valid,
            k=k, bm=bm, bn=bn, margin=margin, prune=prune,
            interpret=interpret, element_stats=element_stats,
            db_layout=layout,
        )
    if sort_queries:
        with jax.named_scope("unsort"):
            inv = jnp.argsort(perm)
            sims, pos = sims[inv], pos[inv]
    return sims, pos, computed, elem, rounds


def _kernel_raw(computed, elem, rounds, m, n_valid, element_stats):
    """The kernel backend's raw stats from its counters."""
    n_computed = computed.sum()
    frac = computed.mean()
    raw = {"block_prune_frac": 1.0 - frac, "tile_computed_frac": frac,
           "merge_rounds": rounds.sum() / jnp.maximum(n_computed, 1)}
    if element_stats:
        raw["elem_prune_frac"] = elem.astype(jnp.float32).sum() / (
            m * n_valid)
    return raw


# ---------------------------------------------------------------------------
# brute backend inner
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("k",))
def brute_search(index: BlockIndex, qn: Array, k: int):
    """Full matmul + top-k over the padded database (positions, not ids).

    ``k`` is clamped to the padded row count — ``lax.top_k`` rejects a k
    wider than its operand — and the tail pads with ``(-inf, -1)``, the
    same fill the ``search()`` contract documents for slots beyond the
    valid rows (and that the scan/tree loops produce naturally).  This
    matters here more than anywhere: ``auto_backend`` routes exactly the
    tiny datastores where ``k > n`` is most likely to brute.
    """
    scores = jnp.dot(qn, index.db.T, precision=_HIGHEST)
    scores = jnp.where(index.valid[None, :], scores, -jnp.inf)
    kk = min(k, scores.shape[-1])
    sims, pos = jax.lax.top_k(scores, kk)
    if kk < k:
        pad = ((0, 0), (0, k - kk))
        sims = jnp.pad(sims, pad, constant_values=-jnp.inf)
        pos = jnp.pad(pos, pad, constant_values=-1)
    return sims, pos.astype(jnp.int32)


# ---------------------------------------------------------------------------
# the registered backends
# ---------------------------------------------------------------------------

@register_backend("scan")
class ScanBackend:
    """Portable pure-JAX block scan."""

    name = "scan"

    def run(self, eng, queries, k, *, prune=True, element_stats=False):
        qn, qp = prep_queries(eng.index, queries)
        s, pos, blk_pruned, elem_pruned = scan_search(
            eng.index, qn, qp, k, prune=prune, margin=eng.margin,
            warm_start=eng.warm_start, best_first=eng.best_first,
            element_stats=element_stats,
            warm_start_blocks=eng.warm_start_blocks,
            n_pivots=eng.n_pivots)
        ids = map_row_ids(eng.index.row_ids, pos)
        m, nb = qn.shape[0], eng.index.n_blocks
        # raw stats stay jnp scalars: engine.search converts to host floats
        # only outside of tracing (lookup may run inside a decode jit)
        raw = {"block_prune_frac": blk_pruned / (m * nb)}
        if element_stats:
            raw["elem_prune_frac"] = elem_pruned / (m * max(1, eng.n_valid))
        return s, ids, raw

    def make_fused(self, eng, k, *, prune, element_stats, donate):
        """One-dispatch callee: prep + τ prescan + scan + id map, one jit.

        ``donate``: also thread the engine-owned best-first permutation
        scratch through the call (donated, cycled by the engine's cache
        entry) so the one large per-call buffer is written in place.
        """
        note = eng._note_trace
        margin, warm_start = eng.margin, eng.warm_start
        best_first, wsb = eng.best_first, eng.warm_start_blocks
        n_piv = eng.n_pivots

        def body(index, queries, scratch=None):
            note()          # Python side effect: fires at trace time only
            qn, qp = prep_queries(index, queries)
            out = scan_search(
                index, qn, qp, k, prune=prune, margin=margin,
                warm_start=warm_start, best_first=best_first,
                element_stats=element_stats, warm_start_blocks=wsb,
                n_pivots=n_piv, db_scratch=scratch)
            s, pos, blk_pruned, elem_pruned = out[:4]
            ids = map_row_ids(index.row_ids, pos)
            m, nb = qn.shape[0], index.n_blocks
            raw = {"block_prune_frac": blk_pruned / (m * nb)}
            if element_stats:
                # traced, not captured: online mutation changes the live
                # row count without retracing this callee
                n_valid = jnp.maximum(index.valid.sum(), 1)
                raw["elem_prune_frac"] = elem_pruned / (m * n_valid)
            if scratch is not None:
                return s, ids, raw, out[4]
            return s, ids, raw

        if donate and best_first:
            return jax.jit(body, donate_argnums=(2,))
        return jax.jit(lambda index, queries: body(index, queries))


@register_backend("kernel")
class KernelBackend:
    """Fused Pallas kernel (interpret mode off-TPU)."""

    name = "kernel"

    def run(self, eng, queries, k, *, prune=True, element_stats=False):
        qn, qp = prep_queries(eng.index, queries)
        s, pos, computed, elem, rounds = kernel_search(
            eng.index, qn, qp, k, bm=eng.bm, bn=eng.bn, prune=prune,
            sort_queries=eng.sort_queries, warm_start=eng.warm_start,
            best_first=eng.best_first, margin=eng.margin,
            interpret=eng.interpret, element_stats=element_stats,
            warm_start_blocks=eng.warm_start_blocks,
            n_pivots=eng.n_pivots)
        ids = map_row_ids(eng.index.row_ids, pos)
        raw = _kernel_raw(computed, elem, rounds, qn.shape[0],
                          max(1, eng.n_valid), element_stats)
        return s, ids, raw

    def make_fused(self, eng, k, *, prune, element_stats, donate):
        """Prep + fused Pallas search + id map as one jitted dispatch."""
        note = eng._note_trace
        bm, bn, sq = eng.bm, eng.bn, eng.sort_queries
        warm_start, best_first = eng.warm_start, eng.best_first
        margin, interpret, wsb = eng.margin, eng.interpret, \
            eng.warm_start_blocks
        n_piv = eng.n_pivots

        @jax.jit
        def fused(index, queries):
            note()
            with jax.named_scope("prep"):
                qn, qp = prep_queries(index, queries)
            s, pos, computed, elem, rounds = kernel_search(
                index, qn, qp, k, bm=bm, bn=bn, prune=prune,
                sort_queries=sq, warm_start=warm_start,
                best_first=best_first, margin=margin, interpret=interpret,
                element_stats=element_stats, warm_start_blocks=wsb,
                n_pivots=n_piv)
            with jax.named_scope("ids"):
                ids = map_row_ids(index.row_ids, pos)
            # traced valid count: online mutation changes it without a retrace
            raw = _kernel_raw(computed, elem, rounds, qn.shape[0],
                              jnp.maximum(index.valid.sum(), 1),
                              element_stats)
            return s, ids, raw

        return fused


@register_backend("brute")
class BruteBackend:
    """Exact baseline: one big matmul, no pruning."""

    name = "brute"

    def run(self, eng, queries, k, *, prune=True, element_stats=False):
        qn, _ = prep_queries(eng.index, queries)
        s, pos = brute_search(eng.index, qn, k)
        ids = map_row_ids(eng.index.row_ids, pos)
        raw = {"block_prune_frac": 0.0}
        if element_stats:
            # brute force evaluates no bounds and skips nothing — the
            # element pruning fraction is 0 by definition (glossary in
            # docs/search-api.md)
            raw["elem_prune_frac"] = 0.0
        return s, ids, raw

    def make_fused(self, eng, k, *, prune, element_stats, donate):
        """Prep + matmul + top-k + id map as one jitted dispatch."""
        note = eng._note_trace

        @jax.jit
        def fused(index, queries):
            note()
            qn, _ = prep_queries(index, queries)
            s, pos = brute_search(index, qn, k)
            ids = map_row_ids(index.row_ids, pos)
            raw = {"block_prune_frac": 0.0}
            if element_stats:
                raw["elem_prune_frac"] = 0.0
            return s, ids, raw

        return fused


@register_backend("sharded")
class ShardedBackend:
    """Mesh-sharded search: each shard's own inner loop, then the
    all-gather top-k merge (needs ``mesh``).

    Each shard runs the search a one-chip engine would run over it
    (``SearchEngine.shard_inner``): the fused Pallas kernel search on TPU,
    brute force on a tiny shard, the scan elsewhere.  With
    ``SearchEngine(tree_shards=...)`` enabled, each shard instead first
    runs the transitive Eq. 13 descent over its own pivot tree (built
    lazily here, one tree per shard, placed like the index so every device
    holds only its own) pruning against the broadcast global τ; the
    surviving leaves feed the per-shard scan loop — DESIGN.md §3.6.
    Everything runs *inside* ``shard_map`` with fully static shapes, so
    the whole path is one jitted callee.
    """

    name = "sharded"

    def _shard_tree(self, eng):
        tree = eng._shard_tree
        if tree is None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            from repro.search.tree import ShardTreeArrays, build_shard_trees
            axis = tuple(eng.axis_names or eng.mesh.axis_names)
            sh = NamedSharding(eng.mesh, P(axis))
            # built under jit with explicit out_shardings (not eagerly +
            # device_put): each device computes only its own shard's tree,
            # and a multi-host index — whose leaves are not addressable
            # outside jit — stays legal input
            build = jax.jit(build_shard_trees,
                            out_shardings=ShardTreeArrays(sh, sh, sh))
            tree = build(eng.index)
            eng._shard_tree = tree
        return tree

    def _replicated_queries(self, eng, queries):
        """Queries as the replicated operand the sharded closure expects.

        Single-process (or under an outer trace) this is a plain
        ``jnp.asarray``; on a multi-process mesh every host passes the
        same batch and it becomes one fully-replicated global array —
        required by ``jit`` when the mesh spans processes.
        """
        q = queries
        if isinstance(q, jax.Array) and not q.is_fully_addressable:
            return q                      # already a global (multi-host) array
        if jax.process_count() > 1 and not isinstance(q, jax.core.Tracer):
            import numpy as _np

            from repro.dist.compat import replicate_to_mesh
            return replicate_to_mesh(_np.asarray(q, _np.float32), eng.mesh)
        return jnp.asarray(q, jnp.float32)

    def make_fused(self, eng, k, *, prune, element_stats, donate):
        """Query prep, each shard's search, the id map and the merge as one
        jitted ``shard_map`` callee (``make_sharded_search``)."""
        if eng.mesh is None:
            raise ValueError("the 'sharded' backend needs SearchEngine(mesh=...)")
        from repro.core.distributed import make_sharded_search
        inner = eng.shard_inner(k, prune)
        use_tree = inner == "tree"
        fn = make_sharded_search(
            eng.mesh, eng.axis_names, with_stats=True,
            trace_hook=eng._note_trace,
            inner="scan" if use_tree else inner, prune=prune,
            warm_start=eng.warm_start, best_first=eng.best_first,
            warm_start_blocks=eng.warm_start_blocks,
            element_stats=element_stats, margin=eng.margin,
            n_pivots=eng.n_pivots, bm=eng.bm, bn=eng.bn,
            sort_queries=eng.sort_queries, interpret=eng.interpret)

        def fused(index, queries):
            # the tree is fetched PER CALL, like the tree backend's: a
            # shape-stable mutation swaps in a widened twin of the same
            # shapes, which reuses the compiled executable
            tree = self._shard_tree(eng) if use_tree else None
            return fn(index, self._replicated_queries(eng, queries), k, tree)

        return fused


# the tree backend lives in its own module (it is a subsystem, not an inner
# loop) but registers here; importing it last keeps the registry complete for
# callers that import repro.search.backends directly.  Safe despite the cycle:
# this module is fully defined by the time the import runs.
from repro.search import tree as _tree  # noqa: E402,F401  (registration)
