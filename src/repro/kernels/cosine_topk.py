"""Pallas kernel: fused block-pruned exact cosine top-k (the paper, on MXU).

One kernel implements the whole search inner loop of
:mod:`repro.core.index`:

  for each query tile i (grid dim 0):
    for each database tile j (grid dim 1, sequential):
      1. evaluate the Eq. 13 pivot-interval upper bound for tile j   (VPU)
      2. if no query in the tile can beat its running k-th best: SKIP —
         ``@pl.when`` guards the matmul and the top-k merge entirely
      3. else: scores = q_tile @ db_tile.T                           (MXU)
         merge into the running top-k held in the resident output block

The running (top_s, top_i) block persists across the sequential j steps
(TPU grid iteration order guarantees this) and is sorted once, outside
the kernel.  The merge keeps the k slots *unsorted*: each round moves
every row's best remaining tile score into that row's current minimum
slot, and the rounds stop as soon as no row's best remaining score beats
its k-th best — after a warm τ most computed tiles need zero or one
round, and no round allocates anything wider than ``[BM, BN]``.  The
rounds are counted, per query tile, into an SMEM output
(``SearchStats.merge_rounds``).

Layout (what Mosaic's (8, 128) tiling rule forced when the kernel was
first compiled for TPU v5e): per-tile pivot intervals and row validity
ride in 3-D arrays whose last two dims are whole (``[nt, 1, P]``,
``[nt, 1, BN]``), the per-(query tile, db tile) ``computed`` / element
counters are whole-array SMEM outputs written at ``[i, tile]``, the
optional joint cap is fetched as a lane-dense ``[BM, 128]`` slab and its
column selected in-kernel, and the score matmul runs at
``Precision.HIGHEST`` (an f32 dot otherwise runs as one bf16 pass on the
MXU, ~1e-3 off, which the ``margin`` cannot absorb — DESIGN.md §3.12).
The HBM->VMEM copy of a pruned tile is not yet elided.

Alignment: BM a multiple of 8, BN a multiple of 128 on TPU; D is kept
whole in VMEM (q tile + db tile at BM=128, BN=256, D=768, f32 = 1.1 MiB).
``bm`` is the largest query tile: a call of fewer rows runs the same
number of tiles, each only as tall as its share of the rows needs
(:func:`query_tile`), so a padded row is never computed for nothing.

Orientation (:func:`db_layout`): the TPU runtime stores an f32 ``[N, D]``
array column-major when D is not a multiple of 128, so the kernel then
reads the corpus as ``db.T`` (``[D, N]``, a bitcast of the stored buffer)
in ``(D, BN)`` blocks.  Read as ``[N, D]`` it would be laid out again,
lane-padded, on every call.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import Array
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BM = 128
DEFAULT_BN = 256
_NEG_INF = float("-inf")
_LANES = 128
_SUBLANES = 8                                   # f32 rows of one vreg tile


def db_layout(d: int) -> str:
    """The orientation the kernels read a stored ``[N, d]`` f32 corpus in.

    ``"rows"`` (``[N, d]`` blocks) where ``d`` is a multiple of 128, which
    the runtime stores row-major; ``"cols"`` (``db.T``, ``[d, N]`` blocks)
    otherwise, where it stores the array column-major, ``{0,1}`` (AOT
    compile for v5e: widths 64, 96, 100 and 200 are column-major; 128,
    256, 384, 768 and 1024 row-major).
    """
    return "rows" if d % _LANES == 0 else "cols"


def query_tile(m: int, bm: int) -> int:
    """The query tile's height for a call of ``m`` query rows.

    ``bm`` is the largest tile.  The call runs ``ceil(m / bm)`` tiles, as
    many as ``bm``-row tiles would take, of ``round_up(ceil(m / tiles), 8)``
    rows (8 is the f32 sublane tile), never more than ``bm``: a 64-row call
    at ``bm=128`` runs one 64-row tile, a 256-row call two 128-row tiles.
    """
    tiles = max(1, -(-m // bm))
    rows = -(-m // tiles)
    return min(bm, max(_SUBLANES, -(-rows // _SUBLANES) * _SUBLANES))


def tile_order(ub: Array, bm: int) -> Array:
    """Best-first ``block_order`` for :func:`pruned_topk`: each query
    tile's db tiles by descending tile bound, the max of ``ub`` [m, nt]
    over the tile's rows (:func:`query_tile` rows each) -> [tiles, nt] i32.
    """
    m, nt = ub.shape
    rows = query_tile(m, bm)
    mp = -(-m // rows) * rows
    ub_p = jnp.pad(ub, ((0, mp - m), (0, 0)), constant_values=_NEG_INF)
    tile_ub = ub_p.reshape(mp // rows, rows, nt).max(axis=1)
    return jnp.argsort(-tile_ub, axis=1).astype(jnp.int32)


def _make_kernel(k: int, bm: int, bn: int, margin: float, prune: bool,
                 element_stats: bool, use_cap: bool = False,
                 cols: bool = False):
    def kernel(order_ref, mvalid_ref, tau_ref, qn_ref, db_ref, qp_ref,
               lo_ref, hi_ref, rv_ref, *rest):
        rest = list(rest)
        cap_ref = rest.pop(0) if use_cap else None
        dp_ref = rest.pop(0) if element_stats else None
        top_s, top_i, computed_ref, rounds_ref = rest[:4]
        elem_ref = rest[4] if element_stats else None
        sc_ref = rest[-1]
        i = pl.program_id(0)
        j = pl.program_id(1)
        kp = top_s.shape[1]
        # best-first: step j of query tile i visits db tile order[i, j]
        # (the BlockSpec index maps fetched that tile; this is the global
        # tile id for id bookkeeping and the counters)
        jb = order_ref[i, j]

        @pl.when(j == 0)
        def _init():
            # warm-start: seed every slot with tau[q] (a true lower bound on
            # the query's k-th best similarity, from a cheap pre-scan of its
            # best-bound block) so early tiles already prune; -inf when
            # disabled.  The seed sits a hair below the real value so that
            # genuine candidates with sim == tau strictly displace seeds —
            # exactness is preserved because >= k real candidates reach tau.
            top_s[...] = jnp.broadcast_to(tau_ref[...], top_s.shape)
            top_i[...] = jnp.full(top_i.shape, -1, jnp.int32)
            rounds_ref[i] = jnp.int32(0)

        qp = qp_ref[...].astype(jnp.float32)              # [BM, P]
        lo = lo_ref[...].astype(jnp.float32)              # [1, P]
        hi = hi_ref[...].astype(jnp.float32)
        rad_q = jnp.maximum(0.0, 1.0 - qp * qp)
        ub_l = qp * lo + jnp.sqrt(rad_q * jnp.maximum(0.0, 1.0 - lo * lo))
        ub_h = qp * hi + jnp.sqrt(rad_q * jnp.maximum(0.0, 1.0 - hi * hi))
        per_p = jnp.where((qp >= lo) & (qp <= hi), 1.0, jnp.maximum(ub_l, ub_h))
        # empty-block sentinel (lo=+inf > hi=-inf, all rows invalid): the
        # raw formula yields NaN (qp=0) or +inf here.  Both are safe —
        # NaN >= tau is False so the tile skips; +inf computes the tile and
        # vmask masks every row.  No explicit branch needed in-kernel.
        ub = per_p.min(axis=1, keepdims=True)             # [BM, 1]
        if use_cap:
            # extra pivot-similarity operand: the precomputed joint
            # multi-pivot cap for this (query row, visited tile), fetched as
            # the 128-tile slab holding column jb — min of valid upper
            # bounds is a valid upper bound (DESIGN.md §3.8)
            slab = cap_ref[...]                           # [BM, 128]
            lane = jax.lax.broadcasted_iota(jnp.int32, slab.shape, 1)
            cap = jnp.max(jnp.where(lane == jb % _LANES, slab, _NEG_INF),
                          axis=1, keepdims=True)
            ub = jnp.minimum(ub, cap)

        lane_k = jax.lax.broadcasted_iota(jnp.int32, (bm, kp), 1)
        in_k = lane_k < k

        def kth_best(top):
            # the running k-th best is the MIN of the k (unsorted) slots
            return jnp.min(jnp.where(in_k, top, jnp.inf), axis=1,
                           keepdims=True)                 # [BM, 1]

        tau = kth_best(top_s[...])
        row = i * bm + jax.lax.broadcasted_iota(jnp.int32, (bm, 1), 0)
        live = row < mvalid_ref[0]                        # padded query rows
        # per-row db validity for this tile: padding AND tombstoned rows.
        # Mutable indexes (repro.core.online) tombstone-delete in place, so
        # valid rows need not be a prefix — a scalar n_valid cut-off would
        # score deleted rows into the top-k.
        vmask = rv_ref[...] > 0                           # [1, BN]
        if prune:
            # padded query rows (>= m_valid) must not force computation
            needed = jnp.any((ub + margin >= tau) & live)
        else:
            needed = True

        if element_stats:
            # per-(query, row) Eq. 13 bound vs the running τ at visit time —
            # the same statistic the scan backend accumulates, so
            # elem_prune_frac is backend-uniform.  Counted regardless of
            # whether the tile matmul itself was skipped (the statistic
            # measures bound power, not work done); unrolled over the P
            # pivots to keep intermediates at [BM, BN].
            dpv = dp_ref[...].astype(jnp.float32)         # [P, BN]
            eub = None
            for p_i in range(dpv.shape[0]):
                a = qp[:, p_i:p_i + 1]                    # [BM, 1]
                b = dpv[p_i:p_i + 1, :]                   # [1, BN]
                rad = rad_q[:, p_i:p_i + 1] * jnp.maximum(0.0, 1.0 - b * b)
                cand = a * b + jnp.sqrt(rad)
                eub = cand if eub is None else jnp.minimum(eub, cand)
            epruned = (eub + margin < tau) & vmask & live
            elem_ref[i, jb] = jnp.sum(epruned.astype(jnp.int32))

        @pl.when(needed)
        def _compute():
            # db tile [BN, D] (rows) or [D, BN] (cols): contract over D
            scores = jax.lax.dot_general(
                qn_ref[...], db_ref[...], (((1,), (0 if cols else 1,)),
                                           ((), ())),
                precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32,
            )                                             # [BM, BN]
            sc_ref[...] = jnp.where(vmask, scores, _NEG_INF)  # pad/tombstone
            lane_n = jax.lax.broadcasted_iota(jnp.int32, (bm, bn), 1)

            def best_left(s, top):
                # rows whose best remaining tile score beats their k-th best
                return (jnp.max(s, axis=1, keepdims=True) > kth_best(top)) & live

            def merge_round(carry):
                _, n = carry
                s, top, ids = sc_ref[...], top_s[...], top_i[...]
                m = jnp.max(s, axis=1, keepdims=True)
                kth = kth_best(top)
                take = (m > kth) & live
                # first lane holding the row max / the row's k-th best slot
                am = jnp.min(jnp.where(s == m, lane_n, bn), axis=1,
                             keepdims=True)
                slot = jnp.min(jnp.where(in_k & (top == kth), lane_k, kp),
                               axis=1, keepdims=True)
                put = take & (lane_k == slot)
                top = jnp.where(put, m, top)
                top_s[...] = top
                top_i[...] = jnp.where(put, jb * bn + am, ids)
                s = jnp.where(take & (lane_n == am), _NEG_INF, s)
                sc_ref[...] = s
                return jnp.any(best_left(s, top)), n + 1

            _, n = jax.lax.while_loop(
                lambda carry: carry[0], merge_round,
                (jnp.any(best_left(sc_ref[...], top_s[...])), jnp.int32(0)))
            rounds_ref[i] = rounds_ref[i] + n

        computed_ref[i, jb] = (jnp.asarray(needed).astype(jnp.int32)
                               if prune else jnp.int32(1))

    return kernel


@functools.partial(
    jax.jit,
    static_argnames=("k", "bm", "bn", "margin", "prune", "interpret",
                     "element_stats", "db_layout"),
)
def pruned_topk(
    qn: Array,
    db: Array,
    qp: Array,
    dp_min: Array,
    dp_max: Array,
    n_valid: Array | int,
    m_valid: Array | int | None = None,
    tau_init: Array | None = None,
    block_order: Array | None = None,
    dp: Array | None = None,
    ub_cap: Array | None = None,
    row_valid: Array | None = None,
    *,
    k: int,
    bm: int = DEFAULT_BM,
    bn: int = DEFAULT_BN,
    margin: float = 4e-7,
    prune: bool = True,
    interpret: bool = False,
    element_stats: bool = False,
    db_layout: str = "rows",
):
    """Fused exact top-k with block pruning.

    Args:
      qn:      [M, D] L2-normalized queries.
      db:      [N, D] L2-normalized database (padding rows at the END), or
               its transpose [D, N] when ``db_layout="cols"``.
      qp:      [M, P] query-pivot similarities.
      dp_min/dp_max: [N // bn, P] pivot intervals at KERNEL tile granularity
               (use :func:`repro.search.backends.coarsen_intervals`).
      n_valid: number of real rows in db.
      tau_init: [M] optional τ warm-start seeds (true lower bounds on each
               query's k-th best; see SearchEngine and DESIGN.md §3.4 for
               the multi-block prescan that produces them).
      block_order: [M_tiles, N_tiles] i32 optional per-query-tile db tile
               visiting order (best-first).  Scalar-prefetched: the
               BlockSpec index maps read it, so a pruned tile's HBM->VMEM
               copy targets the *bound-ordered* tile, and sequential steps
               see monotonically less useful tiles — τ rises early.
               Identity order when None.
      dp:      [N, P] per-row pivot similarities; required when
               ``element_stats`` (the per-element Eq. 13 bound needs them).
      ub_cap:  [M, N_tiles] optional extra per-(query, db tile) upper
               bounds (the joint multi-pivot cap, DESIGN.md §3.8),
               min'd into the interval bound inside the kernel before the
               skip test.  Must be valid upper bounds on every score in
               the tile; exactness is the caller's obligation.
      row_valid: [N] optional bool/int per-row validity.  ``None`` (the
               frozen-index case) derives the classic prefix mask
               ``arange(N) < n_valid``.  Pass the index's ``valid`` vector
               when rows can be tombstoned in place (mutable indexes,
               DESIGN.md §3.9): the kernel masks scores per ROW, so
               validity need not be a prefix.
      k:       top-k (k <= bn).
      bm:      the largest query tile; the kernel runs ``ceil(M / bm)``
               tiles of :func:`query_tile` rows.
      element_stats: also count, per visited tile, the (query, row) pairs
               whose individual Eq. 13 bound is below the running τ — the
               backend-uniform ``elem_prune_frac`` numerator.
      db_layout: ``"rows"`` or ``"cols"``, the orientation ``db`` is
               given in; :func:`db_layout` picks the one that reads a
               stored corpus without relayout.

    Returns (sims [M, k] f32 descending, idx [M, k] i32 positions into db,
    computed [M_tiles, N_tiles] i32 — which db tiles did real work, indexed
    by TILE id, not visit step — elem_pruned [M_tiles, N_tiles] i32
    per-tile pruned-element counts, ``None`` unless ``element_stats``, and
    merge_rounds [M_tiles] i32, the top-k merge rounds each query tile ran
    summed over its computed tiles).
    """
    m, d = qn.shape
    cols = db_layout == "cols"
    assert cols or db_layout == "rows", db_layout
    n = db.shape[1] if cols else db.shape[0]
    p = qp.shape[1]
    assert n % bn == 0 and dp_min.shape[0] == n // bn, (n, bn, dp_min.shape)
    assert k <= bn, "k must fit in one db tile"
    if element_stats and dp is None:
        raise ValueError("element_stats=True requires dp ([N, P] per-row "
                         "pivot similarities)")
    bm = query_tile(m, bm)
    mp = -(-m // bm) * bm
    nt = n // bn
    kp = -(-k // _LANES) * _LANES                  # lane-dense top-k slots
    qn_p = jnp.pad(qn, ((0, mp - m), (0, 0)))
    # padded query rows are masked out of the prune predicate via m_valid
    qp_p = jnp.pad(qp, ((0, mp - m), (0, 0)), constant_values=1.0)
    if m_valid is None:
        m_valid = m
    mv = jnp.asarray(m_valid, jnp.int32).reshape(1)
    if row_valid is None:
        row_valid = jnp.arange(n) < jnp.asarray(n_valid, jnp.int32)
    rv = row_valid.astype(jnp.int32).reshape(nt, 1, bn)
    if tau_init is None:
        tau = jnp.full((mp, 1), _NEG_INF, jnp.float32)
    else:
        tau = jnp.pad(tau_init.reshape(m, 1).astype(jnp.float32) - 1e-6,
                      ((0, mp - m), (0, 0)), constant_values=_NEG_INF)
    grid = (mp // bm, nt)
    if block_order is None:
        block_order = jnp.broadcast_to(
            jnp.arange(grid[1], dtype=jnp.int32)[None, :], grid)
    block_order = block_order.astype(jnp.int32)
    assert block_order.shape == grid, (block_order.shape, grid)
    use_cap = ub_cap is not None
    kern = _make_kernel(k, bm, bn, margin, prune, element_stats,
                        use_cap=use_cap, cols=cols)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    out_shape = [
        jax.ShapeDtypeStruct((mp, kp), jnp.float32),
        jax.ShapeDtypeStruct((mp, kp), jnp.int32),
        jax.ShapeDtypeStruct(grid, jnp.int32),
        jax.ShapeDtypeStruct(grid[:1], jnp.int32),
    ]
    in_specs = [
        pl.BlockSpec((bm, 1), lambda i, j, ord_, mv_: (i, 0)),  # tau seeds
        pl.BlockSpec((bm, d), lambda i, j, ord_, mv_: (i, 0)),  # qn
        (pl.BlockSpec((d, bn), lambda i, j, ord_, mv_: (0, ord_[i, j]))
         if cols else
         pl.BlockSpec((bn, d), lambda i, j, ord_, mv_: (ord_[i, j], 0))),  # db
        pl.BlockSpec((bm, p), lambda i, j, ord_, mv_: (i, 0)),  # qp
        pl.BlockSpec((None, 1, p),
                     lambda i, j, ord_, mv_: (ord_[i, j], 0, 0)),  # lo
        pl.BlockSpec((None, 1, p),
                     lambda i, j, ord_, mv_: (ord_[i, j], 0, 0)),  # hi
        pl.BlockSpec((None, 1, bn),
                     lambda i, j, ord_, mv_: (ord_[i, j], 0, 0)),  # row valid
    ]
    # computed is indexed by the VISITED tile id, not the step; the merge
    # rounds are summed per query tile
    out_specs = [
        pl.BlockSpec((bm, kp), lambda i, j, ord_, mv_: (i, 0)),
        pl.BlockSpec((bm, kp), lambda i, j, ord_, mv_: (i, 0)),
        smem,
        smem,
    ]
    operands = [block_order, mv, tau, qn_p, db, qp_p,
                dp_min.reshape(nt, 1, p), dp_max.reshape(nt, 1, p), rv]
    if use_cap:
        assert ub_cap.shape == (m, nt), (ub_cap.shape, m, nt)
        # padded query rows carry cap 0: their ub shrinks, but the prune
        # predicate already masks them out via m_valid / `live`.  Tile
        # columns pad to whole 128-lane slabs.
        ntc = -(-nt // _LANES) * _LANES
        cap_p = jnp.pad(ub_cap.astype(jnp.float32),
                        ((0, mp - m), (0, ntc - nt)))
        in_specs.append(pl.BlockSpec(
            (bm, _LANES), lambda i, j, ord_, mv_: (i, ord_[i, j] // _LANES)))
        operands.append(cap_p)
    if element_stats:
        # [nt, P, BN]: each visited tile's pivot similarities, lane-dense
        dp_t = dp.reshape(nt, bn, p).transpose(0, 2, 1)
        in_specs.append(pl.BlockSpec(
            (None, p, bn), lambda i, j, ord_, mv_: (ord_[i, j], 0, 0)))
        operands.append(dp_t)
        out_shape.append(jax.ShapeDtypeStruct(grid, jnp.int32))
        out_specs.append(smem)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                        # block_order, m_valid
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],  # merge scores
    )
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=out_shape,
        interpret=interpret,
        name="pruned_topk",
    )(*operands)
    top_s, top_i, computed, rounds = out[:4]
    elem = out[4] if element_stats else None
    # the k slots are unsorted in-kernel; order them once here
    sims, sel = jax.lax.top_k(top_s[:m, :k], k)
    idx = jnp.take_along_axis(top_i[:m, :k], sel, axis=1)
    return sims, idx, computed, elem, rounds
