"""SearchEngine: one front door for every exact-cosine-search path.

The paper's Eq. 13 bound is shared infrastructure; what used to differ per
path (argument conventions, stats shapes, pruning plumbing, warm-start
availability) is now owned here.  Backends (``scan`` / ``kernel`` /
``sharded`` / ``brute`` / ``tree``) are pluggable and auto-selected by
device, mesh, and shape; each one is just an inner loop (see
:mod:`repro.search.backends`; the hierarchical ``tree`` backend is the
subsystem in :mod:`repro.search.tree`).

Usage::

    eng = SearchEngine.build(db, n_pivots=16, block_size=128)
    sims, ids, stats = eng.search(queries, k=10)
    stats.block_prune_frac     # one SearchStats shape for every backend

τ warm-start and best-first block ordering are engine policy (on by
default) and apply to every backend that can use them — they only change
*how fast τ rises*, never the result set, which stays bit-identical to
brute force (property-tested in tests/test_search_engine.py).
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.index import BlockIndex, build_index
from repro.search import backends as _bk
from repro.search import defaults as _defaults
from repro.search.stats import SearchStats

__all__ = ["SearchEngine", "auto_backend", "shard_inner"]

#: below this many padded rows the matmul is cheaper than any bookkeeping
_BRUTE_MAX_ROWS = 256

#: at this many blocks the flat O(n_blocks) bound pass starts to dominate
#: and the tree's O(survivors · depth) transitive descent wins
_TREE_MIN_BLOCKS = 256

#: the widest rows the Pallas kernel holds (its feature dim stays in VMEM)
_KERNEL_MAX_DIM = 4096


def auto_backend(index: BlockIndex, mesh=None) -> str:
    """Pick a backend from device / mesh / shape.

    sharded  — index carries a stacked shard axis (built by
               ``build_sharded_index``) or a mesh was supplied;
    brute    — tiny datastore (bound evaluation would dominate);
    kernel   — on TPU, MXU-shaped work with VMEM-resident feature dim;
    tree     — deep datastores (≥ 256 blocks): the transitive Eq. 13
               descent (DESIGN.md §3.5) replaces the flat per-block bound
               pass, which at that depth dominates the work on clustered
               data;
    scan     — everywhere else (CPU/GPU, odd shapes): same pruning
               semantics, XLA-portable.
    """
    if index.db.ndim == 3 or mesh is not None:
        return "sharded"
    return _flat_backend(*index.db.shape, index.dp_min.shape[-2],
                         kernel=jax.default_backend() == "tpu")


def _flat_backend(n_pad: int, d: int, n_blocks: int, *, kernel: bool) -> str:
    if n_pad <= _BRUTE_MAX_ROWS:
        return "brute"
    if kernel and d <= _KERNEL_MAX_DIM:
        return "kernel"
    if n_blocks >= _TREE_MIN_BLOCKS:
        return "tree"
    return "scan"


def shard_inner(index: BlockIndex, k: int, *, prune: bool = True,
                tree_shards: bool | None = None,
                interpret: bool | None = None, bn: int | None = None) -> str:
    """The search each shard of a shard-stacked ``index`` runs for a call
    at ``k``: the rule :func:`auto_backend` applies to one flat shard, from
    the platform and the shard's shape.

    tree    — ``tree_shards=True``, or (``None``) wherever a flat shard
              would descend a tree: the per-shard descent with its leaf
              scan (DESIGN.md §3.6); with ``prune=False`` the flat scan;
    brute   — a tiny shard;
    kernel  — the fused Pallas kernel search: on TPU, as on one chip, or
              wherever ``interpret=True`` asks for the interpreted kernel;
              the scan where ``k`` exceeds the kernel's tile;
    scan    — everywhere else.
    """
    n_pad, d = index.db.shape[-2:]
    flat = _flat_backend(
        n_pad, d, index.dp_min.shape[-2],
        kernel=jax.default_backend() == "tpu" or interpret is True)
    if prune and (flat == "tree" if tree_shards is None else tree_shards):
        return "tree"
    if flat == "brute" or (flat == "kernel"
                           and k <= _bk._resolve_bn(index, bn)):
        return flat
    return "scan"


@functools.partial(jax.jit, static_argnames=("k",))
def _pad_topk(sims, ids, *, k: int):
    """Widen ``[m, kk]`` results to ``[m, k]`` with the ``(-inf, -1)`` fill.

    Jitted (not host numpy) so it composes with tracers when the engine
    runs inside an outer jit and with multi-host global result arrays,
    which reject eager host-side ops.
    """
    pad = k - sims.shape[1]
    return (jnp.pad(sims, ((0, 0), (0, pad)), constant_values=-jnp.inf),
            jnp.pad(ids, ((0, 0), (0, pad)), constant_values=-1))


class SearchEngine:
    """Backend-dispatched exact top-k cosine search over a BlockIndex.

    Args:
      index: a :class:`BlockIndex` (or a shard-stacked one from
        ``build_sharded_index`` together with ``mesh``).
      backend: registered backend name, or ``"auto"`` (default).
      mesh / axis_names: mesh placement for the ``sharded`` backend.
      warm_start: seed each query's running k-th-best τ by exact-scoring
        its ``ceil(k / block)`` best-bound blocks before the main pass
        (every backend; the multi-block schedule is DESIGN.md §3.4, so the
        seeding engages for every ``k``, including ``k`` > block size).
      warm_start_blocks: widen the warm-start prescan to at least this many
        bound-ranked blocks.  ``None`` (default) defers to the time-tuned
        per-regime table in :mod:`repro.search.defaults` (whose own
        fallback is the ``ceil(k / block)`` floor; pass ``0`` to force the
        floor).  More blocks = a tighter τ seed at the cost of a larger
        prescan gather; never fewer than the floor, clamped to the block
        count.
      best_first: visit database blocks in descending upper-bound order
        (per query tile) so τ rises early and later blocks prune.
        ``None`` (default) defers to the time-tuned per-regime table
        (scan/tree backends on the swept platform; ``True`` elsewhere) —
        explicit ``True`` / ``False`` always wins.
      element_stats: default for ``search(..., element_stats=...)`` — also
        report ``SearchStats.elem_prune_frac``, the fraction of (query,
        valid row) pairs whose *individual* Eq. 13 bound prunes them
        (backend-uniform; see docs/search-api.md for the glossary).
      tree_shards: ``sharded`` backend only — run the transitive Eq. 13
        descent over a per-shard pivot tree (built lazily, one tree per
        shard over its local pivots) before each shard's leaf scan, with
        the global warm-start τ broadcast into every shard's descent
        (DESIGN.md §3.6).  ``True`` / ``False`` force it; ``None``
        (default) enables it wherever a flat shard would take the
        ``tree`` backend (off the TPU, ≥ 256 blocks a shard).  Otherwise
        each shard runs the inner loop :func:`shard_inner` names (the
        kernel search on TPU).  Ignored by non-sharded backends (the ``tree``
        backend always descends).
      n_pivots: joint multi-pivot bound depth (DESIGN.md §3.8): before a
        block is admitted, the ``eq13_multi`` provider intersects the
        classic Eq. 13 interval bound with a joint projection bound over
        the first ``n_pivots`` rows of the index's orthonormalized pivot
        basis — tightest bound wins, validity is inherited pointwise.
        ``0`` disables the extra cap (the single-formula fast path);
        ``None`` (default) defers to the time-tuned per-regime table.
        Clamped to the index's bound-table width.  Consumed by the scan,
        kernel, tree and sharded backends; changing it re-keys the fused
        dispatch cache (one retrace), like every other knob.
      margin: fp32 guard added to bounds before comparing with τ.
      leaf_eval: tree-backend leaf stage — ``"scan"`` (portable, traceable
        inside an outer jit), ``"kernel"`` (compact the surviving leaves
        and run the fused Pallas kernel over just those rows;
        host-orchestrated), or ``"auto"`` (the time-tuned per-regime
        table when it binds, else kernel on TPU / scan elsewhere).
        Ignored by non-tree backends.
      bm / bn / sort_queries / interpret: kernel-backend tile options
        (ignored by other backends; ``bm`` / ``interpret`` also apply to
        the tree backend's kernel leaf stage, and all four to a sharded
        engine's shards, where ``interpret=True`` runs the kernel
        interpreted off the TPU too).  ``bm`` is the largest query tile:
        a call of ``m`` rows runs ``ceil(m / bm)`` tiles of
        ``round_up(ceil(m / tiles), 8)`` rows
        (:func:`repro.kernels.cosine_topk.query_tile`), so a 64-query
        batch at ``bm=128`` runs one 64-row tile.
    """

    def __init__(
        self,
        index: BlockIndex,
        *,
        backend: str = "auto",
        mesh=None,
        axis_names=None,
        warm_start: bool = True,
        warm_start_blocks: int | None = None,
        best_first: bool | None = None,
        element_stats: bool = False,
        tree_shards: bool | None = None,
        n_pivots: int | None = None,
        margin: float = 4e-7,
        leaf_eval: str = "auto",
        bm: int = 128,
        bn: int | None = None,
        sort_queries: bool = True,
        interpret: bool | None = None,
    ):
        if mesh is not None:
            # one mesh type on every sharded path, whatever the caller made
            from repro.core.distributed import auto_mesh, on_mesh
            mesh = auto_mesh(mesh)
            index = on_mesh(index, mesh)
        self.index = index
        self.mesh = mesh
        self.axis_names = axis_names
        self.warm_start = warm_start
        self.element_stats = element_stats
        self.margin = margin
        self.bm = bm
        self.bn = bn
        self.sort_queries = sort_queries
        self.interpret = interpret
        self._fn_cache = {}                     # fused dispatch cache
        self._traces = 0                        # jit traces observed, ever
        self._tree_index = None                 # built lazily by TreeBackend
        self._tree_valid_nodes = 0              # cached host count, ditto
        self._shard_tree = None                 # lazily by ShardedBackend
        #: bumped on every SHAPE-CHANGING online mutation (appended blocks,
        #: reoptimize); part of the fused-dispatch cache key, so
        #: shape-stable mutations keep hitting the cached executable while
        #: a grown index can never collide with a stale entry (whose
        #: donated scratch would have the old shape)
        self.index_epoch = 0
        self._online = None                     # MutableIndex handle, if any
        self.tree_shards = tree_shards
        # dp_min is [nb, P] or [S, nb, P] when shard-stacked
        per_shard_blocks = int(index.dp_min.shape[-2])
        self.backend_name = (auto_backend(index, mesh)
                             if backend == "auto" else backend)
        # time-tuned per-regime defaults (repro.search.defaults): every
        # knob left at its sentinel resolves through the measured table;
        # the regime is detected from the index's Eq. 13 interval widths
        # (one host sync here, never on the search path).  Explicit knob
        # values and non-swept backends keep the static behavior.
        self.regime = (_defaults.detect_regime(index)
                       if self.backend_name in ("scan", "tree") else None)
        self.best_first = (bool(best_first) if best_first is not None
                           else _defaults.tuned_default("best_first",
                                                        self.regime))
        self.warm_start_blocks = (
            warm_start_blocks if warm_start_blocks is not None
            else _defaults.tuned_default("warm_start_blocks", self.regime))
        if leaf_eval == "auto":
            leaf_eval = (_defaults.tuned_default("leaf_eval", self.regime)
                         or "auto")
        self.leaf_eval = leaf_eval
        # joint-bound depth: sentinel -> tuned table; always clamped to the
        # index's table width (0 on pre-PR-7 indexes without the tables)
        table_width = index.bound_table_width
        if n_pivots is None:
            n_pivots = int(_defaults.tuned_default("n_pivots", self.regime)
                           or 0)
        self.n_pivots = max(0, min(int(n_pivots), table_width))
        # a flat 2D index cannot serve the sharded backend: without this
        # check the shard_map body peels a "shard axis" off the real data
        # and dies mid-trace in an opaque reshape TypeError.  Supplying a
        # mesh auto-selects "sharded", so this is an easy construction slip.
        if self.backend_name == "sharded" and index.db.ndim != 3:
            raise ValueError(
                "the 'sharded' backend needs a shard-stacked BlockIndex "
                "(leading [S, ...] shard axis); this index is flat 2D. "
                "Build one with SearchEngine.build(db, mesh=...) or "
                "repro.core.distributed.build_sharded_index(...), or drop "
                "mesh= / pass backend='scan' to search the flat index.")
        if index.db.ndim == 3 and self.backend_name != "sharded":
            raise ValueError(
                f"a shard-stacked BlockIndex is served by the 'sharded' "
                f"backend only (got backend={self.backend_name!r}); pass "
                f"mesh= (and backend='auto') to search it.")
        self.backend = _bk.get_backend(self.backend_name)
        # index.valid may be a multi-host global array (distributed build):
        # not fully addressable, so host-side np.asarray would throw — count
        # through jit instead (the summed scalar is replicated, int() works).
        v = index.valid
        if isinstance(v, jax.Array) and not v.is_fully_addressable:
            self.n_valid = int(jax.jit(jnp.sum)(v))
        else:
            self.n_valid = int(np.asarray(v).sum())
        self.n_blocks = per_shard_blocks
        #: total padded row slots across all shards — the most candidates
        #: any search can return; k above this pads with (-inf, -1)
        self.n_slots = int(index.db.shape[-2]) * (
            int(index.db.shape[0]) if index.db.ndim == 3 else 1)

    # ------------------------------------------------------------- building
    @classmethod
    def build(
        cls,
        db,
        *,
        n_pivots: int = 16,
        block_size: int = 128,
        pivot_method: str = "maxmin",
        reorder: bool = True,
        seed: int = 0,
        mesh=None,
        distributed: bool = False,
        global_rows: int | None = None,
        bound_pivots: int | None = None,
        **engine_kw: Any,
    ) -> "SearchEngine":
        """Build the index and wrap it in an engine in one call.

        Pass ``mesh`` to build a sharded datastore served by the
        ``sharded`` backend: one shard per mesh device, each built on the
        device that holds it.  ``db`` may be a host array or a device
        array already split by rows over the mesh.  A corpus whose length
        does not split evenly over the mesh comes as such an array padded
        to a multiple of the shard count, with ``global_rows`` its true
        length: the rows at and past ``global_rows`` are padding, marked
        invalid and never returned.

        ``n_pivots`` here is the *index* pivot count (interval tables and
        joint-bound table width); ``bound_pivots`` is the engine's search
        time ``n_pivots`` knob — the joint-bound depth actually
        intersected per query (``None`` defers to the tuned table).

        ``distributed=True`` (multi-process jax; needs ``mesh``) switches
        to the process-local build: ``db`` is then only THIS host's slice
        of the datastore — the rows its shards cover, see
        :func:`repro.core.distributed.local_shard_rows` — and
        ``global_rows`` is the total logical row count across all hosts
        (defaults to ``len(db)`` only when running single-process).  No
        host materializes the full datastore; search works unchanged
        (DESIGN.md §3.7).
        """
        if bound_pivots is not None:
            engine_kw["n_pivots"] = bound_pivots
        if distributed and mesh is None:
            raise ValueError(
                "SearchEngine.build(distributed=True) needs mesh= (the "
                "global mesh the datastore shards across)")
        if mesh is not None:
            from repro.core.distributed import (build_sharded_index_local,
                                                local_shard_rows)
            axis_names = engine_kw.get("axis_names")
            if not hasattr(db, "shape"):
                db = np.asarray(db, np.float32)
            if global_rows is None:
                if distributed and jax.process_count() > 1:
                    raise ValueError(
                        "SearchEngine.build(distributed=True) on a "
                        "multi-process mesh needs global_rows= (the total "
                        "datastore rows across all hosts; db holds only "
                        "this host's slice, so the split cannot be "
                        "inferred from it)")
                global_rows = int(db.shape[0])
            if not distributed and jax.process_count() > 1:
                # every host passed the whole datastore: keep its own rows
                _, owned = local_shard_rows(global_rows, mesh, axis_names)
                db = np.concatenate([np.asarray(db[a:b], np.float32)
                                     for _, a, b in owned])
            idx = build_sharded_index_local(
                db, mesh, global_rows=global_rows, axis_names=axis_names,
                n_pivots=n_pivots, block_size=block_size,
                pivot_method=pivot_method)
            return cls(idx, mesh=mesh, **engine_kw)
        idx = build_index(db, n_pivots=n_pivots, block_size=block_size,
                          pivot_method=pivot_method, reorder=reorder,
                          seed=seed)
        return cls(idx, **engine_kw)

    # ------------------------------------------------------------- mutation
    def online(self, **kw) -> "Any":
        """The engine's :class:`~repro.core.online.MutableIndex` handle
        (created on first use; one per engine).  Insert/delete/reoptimize
        through it — the engine's index, tree and dispatch caches stay
        consistent automatically.  Keyword args (``reoptimize_threshold``,
        ``auto_reoptimize``) are forwarded on first creation only.

        Sharded engines get a :class:`~repro.core.online.
        ShardedMutableIndex` — same surface, plus the deterministic
        cross-host row-placement protocol (DESIGN.md §3.10).
        """
        if self._online is None:
            from repro.core.online import MutableIndex, ShardedMutableIndex
            cls = (ShardedMutableIndex if self.index.db.ndim == 3
                   else MutableIndex)
            self._online = cls(self, **kw)
        elif kw:
            raise ValueError(
                "engine.online() already created its MutableIndex; "
                "per-handle options can only be set on the first call")
        return self._online

    def _apply_mutation(self, new_index: BlockIndex, *, n_valid: int,
                        shape_changed: bool, tree=None,
                        tree_valid_nodes: int | None = None,
                        shard_tree=None) -> None:
        """Install a mutated index (called by the
        :mod:`~repro.core.online` handles only).

        Shape-stable mutations keep every cached executable: the index is
        an *argument* of the fused callees, so fresh arrays of the same
        shape flow through the compiled code with zero retraces.  Shape
        changes (appended blocks, reoptimize) bump ``index_epoch``, drop
        the dispatch caches (their donated scratch buffers carry the old
        shapes) and invalidate the lazily built trees.

        ``tree`` / ``shard_tree`` carry the conservatively widened flat
        :class:`~repro.search.tree.TreeIndex` / stacked
        :class:`~repro.search.tree.ShardTreeArrays` twin for shape-stable
        inserts under a live tree.  Sharded deletes need no refresh at
        all: ``ShardTreeArrays`` does not embed the index, so the wide
        node caches keep serving the new index arrays as-is.
        """
        self.index = new_index
        self.n_valid = int(n_valid)
        if shape_changed:
            self.index_epoch += 1
            self._fn_cache.clear()
            self._tree_index = None
            self._tree_valid_nodes = 0
            self._shard_tree = None
            self.n_blocks = int(new_index.dp_min.shape[-2])
            self.n_slots = int(new_index.db.shape[-2]) * (
                int(new_index.db.shape[0]) if new_index.db.ndim == 3 else 1)
            return
        if shard_tree is not None:
            self._shard_tree = shard_tree
        if tree is not None:
            self._tree_index = tree
            if tree_valid_nodes is not None:
                self._tree_valid_nodes = int(tree_valid_nodes)
        elif self._tree_index is not None:
            # validity flipped under an existing tree (tombstone delete):
            # the node caches stay conservatively wide, but the tree must
            # serve the NEW index arrays
            self._tree_index = self._tree_index._replace(index=new_index)

    # ------------------------------------------------- fused dispatch cache
    def _note_trace(self):
        """Trace-time side effect: fused callables call this from inside
        their traced bodies, so it fires exactly once per jit trace and
        never on a cached dispatch — the retrace counter behind
        ``SearchStats.retraces`` and the ``engine.traces`` counter."""
        self._traces += 1
        obs.count("engine.traces")

    def shard_inner(self, k: int, prune: bool = True) -> str:
        """The search each shard runs for a call at ``k`` (the sharded
        backend): :func:`shard_inner` over this engine's index and knobs."""
        return shard_inner(self.index, k, prune=prune,
                           tree_shards=self.tree_shards,
                           interpret=self.interpret, bn=self.bn)

    def _knob_key(self):
        return (self.warm_start, self.warm_start_blocks, self.best_first,
                self.margin, self.leaf_eval, self.bm, self.bn,
                self.sort_queries, self.interpret, self.n_pivots)

    def _fused_callable(self, queries, kk: int, prune: bool,
                        element_stats: bool):
        """The cached one-dispatch callee for this call signature, or
        ``None`` when the backend (or this configuration) has no fused
        path and the legacy ``backend.run`` multi-dispatch is used.

        Keyed on ``(backend, k, query shape, dtype, knobs)``: a repeated
        call hits both this cache and the callee's compiled executable
        (0 retraces); changing ``k`` or the batch shape misses exactly
        once.  The cache entry also owns the donated scratch buffer the
        scan backend's best-first permutation cycles through.
        """
        make = getattr(self.backend, "make_fused", None)
        if make is None or len(getattr(queries, "shape", ())) != 2:
            return None
        # donated scratch needs a concrete buffer to cycle; under an outer
        # trace (serve decode) use the donation-free variant of the callee
        donate = (self.backend_name == "scan" and self.best_first
                  and not isinstance(queries, jax.core.Tracer))
        key = (self.backend_name, kk, tuple(queries.shape),
               str(queries.dtype), prune, element_stats, donate,
               self.index_epoch, self._knob_key())
        entry = self._fn_cache.get(key)
        if entry is None:
            fn = make(self, kk, prune=prune, element_stats=element_stats,
                      donate=donate)
            entry = [fn, None]          # None fn = remembered "unsupported"
            self._fn_cache[key] = entry
        if entry[0] is None:
            return None
        if not donate:
            return lambda q: entry[0](self.index, q)

        def call(q):
            scratch = entry[1]
            if scratch is None:
                nb, bs = self.n_blocks, self.index.block_size
                scratch = jnp.zeros((nb, bs, self.index.db.shape[-1]),
                                    jnp.float32)
            sims, ids, raw, scratch_out = entry[0](self.index, q, scratch)
            entry[1] = scratch_out      # cycle: donated next call
            return sims, ids, raw

        return call

    # ------------------------------------------------------------ searching
    def search(self, queries, k: int, *, prune: bool = True,
               element_stats: bool | None = None):
        """Exact top-k: ``(sims [m,k] f32, ids [m,k] i32, SearchStats)``.

        ``ids`` are original database row ids (-1 marks empty slots when
        ``k`` exceeds the number of valid rows).  The result set is
        identical to brute force for every backend and policy setting.
        ``element_stats`` defaults to the engine-level knob; pass True to
        also get ``SearchStats.elem_prune_frac`` for this call.

        ``k`` may exceed the datastore size: the backends run at
        ``min(k, n_slots)`` and the tail pads with ``(-inf, -1)`` — the
        same fill the valid-row contract above already uses, applied
        uniformly here so no backend's inner ``top_k`` sees a k wider
        than its score matrix.

        The steady-state hot path is one jitted dispatch: query prep, the
        τ prescan, the backend inner loop and the id mapping are fused
        into a per-``(backend, k, shape, knobs)`` cached callee (see
        ``SearchStats.retraces`` — 0 on a warm call).  Backends without a
        fusable configuration fall back to the legacy multi-dispatch
        ``backend.run``.
        """
        if element_stats is None:
            element_stats = self.element_stats
        if not hasattr(queries, "shape"):
            queries = jnp.asarray(queries)
        kk = min(k, self.n_slots)
        notes = {}
        if self.backend_name == "sharded":
            notes.update(shards=int(self.index.db.shape[0]),
                         inner=self.shard_inner(kk, prune))
        m = int(queries.shape[0])
        if "kernel" in (self.backend_name, notes.get("inner")):
            # which orientation the kernel reads the stored corpus in, and
            # the query tile's height it runs for this batch
            notes["db_layout"] = _bk.cosine_topk.db_layout(
                self.index.db.shape[-1])
            notes["bm"] = _bk.cosine_topk.query_tile(m, self.bm)
        with obs.span("engine.search", backend=self.backend_name, k=k,
                      m=m, **notes) as call:
            traces_before = self._traces
            with obs.span("engine.dispatch"):
                fused = self._fused_callable(queries, kk, prune,
                                             element_stats)
                if fused is not None:
                    sims, ids, raw = fused(queries)
                    retraces = self._traces - traces_before
                else:
                    # multi-dispatch (the tree's kernel leaves): unknown
                    sims, ids, raw = self.backend.run(
                        self, queries, kk, prune=prune,
                        element_stats=element_stats)
                    retraces = None
            if kk < k:
                sims, ids = _pad_topk(sims, ids, k=k)
            stats = SearchStats(
                backend=self.backend_name,
                n_queries=m,
                k=k,
                n_blocks=self.n_blocks,
                block_prune_frac=raw.get("block_prune_frac", 0.0),
                tile_computed_frac=raw.get("tile_computed_frac"),
                elem_prune_frac=raw.get("elem_prune_frac"),
                tree_prune_frac=raw.get("tree_prune_frac"),
                tree_node_eval_frac=raw.get("tree_node_eval_frac"),
                merge_rounds=raw.get("merge_rounds"),
                warm_start=self.warm_start,
                best_first=self.best_first,
                n_pivots=(None if self.backend_name == "brute"
                          else self.n_pivots),
                retraces=retraces,
                generation=(self._online.generation
                            if self._online is not None else None),
                decay_estimate=(self._online.decay_estimate
                                if self._online is not None else None),
                extras={k_: v for k_, v in raw.items()
                        if k_ not in ("block_prune_frac",
                                      "tile_computed_frac",
                                      "elem_prune_frac", "tree_prune_frac",
                                      "tree_node_eval_frac",
                                      "merge_rounds")},
            )
            # under an outer jit the stats are tracers: keep none
            call.note(retraced=retraces,
                      stats=None if isinstance(queries, jax.core.Tracer)
                      else stats)
        return sims, ids, stats
