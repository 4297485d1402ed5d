"""Online mutation for a live :class:`~repro.search.SearchEngine`.

The block index (DESIGN.md §3.1) is a static pytree built for a frozen
corpus; this module makes it *mutable* without giving up the bound
machinery.  The trick is that every structure the search paths consult is
valid under **conservative widening** (DESIGN.md §3.9):

* inserts write rows into free padded slots (block tails, or freshly
  appended all-padding blocks) and only *loosen* the per-block pivot
  intervals ``dp_min/dp_max`` and the tree's node caches — a looser
  interval can only make the Eq. 13 upper bound larger, so bounds remain
  true upper bounds and search stays exact;
* deletes are tombstones: flip ``valid`` off and leave every interval
  untouched — stale-but-wide bounds never exclude a live row, and all
  backends mask scores by per-row validity *before* top-k, so a
  tombstoned row can never be returned.

Widening degrades pruning power over time (intervals only grow,
tombstones keep paying their bound checks), so the handle tracks a
*pruning-decay estimate* — mutated rows as a fraction of the corpus size
at the last (re)build — and triggers a deferred :meth:`reoptimize`
(full rebuild: repack live rows, reselect pivots, tighten everything)
once it crosses a threshold.

Mutations are classified by whether the pytree *shapes* change:

* shape-stable (tail inserts, deletes): the new index flows as an
  argument through the engine's cached fused executables — zero
  retraces (the dispatch key's ``index_epoch`` is unchanged);
* shape-changing (appended blocks, reoptimize): the engine bumps
  ``index_epoch`` and drops its dispatch caches, so the next search
  pays exactly one retrace at the new shape.

Sharded (multi-host / multi-device) engines are mutable too, through
:class:`ShardedMutableIndex` (``SearchEngine.online()`` picks the right
handle automatically): external ids come from a replicated monotone
counter, a deterministic placement protocol maps each id to an owning
shard as a pure function of replicated host state (so every process
decides identically with no extra collectives — DESIGN.md §3.10), and the
widening machinery above is applied per shard through vmapped masked
scatters (:func:`repro.core.distributed.make_sharded_mutation`).

External row ids are stable across the handle's lifetime: the ids
returned by :meth:`insert` (and the original ``0..n-1`` corpus ids)
survive :meth:`reoptimize` unchanged, so id-aligned side tables (e.g.
the kNN-LM value array, :mod:`repro.serve.knnlm`) never need remapping.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
import numpy as np

from repro.core.index import BlockIndex, build_index

__all__ = ["MutableIndex", "ShardedMutableIndex"]


def _append_blocks(index: BlockIndex, n_add: int) -> BlockIndex:
    """Grow the index by ``n_add`` all-padding blocks (``valid`` False,
    ``row_ids`` -1) — a pure shape change; no live row moves.

    New blocks carry the *empty-interval sentinel* ``dp_min = +inf,
    dp_max = -inf``: every bound path maps an inverted interval to a -inf
    upper bound (empty blocks prune unconditionally), and the insert
    scatter-min/max against the sentinel records the first rows' EXACT
    interval.  The old neutral ``[0, 0]`` seed permanently anchored every
    appended block's interval at zero — a block whose rows all sit in e.g.
    ``[0.6, 0.9]`` was stuck with the loose ``[0, 0.9]`` until reoptimize.
    """
    bs = index.block_size
    nr = n_add * bs
    p = index.dp.shape[1]
    zrows = jnp.zeros((nr, index.db.shape[1]), index.db.dtype)
    zdp = jnp.zeros((nr, p), index.dp.dtype)
    new = index._replace(
        db=jnp.concatenate([index.db, zrows]),
        dp=jnp.concatenate([index.dp, zdp]),
        valid=jnp.concatenate([index.valid,
                               jnp.zeros((nr,), index.valid.dtype)]),
        row_ids=jnp.concatenate([index.row_ids,
                                 jnp.full((nr,), -1, jnp.int32)]),
        dp_min=jnp.concatenate([index.dp_min,
                                jnp.full((n_add, p), jnp.inf,
                                         index.dp_min.dtype)]),
        dp_max=jnp.concatenate([index.dp_max,
                                jnp.full((n_add, p), -jnp.inf,
                                         index.dp_max.dtype)]),
    )
    if index.beta is not None:
        new = new._replace(
            beta=jnp.concatenate([index.beta, zdp]),
            beta_nsq=jnp.concatenate([index.beta_nsq, zdp]),
        )
    return new


class MutableIndex:
    """Insert/delete/reoptimize handle over a ``SearchEngine``'s index.

    Obtain one via :meth:`SearchEngine.online`; do not construct two
    handles over the same engine (the handle owns host-side mirrors —
    the free-slot list and the external-id → slot map — that must stay
    in sync with the device arrays).

    Args:
      engine: the engine to mutate (single-shard backends only).
      reoptimize_threshold: trigger a full rebuild once
        ``decay_estimate`` (mutated rows / corpus size at last build)
        reaches this value.
      auto_reoptimize: if False, never rebuild implicitly — the caller
        watches ``decay_estimate`` and calls :meth:`reoptimize` at a
        convenient moment (e.g. off the serving hot path).
    """

    def __init__(self, engine, *, reoptimize_threshold: float = 0.5,
                 auto_reoptimize: bool = True):
        index = engine.index
        if engine.backend_name == "sharded" or index.db.ndim != 2:
            raise TypeError(
                "MutableIndex serves flat single-shard engines; sharded "
                "engines are mutated through ShardedMutableIndex — "
                "engine.online() picks the right handle automatically")
        self.engine = engine
        self.reoptimize_threshold = float(reoptimize_threshold)
        self.auto_reoptimize = bool(auto_reoptimize)
        #: total mutation calls applied through this handle (also
        #: surfaced as ``SearchStats.generation``)
        self.generation = 0
        self._mutations_since_opt = 0
        row_ids = np.asarray(index.row_ids)
        self._id_pos = {int(r): int(p) for p, r in enumerate(row_ids)
                        if r >= 0}
        # descending so list.pop() hands out the lowest free slot first
        # (keeps inserts packed toward block fronts)
        self._free = sorted(
            np.flatnonzero(row_ids < 0).tolist(), reverse=True)
        self._next_id = max(self._id_pos, default=-1) + 1
        self._rows_at_opt = max(1, len(self._id_pos))

    # ------------------------------------------------------------- inspect
    @property
    def n_live(self) -> int:
        """Number of live (searchable) rows."""
        return len(self._id_pos)

    @property
    def decay_estimate(self) -> float:
        """Mutated rows since the last (re)build, as a fraction of the
        corpus size at that build — the proxy for how much pruning power
        the widened intervals have lost (DESIGN.md §3.9)."""
        return self._mutations_since_opt / self._rows_at_opt

    def __contains__(self, row_id: int) -> bool:
        return int(row_id) in self._id_pos

    # -------------------------------------------------------------- insert
    def insert(self, rows) -> list[int]:
        """Insert ``rows`` ([n, d] or [d]); returns their external ids.

        Rows are normalized here (cosine search stores unit vectors).
        Free padded slots are filled first; if they run out, all-padding
        blocks are appended (a shape change — the next search retraces
        once).  Affected block intervals, joint-bound table rows and —
        when the tree backend has already built one — the tree's
        root-to-leaf node caches are conservatively widened in one fused
        scatter per table.
        """
        rows64 = np.asarray(rows, np.float64)
        if rows64.ndim == 1:
            rows64 = rows64[None, :]
        n_new = rows64.shape[0]
        if n_new == 0:
            return []
        eng = self.engine
        index = eng.index
        if rows64.shape[1] != index.db.shape[1]:
            raise ValueError(
                f"inserted rows have dim {rows64.shape[1]}, "
                f"index has dim {index.db.shape[1]}")
        norms = np.linalg.norm(rows64, axis=1, keepdims=True)
        rows64 = rows64 / np.where(norms == 0.0, 1.0, norms)

        bs = index.block_size
        shape_changed = False
        if len(self._free) < n_new:
            n_add = -(-(n_new - len(self._free)) // bs)
            old_slots = index.db.shape[0]
            index = _append_blocks(index, n_add)
            self._free = sorted(
                self._free + list(range(old_slots, old_slots + n_add * bs)),
                reverse=True)
            shape_changed = True
        pos = np.array([self._free.pop() for _ in range(n_new)], np.int64)
        ids = list(range(self._next_id, self._next_id + n_new))

        posj = jnp.asarray(pos, jnp.int32)
        blkj = jnp.asarray(pos // bs, jnp.int32)
        rows_n = jnp.asarray(rows64, jnp.float32)
        # same fp32 product the flat search paths compare against, so the
        # widened intervals bound exactly what the kernels compute
        dp_new = jnp.dot(rows_n, index.pivots.T,
                         precision=jax.lax.Precision.HIGHEST)  # [n_new, P]
        new_index = index._replace(
            db=index.db.at[posj].set(rows_n),
            dp=index.dp.at[posj].set(dp_new),
            valid=index.valid.at[posj].set(True),
            row_ids=index.row_ids.at[posj].set(
                jnp.asarray(ids, jnp.int32)),
            dp_min=index.dp_min.at[blkj].min(dp_new),
            dp_max=index.dp_max.at[blkj].max(dp_new),
        )
        if index.ortho is not None:
            # stored basis is fp32; the upcast error vs the build-time fp64
            # basis is ~1e-7 per coordinate, absorbed by JOINT_SLACK
            u64 = np.asarray(index.ortho, np.float64)
            beta64 = rows64 @ u64.T
            bnsq64 = np.cumsum(beta64 * beta64, axis=1)
            new_index = new_index._replace(
                beta=index.beta.at[posj].set(
                    jnp.asarray(beta64, jnp.float32)),
                beta_nsq=index.beta_nsq.at[posj].set(
                    jnp.asarray(bnsq64, jnp.float32)),
            )

        tree = tvn = None
        if not shape_changed and eng._tree_index is not None:
            from repro.search.tree import widen_tree
            tree = widen_tree(eng._tree_index, new_index, blkj, dp_new)
            tvn = tree.n_valid_nodes

        for i, p in zip(ids, pos):
            self._id_pos[i] = int(p)
        self._next_id += n_new
        self.generation += 1
        self._mutations_since_opt += n_new
        eng._apply_mutation(new_index, n_valid=len(self._id_pos),
                            shape_changed=shape_changed, tree=tree,
                            tree_valid_nodes=tvn)
        self._maybe_reoptimize()
        return ids

    # -------------------------------------------------------------- delete
    def delete(self, ids) -> None:
        """Tombstone-delete rows by external id.

        ``valid`` flips off and ``row_ids`` goes -1; the block/tree
        intervals stay conservatively wide (a bound that is too loose is
        still a bound), and every backend masks by per-row validity
        before top-k, so deleted rows are unreachable immediately.
        Raises ``KeyError`` (before any state changes) if any id is not
        live.
        """
        if isinstance(ids, (int, np.integer)):
            ids = [ids]
        ids = [int(i) for i in ids]
        if not ids:
            return
        bad = [i for i in ids if i not in self._id_pos]
        if bad:
            raise KeyError(
                f"row ids {bad} are not in the live set (never inserted, "
                f"or already deleted)")
        if len(set(ids)) != len(ids):
            raise KeyError(f"duplicate row ids in delete: {ids}")
        pos = [self._id_pos.pop(i) for i in ids]
        posj = jnp.asarray(pos, jnp.int32)
        index = self.engine.index
        new_index = index._replace(
            valid=index.valid.at[posj].set(False),
            row_ids=index.row_ids.at[posj].set(-1),
        )
        self._free = sorted(self._free + pos, reverse=True)
        self.generation += 1
        self._mutations_since_opt += len(pos)
        self.engine._apply_mutation(new_index,
                                    n_valid=len(self._id_pos),
                                    shape_changed=False)
        self._maybe_reoptimize()

    # ---------------------------------------------------------- reoptimize
    def reoptimize(self) -> None:
        """Full rebuild: repack live rows, reselect pivots, tighten every
        interval.  External ids are preserved (remapped through the new
        build's permutation).  A shape change: caches drop, next search
        retraces once."""
        eng = self.engine
        index = eng.index
        row_ids = np.asarray(index.row_ids)
        live = np.flatnonzero(row_ids >= 0)
        self._rows_at_opt = max(1, live.size)
        self._mutations_since_opt = 0
        self.generation += 1
        if live.size == 0:
            # no live rows: still go through _apply_mutation with a clean
            # all-padding index (empty-interval sentinels, free pivots kept)
            # so the stale widened tree / dispatch caches drop and
            # index_epoch bumps exactly like every other reoptimize — an
            # early return here left the engine serving dead caches
            new = index._replace(
                db=jnp.zeros_like(index.db),
                dp=jnp.zeros_like(index.dp),
                valid=jnp.zeros_like(index.valid),
                row_ids=jnp.full_like(index.row_ids, -1),
                dp_min=jnp.full_like(index.dp_min, jnp.inf),
                dp_max=jnp.full_like(index.dp_max, -jnp.inf),
            )
            if index.beta is not None:
                new = new._replace(beta=jnp.zeros_like(index.beta),
                                   beta_nsq=jnp.zeros_like(index.beta_nsq))
            self._id_pos = {}
            self._free = list(range(index.db.shape[0] - 1, -1, -1))
            eng._apply_mutation(new, n_valid=0, shape_changed=True)
            return
        ext_ids = row_ids[live].astype(np.int32)
        rows = np.asarray(index.db)[live]
        new = build_index(rows, n_pivots=int(index.pivots.shape[0]),
                          block_size=index.block_size)
        # the fresh build numbers rows 0..n_live-1; map back to external ids
        nr = np.asarray(new.row_ids)
        mapped = np.where(nr >= 0,
                          ext_ids[np.clip(nr, 0, live.size - 1)],
                          -1).astype(np.int32)
        new = new._replace(row_ids=jnp.asarray(mapped))
        self._id_pos = {int(r): int(p) for p, r in enumerate(mapped)
                        if r >= 0}
        self._free = sorted(
            np.flatnonzero(mapped < 0).tolist(), reverse=True)
        eng._apply_mutation(new, n_valid=live.size, shape_changed=True)

    def _maybe_reoptimize(self) -> None:
        if (self.auto_reoptimize
                and self.decay_estimate >= self.reoptimize_threshold):
            self.reoptimize()


class ShardedMutableIndex(MutableIndex):
    """Insert/delete/reoptimize handle over a *sharded* ``SearchEngine``.

    Same public surface and widening semantics as :class:`MutableIndex`,
    plus the cross-host row-placement protocol (DESIGN.md §3.10):

    * every process mirrors the same host state — the id → (shard, slot)
      map and per-shard descending free lists, derived once from the
      replicated ``row_ids`` (:func:`~repro.core.distributed.
      replicated_row_ids`) — and the external-id counter is monotone over
      it, so id allocation is replicated by construction;
    * a new row's owning shard is a *pure function* of that state:
      round-robin by id (``id % S``), falling back to the shard with the
      most free slots (ties → lowest shard id) when the preferred tail is
      full, and appending one all-padding block to EVERY shard (stacked
      shapes stay uniform) when all tails are full.  Rows place one at a
      time so the free lists evolve deterministically — every process
      computes the identical placement with zero extra collectives;
    * the device apply is shard-local: uniform-width update operands are
      replicated and each shard's slice lands via vmapped masked scatters
      (:func:`~repro.core.distributed.make_sharded_mutation`), including
      per-shard interval widening, joint-table rows, and — when the
      sharded tree is live — per-shard ``widen_tree``.

    :meth:`reoptimize` repacks **within** shards (drop tombstones, restore
    angular block coherence, re-tighten every interval from live rows)
    under each shard's existing pivots; no row moves across shards and no
    pivot is reselected, which is what keeps the rebuild collective-free
    apart from the one ``row_ids`` re-replication.

    Multi-process contract: mutation calls must be made identically on
    every process (same rows, same order) — the same SPMD discipline
    every other call in a multi-host program already follows.
    """

    def __init__(self, engine, *, reoptimize_threshold: float = 0.5,
                 auto_reoptimize: bool = True):
        index = engine.index
        if index.db.ndim != 3 or engine.mesh is None:
            raise TypeError(
                "ShardedMutableIndex needs a shard-stacked index and a "
                "mesh; flat engines are mutated through MutableIndex — "
                "engine.online() picks the right handle automatically")
        from repro.core.distributed import (make_sharded_mutation,
                                            replicated_row_ids)
        self.engine = engine
        self.reoptimize_threshold = float(reoptimize_threshold)
        self.auto_reoptimize = bool(auto_reoptimize)
        self.generation = 0
        self._mutations_since_opt = 0
        self._ops = make_sharded_mutation(engine.mesh, engine.axis_names)
        self._sync_mirrors(replicated_row_ids(index, engine.mesh))
        self._next_id = max(self._id_pos, default=-1) + 1
        self._rows_at_opt = max(1, len(self._id_pos))

    def _sync_mirrors(self, row_ids: np.ndarray) -> None:
        """Rebuild the replicated host mirrors from a ``[S, n_pad]``
        ``row_ids`` copy: ``_id_pos`` maps external id → (shard, slot),
        ``_free[s]`` is shard ``s``'s free slots, descending so ``pop()``
        hands out the lowest slot first (packed toward block fronts, like
        the flat handle)."""
        self._id_pos = {}
        self._free = []
        for s in range(row_ids.shape[0]):
            rid = row_ids[s]
            for slot in np.flatnonzero(rid >= 0):
                self._id_pos[int(rid[slot])] = (s, int(slot))
            self._free.append(
                sorted(np.flatnonzero(rid < 0).tolist(), reverse=True))

    # -------------------------------------------------------------- insert
    def insert(self, rows) -> list[int]:
        """Insert ``rows`` ([n, d] or [d]); returns their external ids.

        Placement (shard + slot per row) is decided host-side from the
        replicated mirrors *before* any device work; the apply is one
        vmapped masked scatter per table.  Appending blocks (all tails
        full) is a shape change — every shard grows together and the next
        search retraces once; otherwise the mutation is shape-stable and
        the cached sharded executables keep serving at zero retraces.
        """
        rows64 = np.asarray(rows, np.float64)
        if rows64.ndim == 1:
            rows64 = rows64[None, :]
        n_new = rows64.shape[0]
        if n_new == 0:
            return []
        eng = self.engine
        index = eng.index
        n_shards, n_pad, d = index.db.shape
        if rows64.shape[1] != d:
            raise ValueError(
                f"inserted rows have dim {rows64.shape[1]}, "
                f"index has dim {d}")
        norms = np.linalg.norm(rows64, axis=1, keepdims=True)
        rows64 = rows64 / np.where(norms == 0.0, 1.0, norms)
        bs = n_pad // index.dp_min.shape[1]
        ids = list(range(self._next_id, self._next_id + n_new))

        # ---- placement: a pure function of the replicated host mirrors
        n_add = 0
        placements = []
        for rid in ids:
            s = rid % n_shards
            if not self._free[s]:
                # least-loaded fallback: most free slots, ties lowest shard
                s2 = max(range(n_shards),
                         key=lambda j: (len(self._free[j]), -j))
                if self._free[s2]:
                    s = s2
                else:
                    # all tails full: append one block to EVERY shard
                    base = n_pad + n_add * bs
                    for fl in self._free:
                        fl.extend(range(base + bs - 1, base - 1, -1))
                    n_add += 1
                    s = rid % n_shards
            placements.append((s, self._free[s].pop()))
        shape_changed = n_add > 0
        if shape_changed:
            index = self._ops.grow(index, n_add=n_add)

        # ---- uniform-width per-shard update operands (replicated)
        per_shard = [[] for _ in range(n_shards)]
        for (s, slot), rid, row in zip(placements, ids, rows64):
            per_shard[s].append((slot, rid, row))
        width = max(len(v) for v in per_shard)
        slots = np.zeros((n_shards, width), np.int32)
        mask = np.zeros((n_shards, width), bool)
        ids_arr = np.full((n_shards, width), -1, np.int32)
        rows_arr = np.zeros((n_shards, width, d), np.float32)
        for s, entries in enumerate(per_shard):
            for j, (slot, rid, row) in enumerate(entries):
                slots[s, j] = slot
                mask[s, j] = True
                ids_arr[s, j] = rid
                rows_arr[s, j] = row
        rep = self._ops.replicate
        mask_r = rep(mask)
        new_index, dp_new = self._ops.insert(
            index, rep(slots), mask_r, rep(rows_arr), rep(ids_arr))

        shard_tree = None
        if not shape_changed and eng._shard_tree is not None:
            shard_tree = self._ops.widen(
                eng._shard_tree, rep((slots // bs).astype(np.int32)),
                dp_new, mask_r)

        for rid, loc in zip(ids, placements):
            self._id_pos[rid] = loc
        self._next_id += n_new
        self.generation += 1
        self._mutations_since_opt += n_new
        eng._apply_mutation(new_index, n_valid=len(self._id_pos),
                            shape_changed=shape_changed,
                            shard_tree=shard_tree)
        self._maybe_reoptimize()
        return ids

    # -------------------------------------------------------------- delete
    def delete(self, ids) -> None:
        """Tombstone-delete rows by external id (semantics of
        :meth:`MutableIndex.delete`, applied to each row's owning shard).
        """
        if isinstance(ids, (int, np.integer)):
            ids = [ids]
        ids = [int(i) for i in ids]
        if not ids:
            return
        bad = [i for i in ids if i not in self._id_pos]
        if bad:
            raise KeyError(
                f"row ids {bad} are not in the live set (never inserted, "
                f"or already deleted)")
        if len(set(ids)) != len(ids):
            raise KeyError(f"duplicate row ids in delete: {ids}")
        eng = self.engine
        n_shards = eng.index.db.shape[0]
        locs = [self._id_pos.pop(i) for i in ids]
        per_shard = [[] for _ in range(n_shards)]
        for s, slot in locs:
            per_shard[s].append(slot)
            self._free[s].append(slot)
        for s in {s for s, _ in locs}:
            self._free[s].sort(reverse=True)
        width = max(len(v) for v in per_shard)
        slots = np.zeros((n_shards, width), np.int32)
        mask = np.zeros((n_shards, width), bool)
        for s, sl in enumerate(per_shard):
            slots[s, :len(sl)] = sl
            mask[s, :len(sl)] = True
        rep = self._ops.replicate
        new_index = self._ops.delete(eng.index, rep(slots), rep(mask))
        self.generation += 1
        self._mutations_since_opt += len(ids)
        eng._apply_mutation(new_index, n_valid=len(self._id_pos),
                            shape_changed=False)
        self._maybe_reoptimize()

    # ---------------------------------------------------------- reoptimize
    def reoptimize(self) -> None:
        """Per-shard repack: drop tombstones, restore angular block
        coherence (build_index's reorder key under each shard's existing
        pivots), recompute every interval from live rows only, and shrink
        the common padded size to fit the fullest shard.  External ids are
        preserved (rows carry them through the permutation); no row moves
        across shards and no pivot is reselected.  A shape change: caches
        drop, next search retraces once.  Works uniformly down to the
        empty live set (one all-padding block per shard)."""
        eng = self.engine
        index = eng.index
        from repro.core.distributed import replicated_row_ids
        self._rows_at_opt = max(1, len(self._id_pos))
        self._mutations_since_opt = 0
        self.generation += 1
        n_shards, n_pad, _ = index.db.shape
        bs = n_pad // index.dp_min.shape[1]
        per_live = np.zeros(n_shards, np.int64)
        for s, _ in self._id_pos.values():
            per_live[s] += 1
        max_live = int(per_live.max()) if self._id_pos else 0
        n_pad_new = max(bs, -(-max_live // bs) * bs)
        new_index = self._ops.repack(index, n_pad_new=n_pad_new)
        self._sync_mirrors(replicated_row_ids(new_index, eng.mesh))
        eng._apply_mutation(new_index, n_valid=len(self._id_pos),
                            shape_changed=True)
