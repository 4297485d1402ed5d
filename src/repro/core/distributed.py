"""Mesh-sharded exact cosine search: the pod-scale datastore.

This module is the engine room of the SearchEngine's ``"sharded"`` backend
(:mod:`repro.search.backends`): the datastore rows shard across every device
of the mesh (the product of all named axes handed in).  Each device holds
its own :class:`BlockIndex` shard — pivots are *local* to a shard, which
keeps build embarrassingly parallel and, because a shard covers a narrower
slice of the sphere, makes the local Eq. 13 bounds slightly tighter than
global pivots would be.

Search is the shard-local inner loop a one-chip engine would run over the
shard — the fused Pallas kernel search (:func:`~repro.search.backends.
kernel_search`), so the engine's τ warm-start and best-first ordering apply
per shard — followed by a tiny global merge:
``all_gather`` of the per-shard (k sims, k global ids) — ``O(devices * k)``
bytes, negligible next to the avoided score matmuls — then ``lax.top_k``.
Exactness is preserved: every shard returns its true local top-k and the
union of local top-k sets contains the global top-k.

With per-shard pivot trees (``SearchEngine(tree_shards=...)``) a local leaf
scan is preceded by the transitive Eq. 13 descent over each shard's own
tree, pruning against a **global** τ assembled from every shard's
warm-start candidates by a second tiny collective (mask-carrying top-k
merge, ``O(devices * k)``) — DESIGN.md §3.6.  The merge argument weakens
from "every shard returns its local top-k" to "every dropped candidate is
provably below the global k-th best", which is still exact.

At 1000+ nodes this is the standard sharded-retrieval pattern (one shard per
chip, single small collective per query batch); the same code runs on any
mesh because only the flattened axis names are referenced.

**Multi-host** (DESIGN.md §3.7): :func:`build_sharded_index_local` is the
process-local variant of the build — each shard's pivots, blocks and
interval caches are built on the device that holds the shard, from only
the rows it owns, and the global stacked index is assembled from those
per-device pieces with ``jax.make_array_from_single_device_arrays``, so
no host and no device ever materializes the full datastore.  Search needs no multi-host
changes at all: the per-shard work and the τ / top-k merges already run
as collectives inside ``shard_map``, which is topology-blind — the same
jitted program serves one process with eight virtual devices and eight
hosts with one chip each.  Exactness is likewise unchanged, because
pivots were *always* shard-local (see §3.7: local pivots only loosen a
shard's bounds relative to global pivots, and a loose bound can only
under-prune, never cut a true neighbor).

**Online mutation** (DESIGN.md §3.10): sharded engines are mutable through
:class:`repro.core.online.ShardedMutableIndex`, obtained transparently via
``SearchEngine.online()``.  The cross-host question — which shard owns a
new row? — is answered by a *deterministic placement protocol*: external
ids come from a replicated monotone counter and map to an owning shard
round-robin by id, falling back to the least-loaded free list when the
preferred shard's tail is full (appending one all-padding block to every
shard when all tails are full, keeping the stacked shapes uniform).
Placement is a pure function of replicated host state (the id → (shard,
slot) map every process mirrors from the replicated ``row_ids``), so all
processes decide identically with **zero extra collectives**; each process
then applies only its own shards' slices through the vmapped masked
scatters behind :func:`make_sharded_mutation`.  Widening (§3.9) holds
shard-locally, and the merges never assumed anything about row placement,
so search stays exact — see §3.10 for the full argument.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import Array
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.index import BlockIndex, build_index

_HIGHEST = jax.lax.Precision.HIGHEST

__all__ = ["auto_mesh", "on_mesh", "build_sharded_index", "build_sharded_index_local",
           "local_shard_rows", "make_sharded_search", "sharded_search_local",
           "place_sharded_index", "make_sharded_mutation",
           "replicated_row_ids"]


def _build_shard_part(shard, n_valid: int, row_offset: int, *,
                      n_pivots: int, block_size: int,
                      pivot_method: str) -> BlockIndex:
    """One shard's :class:`BlockIndex` with GLOBAL row ids baked in.

    The one per-shard build both :func:`build_sharded_index` and
    :func:`build_sharded_index_local` call — keeping it shared is what
    makes the process-local build bit-identical to the single-controller
    one (same rows in ⇒ same pivots, reorder, intervals out).
    """
    idx = build_index(
        jnp.asarray(shard), n_pivots=n_pivots, block_size=block_size,
        pivot_method=pivot_method if n_valid > n_pivots else "random",
    )
    # mark padding rows (zero vectors) invalid even when build_index's own
    # padding did not cover them (row_ids tracks the pre-reorder position),
    # and bake GLOBAL row ids in, so the merge needs no rank arithmetic
    # (robust to any device->shard mapping).
    valid = idx.valid & (idx.row_ids >= 0) & (idx.row_ids < n_valid)
    gids = jnp.where(valid, idx.row_ids + row_offset, -1).astype(jnp.int32)
    return idx._replace(valid=valid, row_ids=gids)


def build_sharded_index(
    db: np.ndarray,
    n_shards: int,
    *,
    n_pivots: int = 16,
    block_size: int = 128,
    pivot_method: str = "maxmin",
) -> BlockIndex:
    """Split ``db`` row-wise into ``n_shards`` and build one index per shard.

    Returns a :class:`BlockIndex` whose arrays carry a leading shard axis
    ``[S, ...]``, stacked on the HOST (numpy leaves): place it with
    :func:`place_sharded_index` so that each device materializes only its
    own shard — no device ever holds the stack.  Rows pad to equal shard
    sizes.  :func:`build_sharded_index_local` (what
    ``SearchEngine.build(db, mesh=...)`` calls) skips the host round trip
    and builds every shard on its own device.
    """
    db = np.asarray(db, np.float32)
    n = db.shape[0]
    per = -(-n // n_shards)
    pad = per * n_shards - n
    if pad:
        db = np.concatenate([db, np.zeros((pad, db.shape[1]), np.float32)], 0)
    parts = []
    for s in range(n_shards):
        part = _build_shard_part(
            db[s * per : (s + 1) * per],
            n_valid=min(per, max(0, n - s * per)), row_offset=s * per,
            n_pivots=n_pivots, block_size=block_size,
            pivot_method=pivot_method)
        parts.append(jax.tree.map(np.asarray, part))
    return jax.tree.map(lambda *xs: np.stack(xs), *parts)


def auto_mesh(mesh: Mesh) -> Mesh:
    """``mesh`` with every axis in ``AxisType.Auto``.

    ``jax.make_mesh`` makes Explicit axes by default, and arrays placed on
    such a mesh carry their sharding in their type: the per-shard ``vmap``
    and scatter code of this module (written for Auto meshes, where GSPMD
    propagates shardings) then fails to trace.  Every sharded entry point
    normalizes the caller's mesh here — same devices, same axis names —
    so any mesh a caller passes works.
    """
    from jax.sharding import AxisType
    if all(t == AxisType.Auto for t in mesh.axis_types):
        return mesh
    return Mesh(mesh.devices, mesh.axis_names,
                axis_types=(AxisType.Auto,) * len(mesh.axis_names))


def _flat_axes(mesh: Mesh, axis_names) -> tuple[str, ...]:
    axis = tuple(axis_names or mesh.axis_names)
    if jax.process_count() > 1 and set(axis) != set(mesh.axis_names):
        raise NotImplementedError(
            "multi-host sharded build supports sharding over ALL mesh axes "
            f"only (got axis_names={axis!r} on a mesh with axes "
            f"{mesh.axis_names!r}); replicated shard axes would need "
            "identical cross-host replicas")
    return axis


def local_shard_rows(n_rows: int, mesh: Mesh, axis_names=None):
    """Which global datastore rows THIS process's shards cover.

    The sharded datastore places one shard per device of the flattened
    mesh axes; ownership is read off the placement sharding's own index
    map (``NamedSharding(mesh, P(axis)).devices_indices_map``), so the
    shard-id ↔ device assignment is by construction the one
    ``place_sharded_index`` / ``build_sharded_index_local`` use
    — including permuted ``axis_names`` orders, which flatten differently
    from ``mesh.devices``.  Returns ``(per, owned)`` where ``per`` is the
    global rows-per-shard (``ceil(n_rows / n_shards)``) and ``owned`` is
    this process's shards as ``[(shard_id, row_start, row_stop), ...]``
    in ascending shard order — the order a process-local datastore slab
    must be concatenated in for :func:`build_sharded_index_local`.
    ``row_stop`` is clamped to ``n_rows`` (the trailing shard may be
    short; its tail pads with invalid rows at build time).
    """
    axis = _flat_axes(mesh, axis_names)
    n_shards = int(np.prod([mesh.shape[a] for a in axis]))
    imap = NamedSharding(mesh, P(axis)).devices_indices_map((n_shards,))
    pid = jax.process_index()
    owned_ids = sorted({(idx[0].start or 0) for d, idx in imap.items()
                        if d.process_index == pid})
    per = -(-n_rows // n_shards)
    owned = [(s, min(s * per, n_rows), min((s + 1) * per, n_rows))
             for s in owned_ids]
    return per, owned


def build_sharded_index_local(
    db_local: np.ndarray | Array,
    mesh: Mesh,
    *,
    global_rows: int,
    axis_names=None,
    n_pivots: int = 16,
    block_size: int = 128,
    pivot_method: str = "maxmin",
) -> BlockIndex:
    """Process-local sharded build: assemble the global index from each
    host's own rows (DESIGN.md §3.7).

    ``db_local`` holds ONLY the rows this process's shards cover — the
    concatenation, in ascending shard order, of the ``local_shard_rows``
    ranges (for the usual contiguous ownership that is one slice of the
    logical datastore).  A device array already split by rows over the
    mesh (the global array, when single-process) is used shard by shard
    where it lies.  Every per-shard index (pivots, reorder, interval
    caches) is built on the device that will hold it, from those rows
    alone, and the stacked global :class:`BlockIndex` is assembled
    leaf-by-leaf from the per-device pieces — each device materializes
    exactly its own shard and no host ever holds the full datastore.

    ``global_rows`` is the TOTAL logical row count across all hosts
    (metadata every launcher knows; it fixes the rows-per-shard split and
    the global row-id offsets).  ``db_local`` may also hold every one of
    its shards whole, ``ceil(global_rows / n_shards)`` rows each — a
    device array split evenly by rows over the mesh, whose length is
    ``global_rows`` rounded up to a multiple of the shard count: rows at
    and past ``global_rows`` are then padding, marked invalid like the
    tail of a short last shard, and each shard's rows are used where they
    lie.  The result is placed like
    :func:`place_sharded_index` would place it — ``P(axis_names)`` over
    the flattened mesh axes — and is bit-identical, shard for shard, to
    ``build_sharded_index(full_db, n_shards)`` on the same rows: both
    call the same per-shard builder.  Search then works unchanged (the
    merges are collectives inside ``shard_map``); exactness never
    depended on cross-shard pivot knowledge in the first place.
    """
    if not isinstance(db_local, jax.Array):
        db_local = np.asarray(db_local, np.float32)
    axis = _flat_axes(mesh, axis_names)
    per, owned = local_shard_rows(global_rows, mesh, axis_names)
    n_shards = int(np.prod([mesh.shape[a] for a in axis]))
    expected = sum(stop - start for _, start, stop in owned)
    # whole shards: the corpus rounded up to the shard count, padding last
    whole = db_local.shape[0] == per * len(owned)
    if db_local.shape[0] != expected and not whole:
        raise ValueError(
            f"db_local has {db_local.shape[0]} rows but this process's "
            f"shards {[s for s, _, _ in owned]} cover {expected} of the "
            f"{global_rows} global rows ({per} per shard across {n_shards} "
            f"shards, {per * len(owned)} with the padding); slice the "
            f"datastore with local_shard_rows()")
    mesh = auto_mesh(mesh)
    sh = NamedSharding(mesh, P(axis))
    device_of = {(idx[0].start or 0): dev for dev, idx
                 in sh.devices_indices_map((n_shards,)).items()
                 if dev.process_index == jax.process_index()}
    shards, ofs = [], 0
    for s, start, stop in owned:
        cnt = stop - start
        take = per if whole else cnt
        dev = device_of[s]
        # each shard is built on the device that will hold it: the rows
        # move there first, and every step of the build runs where its
        # input lives — no device builds or stacks another's shard
        rows = _rows_on_device(db_local, ofs, ofs + take, dev)
        ofs += take
        if take < per:  # trailing short shard: pad with invalid zero rows
            rows = jnp.pad(rows, ((0, per - take), (0, 0)))
        with jax.default_device(dev):
            part = _build_shard_part(
                rows, n_valid=cnt, row_offset=s * per, n_pivots=n_pivots,
                block_size=block_size, pivot_method=pivot_method)
        del rows
        shards.append(_with_shard_axis(jax.device_put(part, dev)))
    return jax.tree.map(
        lambda *xs: jax.make_array_from_single_device_arrays(
            (n_shards,) + xs[0].shape[1:], sh, list(xs)), *shards)


#: [...] -> [1, ...] per leaf; donated, so the reshape reuses the shard's
#: own buffers instead of copying them
_with_shard_axis = jax.jit(lambda t: jax.tree.map(lambda x: x[None], t),
                           donate_argnums=0)


def _rows_on_device(db_local, start: int, stop: int, dev):
    """Rows ``[start, stop)`` of ``db_local`` on ``dev``.

    A device array already split by rows hands over the matching shard
    without a copy; anything else is sliced and copied to ``dev``.
    """
    if isinstance(db_local, jax.Array):
        for piece in db_local.addressable_shards:
            rows = piece.index[0]
            if (piece.device == dev and (rows.start or 0) == start
                    and piece.data.shape[0] == stop - start):
                return piece.data
        return jax.device_put(db_local[start:stop], dev)
    return jax.device_put(np.asarray(db_local[start:stop], np.float32), dev)


def sharded_search_local(index: BlockIndex, queries: Array, k: int, axis_names,
                         *, inner: str = "scan", prune: bool = True,
                         warm_start: bool = False, best_first: bool = False,
                         warm_start_blocks: int | None = None,
                         element_stats: bool = False,
                         with_stats: bool = False,
                         tree=None, margin: float = 4e-7,
                         n_pivots: int = 0, bm: int = 128,
                         bn: int | None = None, sort_queries: bool = True,
                         interpret: bool | None = None):
    """Body that runs inside ``shard_map``: local search + global merge.

    ``index`` arrives with the leading shard axis of size 1 (this device's
    shard); ``queries`` are replicated.  ``inner`` names the search each
    shard runs over its flat local index, the same inner loop a one-chip
    engine runs: ``"kernel"`` (:func:`~repro.search.backends.kernel_search`,
    the fused Pallas kernel; ``bm`` / ``bn`` / ``sort_queries`` /
    ``interpret`` are its tile options), ``"brute"`` or ``"scan"`` (the
    default).
    ``warm_start`` / ``best_first`` / ``warm_start_blocks`` /
    ``element_stats`` / ``n_pivots`` are the engine policies, applied to
    each shard's local search (the τ prescan seeds from each shard's own
    best-bound blocks — DESIGN.md §3.4).

    With ``tree`` (a :class:`~repro.search.tree.ShardTreeArrays`, leading
    shard axis of size 1) each shard instead runs the transitive Eq. 13
    descent over its *local* pivot tree before a leaf scan — DESIGN.md
    §3.6.  The τ the descent prunes against is **global**: every shard's
    beam warm-start candidates are merged with the mask-carrying top-k
    all-gather and the k-th best of the union is broadcast back, so each
    shard's pruning threshold is at least the flat path's local seed
    (per-shard pruning is a superset of the flat per-shard pruning) while
    remaining a true lower bound on the global k-th best (cut subtrees
    provably hold no global top-k member, so the merge stays exact).
    Everything stays statically shaped — the surviving leaves are a
    boolean mask into the local scan, not a compaction — which is what
    ``shard_map`` tracing requires.

    With ``with_stats`` the result gains the raw stats dict of the inner
    loop (as the one-chip backends report it), every fraction
    psum-weighted: sums of per-shard counts over sums of per-shard
    denominators, so unevenly filled shards weigh correctly.
    """
    from repro.dist.collectives import topk_allgather_merge
    from repro.search import backends as bk
    local = jax.tree.map(lambda x: x[0], index)

    def total(x):
        return jax.lax.psum(jnp.asarray(x, jnp.float32), axis_names)

    # each branch leaves ``stats``, a thunk of its inner loop's
    # psum-weighted raw stats (called only with ``with_stats``), and
    # ``elem``, this shard's count of element-pruned (query, row) pairs
    with jax.named_scope("shard_search"):
        qn, qp = bk.prep_queries(local, queries)
        m = qn.shape[0]
        if tree is not None:
            sims, pos, stats, elem = _tree_shard_search(
                local, tree, qn, qp, k, axis_names, total,
                warm_start=warm_start, best_first=best_first,
                warm_start_blocks=warm_start_blocks,
                element_stats=element_stats, margin=margin,
                n_pivots=n_pivots)
        elif inner == "kernel":
            sims, pos, computed, elem, rounds = bk.kernel_search(
                local, qn, qp, k, bm=bm, bn=bn, prune=prune,
                sort_queries=sort_queries, warm_start=warm_start,
                best_first=best_first, margin=margin, interpret=interpret,
                element_stats=element_stats,
                warm_start_blocks=warm_start_blocks, n_pivots=n_pivots)
            elem = elem.sum() if element_stats else 0.0

            def stats():                # (query tile, kernel tile) pairs
                done = total(computed.sum())
                frac = done / total(computed.size)
                return {"block_prune_frac": 1.0 - frac,
                        "tile_computed_frac": frac,
                        "merge_rounds": (total(rounds.sum())
                                         / jnp.maximum(done, 1.0))}
        elif inner == "brute":
            sims, pos = bk.brute_search(local, qn, k)
            elem = 0.0

            def stats():                # brute skips nothing
                return {"block_prune_frac": jnp.float32(0.0)}
        else:
            sims, pos, blk_pruned, elem = bk.scan_search(
                local, qn, qp, k, prune=prune, margin=margin,
                warm_start=warm_start, best_first=best_first,
                warm_start_blocks=warm_start_blocks,
                element_stats=element_stats, n_pivots=n_pivots)

            def stats():                # (query, index block) pairs
                return {"block_prune_frac": (
                    total(blk_pruned) / (m * total(local.n_blocks)))}
        # build_sharded_index bakes GLOBAL ids into row_ids — no rank
        # arithmetic
        gids = bk.map_row_ids(local.row_ids, pos)
    with jax.named_scope("merge"):
        # tiny collective: O(devices * k) candidates
        merged = topk_allgather_merge(sims, gids, k, axis_names)
    if not with_stats:
        return merged
    raw = stats()
    if element_stats:
        raw["elem_prune_frac"] = total(elem) / jnp.maximum(
            1.0, m * total(local.valid.sum()))
    return merged + (raw,)


def _tree_shard_search(local, tree, qn, qp, k, axis_names, total, *,
                       warm_start, best_first, warm_start_blocks,
                       element_stats, margin, n_pivots):
    """One shard's descent over its local pivot tree, seeded by the global
    τ, then the masked leaf scan (DESIGN.md §3.6)."""
    from repro.dist.collectives import global_tau_merge
    from repro.search.backends import scan_search
    from repro.search.tree import TreeIndex, _seed_and_descend
    ltree = TreeIndex(local, tree.node_lo[0], tree.node_hi[0],
                      tree.node_valid[0])
    # the one exactness-critical seed -> descend -> flat-reseed sequence,
    # shared with the single-device tree backend; the merge hook turns each
    # shard's beam candidates into ONE global τ per query (mask-carrying,
    # so shards holding < k candidates still contribute theirs) — §3.6
    tau0, leaf_alive, leaf_ub, evals = _seed_and_descend(
        ltree, qn, qp, k, warm_start=warm_start,
        warm_start_blocks=warm_start_blocks, margin=margin,
        tau_merge=lambda s, v: global_tau_merge(s, v, k, axis_names))
    if n_pivots > 0:
        # eq13_multi over the LOCAL shard tables (pivots — and so the joint
        # basis — were always shard-local); the leaf scan below consumes
        # the tightened bound matrix unchanged
        from repro.core.index import multipivot_block_cap
        leaf_ub = jnp.minimum(
            leaf_ub, multipivot_block_cap(local, qn, n_pivots=n_pivots))
    sims, pos, blk_pruned, elem_pruned = scan_search(
        local, qn, qp, k, margin=margin, warm_start=False,
        best_first=best_first, element_stats=element_stats,
        tau0=tau0, ub_all=leaf_ub, leaf_mask=leaf_alive)

    def stats():                        # (query, index block) pairs
        pairs = qn.shape[0] * total(local.n_blocks)
        return {"block_prune_frac": total(blk_pruned) / pairs,
                "tree_prune_frac": total((~leaf_alive).sum()) / pairs,
                "tree_node_eval_frac": total(evals) / jnp.maximum(
                    1.0, qn.shape[0] * total(ltree.node_valid.sum()))}

    return sims, pos, stats, elem_pruned


def make_sharded_search(mesh: Mesh, axis_names: tuple[str, ...] | None = None,
                        *, with_stats: bool = False, trace_hook=None,
                        **search_kw):
    """Build a jitted ``(index, queries, k[, tree]) -> (sims, gids[, raw])``
    closure: :func:`sharded_search_local` in ``shard_map`` over ``mesh``.

    ``search_kw`` are :func:`sharded_search_local`'s options (``inner``,
    the engine policies and the kernel's tile options).  ``trace_hook``
    (optional zero-arg callable) is invoked inside the traced body, i.e.
    exactly once per trace+compile and never on cached dispatches — the
    engine passes its retrace counter.

    ``axis_names`` defaults to *all* mesh axes — the datastore shards over
    every chip.  Results are fully replicated.  With ``with_stats`` the
    closure also returns the inner loop's raw stats dict, psum-weighted
    over shards.

    Pass ``tree`` (a shard-stacked
    :class:`~repro.search.tree.ShardTreeArrays`, placed like the index) to
    run the per-shard transitive Eq. 13 descent with the broadcast global
    τ before each shard's leaf scan (DESIGN.md §3.6); the stats then also
    hold the psum-weighted ``tree_prune_frac`` and ``tree_node_eval_frac``.
    """
    axis_names = tuple(axis_names or mesh.axis_names)
    mesh = auto_mesh(mesh)

    from repro.dist.compat import shard_map

    @functools.partial(jax.jit, static_argnames=("k",))
    def run(index: BlockIndex, queries: Array, k: int, tree=None):
        if trace_hook is not None:
            trace_hook()
        body = functools.partial(
            sharded_search_local, k=k, axis_names=axis_names,
            with_stats=with_stats, **search_kw)
        shards = P(axis_names)
        idx_specs = jax.tree.map(lambda _: shards, index)
        if tree is None:
            return shard_map(body, mesh=mesh, in_specs=(idx_specs, P()),
                             out_specs=P(), check_vma=False)(index, queries)
        fn = shard_map(
            lambda idx, q, tr: body(idx, q, tree=tr), mesh=mesh,
            in_specs=(idx_specs, P(), jax.tree.map(lambda _: shards, tree)),
            out_specs=P(), check_vma=False)
        return fn(index, queries, tree)

    return run


def place_sharded_index(index: BlockIndex, mesh: Mesh, axis_names=None) -> BlockIndex:
    """Device-put a stacked index with the shard axis over the mesh axes."""
    axis_names = tuple(axis_names or mesh.axis_names)
    sh = NamedSharding(auto_mesh(mesh), P(axis_names))
    return jax.tree.map(lambda x: jax.device_put(x, sh), index)


def on_mesh(index: BlockIndex, mesh: Mesh) -> BlockIndex:
    """``index`` with every leaf sharded over ``mesh`` itself.

    Leaves placed on another mesh over the same devices (typically the
    caller's Explicit-axis mesh, see :func:`auto_mesh`) are re-labelled
    with the same partition spec; anything else passes through unchanged.
    """
    def move(x):
        sh = getattr(x, "sharding", None)
        if isinstance(sh, NamedSharding) and sh.mesh != mesh:
            return jax.device_put(x, NamedSharding(mesh, sh.spec))
        return x

    return jax.tree.map(move, index)


def replicated_row_ids(index: BlockIndex, mesh: Mesh) -> np.ndarray:
    """Host copy of a stacked index's ``row_ids`` — ``[S, n_pad]`` int32.

    The one replication the sharded online handle performs, at handle init
    and after each :meth:`~repro.core.online.ShardedMutableIndex.reoptimize`
    (both rebuild events, never the per-mutation hot path): multi-host
    ``row_ids`` are not addressable outside jit, so an identity jit with
    replicated ``out_shardings`` all-gathers them and every process reads
    the same full copy off its first addressable shard.  From this mirror
    each process derives the id → (shard, slot) map and the per-shard free
    lists — the *replicated host state* the placement protocol is a pure
    function of (DESIGN.md §3.10).
    """
    rid = index.row_ids
    if isinstance(rid, jax.Array) and not rid.is_fully_addressable:
        rep = jax.jit(lambda x: x,
                      out_shardings=NamedSharding(auto_mesh(mesh), P()))(rid)
        return np.asarray(rep.addressable_shards[0].data)
    return np.asarray(rid)


class ShardedMutationOps:
    """Jitted device-apply closures for one sharded engine's mutations.

    Built once per online handle by :func:`make_sharded_mutation`.  Every
    closure takes the stacked index (sharded ``P(axis)`` over the mesh)
    plus small *replicated* per-shard update operands padded to a uniform
    width R, and applies each shard's slice with vmapped masked scatters —
    masked entries index the out-of-range sentinel and are dropped, so a
    shard receiving fewer (or zero) rows this call is untouched.  All
    outputs keep the index placement (``out_shardings``), so under GSPMD
    each device scatters only into its local shard and the apply itself
    needs no communication.

    ``insert`` computes the new rows' pivot projections **on device, per
    shard** (``rows @ pivots_s.T`` — multi-host processes cannot read other
    shards' pivots host-side); the fp32 joint-table rows it writes differ
    from the flat path's fp64-then-cast ones by ~1e-7, absorbed by
    ``JOINT_SLACK`` like the stored-basis upcast error already is.
    """

    def __init__(self, mesh: Mesh, axis_names=None):
        axis = _flat_axes(mesh, axis_names)
        mesh = auto_mesh(mesh)
        self.mesh = mesh
        self.axis = axis
        self.sharding = NamedSharding(mesh, P(axis))
        sh = self.sharding

        def _insert(index, slots, mask, rows, ids):
            def one(idx, sl, mk, rw, di):
                n_pad = idx.db.shape[0]
                nb = idx.dp_min.shape[0]
                bs = n_pad // nb
                dp_new = jnp.dot(rw, idx.pivots.T,
                                 precision=_HIGHEST)         # [R, P]
                sl_s = jnp.where(mk, sl, n_pad)              # drop padding
                blk = jnp.where(mk, sl // bs, nb)
                new = idx._replace(
                    db=idx.db.at[sl_s].set(rw, mode="drop"),
                    dp=idx.dp.at[sl_s].set(dp_new, mode="drop"),
                    valid=idx.valid.at[sl_s].set(True, mode="drop"),
                    row_ids=idx.row_ids.at[sl_s].set(di, mode="drop"),
                    dp_min=idx.dp_min.at[blk].min(dp_new, mode="drop"),
                    dp_max=idx.dp_max.at[blk].max(dp_new, mode="drop"),
                )
                if idx.ortho is not None:
                    beta = jnp.dot(rw, idx.ortho.T, precision=_HIGHEST)
                    bnsq = jnp.cumsum(beta * beta, axis=1)
                    new = new._replace(
                        beta=idx.beta.at[sl_s].set(beta, mode="drop"),
                        beta_nsq=idx.beta_nsq.at[sl_s].set(bnsq,
                                                           mode="drop"))
                return new, dp_new

            return jax.vmap(one)(index, slots, mask, rows, ids)

        def _delete(index, slots, mask):
            def one(idx, sl, mk):
                sl_s = jnp.where(mk, sl, idx.valid.shape[0])
                return idx._replace(
                    valid=idx.valid.at[sl_s].set(False, mode="drop"),
                    row_ids=idx.row_ids.at[sl_s].set(-1, mode="drop"))

            return jax.vmap(one)(index, slots, mask)

        def _grow(index, *, n_add):
            s = index.db.shape[0]
            d = index.db.shape[2]
            p = index.dp.shape[2]
            bs = index.db.shape[1] // index.dp_min.shape[1]
            nr = n_add * bs
            zdp = jnp.zeros((s, nr, p), index.dp.dtype)
            new = index._replace(
                db=jnp.concatenate(
                    [index.db, jnp.zeros((s, nr, d), index.db.dtype)], 1),
                dp=jnp.concatenate([index.dp, zdp], 1),
                valid=jnp.concatenate(
                    [index.valid, jnp.zeros((s, nr), index.valid.dtype)], 1),
                row_ids=jnp.concatenate(
                    [index.row_ids, jnp.full((s, nr), -1, jnp.int32)], 1),
                # empty-interval sentinel: the first insert records its
                # exact min/max (same convention as the flat append path)
                dp_min=jnp.concatenate(
                    [index.dp_min,
                     jnp.full((s, n_add, p), jnp.inf, index.dp_min.dtype)],
                    1),
                dp_max=jnp.concatenate(
                    [index.dp_max,
                     jnp.full((s, n_add, p), -jnp.inf, index.dp_max.dtype)],
                    1),
            )
            if index.beta is not None:
                new = new._replace(
                    beta=jnp.concatenate([index.beta, zdp], 1),
                    beta_nsq=jnp.concatenate([index.beta_nsq, zdp], 1))
            return new

        def _repack(index, *, n_pad_new):
            def one(idx):
                p = idx.dp.shape[1]
                bs = idx.db.shape[0] // idx.dp_min.shape[0]
                # build_index's reorder key: (nearest pivot asc, similarity
                # to it desc), tombstones and padding grouped last
                nearest = jnp.argmax(idx.dp, axis=1).astype(jnp.int32)
                near_sim = jnp.max(idx.dp, axis=1)
                group = jnp.where(idx.valid, nearest, p)
                perm = jnp.lexsort((-near_sim, group))
                db = idx.db[perm][:n_pad_new]
                dp = idx.dp[perm][:n_pad_new]
                valid = idx.valid[perm][:n_pad_new]
                rid = jnp.where(valid, idx.row_ids[perm][:n_pad_new], -1)
                nb2 = n_pad_new // bs
                dmin = jnp.where(valid[:, None], dp,
                                 jnp.inf).reshape(nb2, bs, p).min(axis=1)
                dmax = jnp.where(valid[:, None], dp,
                                 -jnp.inf).reshape(nb2, bs, p).max(axis=1)
                new = idx._replace(db=db, dp=dp, valid=valid, row_ids=rid,
                                   dp_min=dmin, dp_max=dmax)
                if idx.beta is not None:
                    new = new._replace(
                        beta=idx.beta[perm][:n_pad_new],
                        beta_nsq=idx.beta_nsq[perm][:n_pad_new])
                return new

            return jax.vmap(one)(index)

        def _widen(tree, blocks, dp_rows, mask):
            from repro.search.tree import widen_shard_trees
            return widen_shard_trees(tree, blocks, dp_rows, mask)

        self.insert = jax.jit(_insert, out_shardings=sh)
        self.delete = jax.jit(_delete, out_shardings=sh)
        self.grow = jax.jit(_grow, static_argnames="n_add", out_shardings=sh)
        self.repack = jax.jit(_repack, static_argnames="n_pad_new",
                              out_shardings=sh)
        self.widen = jax.jit(_widen, out_shardings=sh)

    def replicate(self, x) -> Array:
        """Small host update operand -> replicated global device array."""
        from repro.dist.compat import replicate_to_mesh
        return replicate_to_mesh(np.asarray(x), self.mesh)


def make_sharded_mutation(mesh: Mesh, axis_names=None) -> ShardedMutationOps:
    """Build the jitted sharded-mutation closures for ``mesh``.

    Called once per :class:`~repro.core.online.ShardedMutableIndex`; the
    returned object's jit caches persist for the handle's lifetime, so
    shape-stable mutations dispatch without retracing (the index is an
    argument, exactly like the search closures).  Per-shard *repack*
    (``reoptimize``) deliberately moves no row across shards and keeps each
    shard's existing pivots: tightening intervals, dropping tombstones and
    re-coherent block packing are all shard-local, which is what keeps the
    rebuild collective-free (DESIGN.md §3.10).
    """
    return ShardedMutationOps(mesh, axis_names)
