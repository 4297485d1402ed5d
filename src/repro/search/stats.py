"""The one stats object every search path returns.

Replaces the three ad-hoc shapes the backends used to hand back (the scan
path's ``{"block_prune_frac": ...}`` dict, the kernel path's bare
``computed.mean()`` scalar, and the sharded path's discarded stats) with a
single dataclass.  Dict-style access (``stats["block_prune_frac"]``,
``stats.items()``) is kept so existing benchmark/report code keeps working.
"""
from __future__ import annotations

from dataclasses import dataclass, field, fields

__all__ = ["SearchStats"]


@dataclass(frozen=True)
class SearchStats:
    """Per-call search statistics.

    Numeric fields are *lazy* jnp scalars (or tracers when the search ran
    inside an outer jit, e.g. the serving decode step): reading one forces
    the device sync, ignoring them costs nothing on the hot path.  Call
    ``float(...)`` / :meth:`as_dict` to materialize for logging.

    ``block_prune_frac`` is the engine-wide comparable number: the fraction
    of (query-or-query-tile, block) work units whose Eq. 13 upper bound
    proved them unnecessary.  For the scan backend the unit is a (query,
    index block) pair; for the kernel backend it is a (query tile, kernel
    tile) pair (``1 - tile_computed_frac``); for the sharded backend it is
    the mean over shards of the local scan fraction; brute force is 0 by
    definition.  The τ warm-start pre-scan (``ceil(k / block)`` blocks per
    query, DESIGN.md §3.4) is not counted as pruned or computed work.

    ``elem_prune_frac`` (requires ``element_stats``) is backend-uniform:
    the fraction of (query, valid row) pairs whose *individual* Eq. 13
    bound fell below the query's running τ at the moment the row's block
    was visited — the pruning a scalar per-point index (LAESA) would have
    achieved with the same pivots and visit order.  All backends report it
    over the same denominator ``n_queries * n_valid_rows`` (sharded: psum
    of counts over psum of valid rows); brute force is 0 by definition.

    ``tree_prune_frac`` (``tree`` backend, and ``sharded`` with per-shard
    trees) is the fraction of (query, block) pairs excluded by the
    *transitive* Eq. 13 descent alone — whole subtrees cut at an internal
    node before any leaf bound was evaluated (DESIGN.md §3.5; sharded:
    psum-weighted over shards, §3.6).  It is a component of
    ``block_prune_frac`` (descent-pruned blocks are also counted there),
    reported separately so the hierarchy's contribution is visible next
    to the flat leaf-stage pruning.

    ``tree_node_eval_frac`` (same backends) is the fraction of (query,
    valid tree node) pairs whose bound the descent actually had to
    evaluate — the flat scan is 1.0 at the leaf level by construction,
    so lower means the hierarchy is paying for itself.

    ``merge_rounds`` (``kernel`` backend only) is the mean number of
    rounds the Pallas kernel's top-k merge ran per computed (query tile,
    db tile) pair: each round moves every row's best remaining tile score
    into its k slots, and rounds stop once no row's best remaining score
    beats its k-th best, so it counts the merge's serial steps.  Lazy like
    the fractions; ``None`` on every other backend.

    ``retraces`` is the number of jit traces (trace + XLA compile) this
    ``search`` call triggered through the engine's compiled-function
    cache: 0 means the fully-fused hot path was dispatch-cached (the
    steady state), 1 means this call paid one compilation (first call,
    or a new ``(backend, k, query shape, dtype, knobs)`` key).  It is a
    host ``int``, not a lazy scalar — the counter is a Python side effect
    that fires at trace time only.  ``None`` means the call went through
    a path the engine cannot count (the tree backend's host-orchestrated
    kernel-leaf stage).  Under an outer jit the reported value reflects
    trace-time work: the outer trace's first pass re-traces the fused
    callee, later cached outer calls never re-enter Python at all.

    ``n_pivots`` is the resolved joint-bound depth this engine searched
    with (the ``eq13_multi`` intersection of DESIGN.md §3.8): 0 means the
    single-formula ``eq13`` interval bound alone, ``None`` means the
    backend does not consume the knob (brute force).

    ``generation`` / ``decay_estimate`` (engines with an online
    :class:`~repro.core.online.MutableIndex` handle only) are the handle's
    mutation counter and its tracked pruning-decay estimate at the time of
    the call — host numbers, ``None`` on engines that never mutated
    (DESIGN.md §3.9).

    **Absent-stage fields are ``None``, never 0.**  A stage that did not
    run (no tree built, element stats off, not the kernel) reports
    ``None``; ``0.0`` always means the stage ran and pruned/skipped
    nothing.  Dashboards and regression gates can therefore tell "not
    run" from "pruned nothing" without knowing the backend.  Full
    glossary: docs/search-api.md.
    """

    backend: str
    n_queries: int
    k: int
    n_blocks: int
    block_prune_frac: float = 0.0
    tile_computed_frac: float | None = None
    elem_prune_frac: float | None = None
    tree_prune_frac: float | None = None
    tree_node_eval_frac: float | None = None
    merge_rounds: float | None = None
    warm_start: bool = False
    best_first: bool = False
    n_pivots: int | None = None
    retraces: int | None = None
    generation: int | None = None
    decay_estimate: float | None = None
    extras: dict = field(default_factory=dict)

    # -- dict-style compatibility with the old ad-hoc stats dicts ----------
    def __getitem__(self, key):
        if key in self.extras:
            return self.extras[key]
        try:
            return getattr(self, key)
        except AttributeError:
            raise KeyError(key) from None

    def keys(self):
        return [f.name for f in fields(self) if f.name != "extras"] + list(self.extras)

    def items(self):
        return [(k, self[k]) for k in self.keys()]

    def as_dict(self) -> dict:
        return dict(self.items())
