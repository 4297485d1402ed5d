"""Tiny exact-search collectives (run inside ``shard_map``).

The sharded datastore pattern: every shard computes its exact local top-k,
then the global top-k is the top-k of the union — ``O(devices * k)`` bytes
on the wire, negligible next to the score matmuls the pruning avoided.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import Array

__all__ = ["topk_allgather_merge", "masked_topk_merge", "global_tau_merge"]


def topk_allgather_merge(sims: Array, ids: Array, k: int, axis_names):
    """Merge per-shard (sims [m, k], ids [m, k]) into the global top-k.

    All-gathers the candidate sets over ``axis_names`` (a mesh axis name or
    tuple of names) and re-runs ``top_k`` on the ``[m, shards * k]`` union.
    Exact: every shard's true local top-k is in the union, and the global
    top-k is a subset of the union of local top-k sets.  The sims travel
    bit for bit as int32 beside the ids (bools as 0/1), so the exchange is
    one all-gather.
    """
    axis_names = (axis_names,) if isinstance(axis_names, str) else tuple(axis_names)
    kk = sims.shape[1]
    packed = jnp.concatenate(
        [jax.lax.bitcast_convert_type(sims, jnp.int32),
         ids.astype(jnp.int32)], axis=1)            # [m, 2k]
    both = jax.lax.all_gather(packed, axis_names)   # [S, m, 2k]
    m = both.shape[1]
    s = jax.lax.bitcast_convert_type(both[..., :kk], sims.dtype)
    s = jnp.moveaxis(s, 0, 1).reshape(m, -1)        # [m, S * k]
    g = jnp.moveaxis(both[..., kk:], 0, 1).reshape(m, -1)
    top_s, pos = jax.lax.top_k(s, k)
    top_g = jnp.take_along_axis(g, pos, axis=1).astype(ids.dtype)
    return top_s, top_g


def masked_topk_merge(sims: Array, valid: Array, k: int, axis_names):
    """Mask-carrying top-k merge: per-shard candidate scores + validity.

    Like :func:`topk_allgather_merge` but the payload is a boolean
    validity mask instead of ids: all-gathers per-shard ``(sims [m, k],
    valid [m, k])`` candidate lists, masks invalid entries to ``-inf``,
    and returns the top-k of the union together with the surviving mask.
    ``valid[i, j]`` must be True iff ``sims[i, j]`` is the exact score of
    a *real* database row (warm-start prescans pad with ``-inf`` /
    ``False`` when a shard holds fewer than k candidates) — carrying the
    mask through the merge is what lets a consumer distinguish "k-th best
    of ≥ k real candidates" from "ran out of candidates", which a bare
    ``-inf`` convention cannot once scores are compared across shards.
    """
    # the id-merge already gathers an arbitrary payload column alongside
    # the scores; riding it with the mask as payload keeps one collective
    return topk_allgather_merge(jnp.where(valid, sims, -jnp.inf), valid, k,
                                axis_names)


def global_tau_merge(sims: Array, valid: Array, k: int, axis_names) -> Array:
    """Global τ broadcast: k-th best of the union of per-shard candidates.

    The returned ``tau [m]`` is the k-th highest *real* candidate score
    across every shard's warm-start list, or ``-inf`` for queries whose
    union holds fewer than k real candidates (no seed, never a wrong
    one).  Because each entry is the exact score of a real database row,
    τ is a true lower bound on the final **global** k-th best similarity
    — the exactness keystone of the sharded tree descent (DESIGN.md
    §3.6): any subtree or block with ``ub + margin < τ`` on *any* shard
    provably contains no global top-k member, so per-shard pruning
    against this one broadcast scalar per query is globally safe.
    """
    from repro.dist.compat import optimization_barrier

    top_s, top_v = masked_topk_merge(sims, valid, k, axis_names)
    # barrier before slicing the k-th column: the folded [k-1:k] slice
    # breaks XLA's TopkRewriter and the merge's top_k silently lowers to
    # a full sort (see repro.kernels.ref.kth_value for the measurement)
    top_s = optimization_barrier(top_s)
    return jnp.where(top_v[:, -1], top_s[:, -1], -jnp.inf)
