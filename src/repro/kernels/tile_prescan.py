"""Pallas kernel: the τ warm-start prescan over a column-stored corpus.

:func:`repro.search.backends.tau_warm_start` gathers each query's
best-bound tiles with an XLA gather over ``db.reshape(nt, bn, d)``.  Where
the runtime stores the corpus column-major (:func:`cosine_topk.db_layout`
is ``"cols"``) that reshape is a whole-corpus relayout on every call.
This kernel instead reads ``db.T`` (``[d, N]``, a bitcast of the stored
buffer): grid step ``(i, j)`` DMAs query i's j-th best tile as one
``(d, bn)`` block, its tile id scalar-prefetched into the index map, and
scores it against the query.  The k-th best of the scores is taken outside,
as ``tau_warm_start`` takes it, so both give the same τ for the same tiles.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import Array
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import ref as kref

_NEG_INF = float("-inf")


def _kernel(best_ref, q_ref, db_ref, rv_ref, out_ref):
    del best_ref                                      # read by the index maps
    scores = jax.lax.dot_general(
        q_ref[...], db_ref[...], (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )                                                 # [1, BN]
    out_ref[...] = jnp.where(rv_ref[...] > 0, scores, _NEG_INF)


@functools.partial(jax.jit, static_argnames=("k", "n_pre", "bn", "interpret"))
def tau_prescan(qn: Array, db_t: Array, row_valid: Array, ub: Array, *,
                k: int, n_pre: int, bn: int, interpret: bool = False) -> Array:
    """τ seeds from each query's ``n_pre`` best-bound tiles of ``db_t``.

    Args:
      qn:        [m, d] normalized queries.
      db_t:      [d, N] the corpus, transposed (tile t is columns
                 ``t * bn`` to ``(t + 1) * bn``).
      row_valid: [N] bool, False on padding and tombstoned rows.
      ub:        [m, N // bn] per-(query, tile) upper bounds; each query
                 scores the ``n_pre`` tiles where it is highest.

    Returns [m] f32: the k-th best score over the valid rows of those tiles,
    ``-inf`` where they hold fewer than k — what
    :func:`~repro.search.backends.tau_warm_start` returns for the same tiles.
    """
    m, d = qn.shape
    n = db_t.shape[1]
    nt = n // bn
    assert n % bn == 0 and ub.shape == (m, nt), (n, bn, ub.shape)
    n_pre = max(1, min(n_pre, nt))
    if n_pre * bn < k:
        return jnp.full((m,), -jnp.inf, jnp.float32)
    best = jax.lax.top_k(ub, n_pre)[1].astype(jnp.int32).reshape(-1)

    def tile(i, j, best_):
        return best_[i * n_pre + j]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,                        # best, [m * n_pre]
        grid=(m, n_pre),
        in_specs=[
            pl.BlockSpec((None, 1, d), lambda i, j, b: (i, 0, 0)),     # query
            pl.BlockSpec((d, bn), lambda i, j, b: (0, tile(i, j, b))),  # db
            pl.BlockSpec((None, 1, bn),
                         lambda i, j, b: (tile(i, j, b), 0, 0)),  # row valid
        ],
        out_specs=pl.BlockSpec((None, 1, bn), lambda i, j, b: (i, 0, j)),
    )
    scores = pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, 1, n_pre * bn), jnp.float32),
        interpret=interpret,
        name="tau_prescan",
    )(best, qn.reshape(m, 1, d), db_t,
      row_valid.astype(jnp.int32).reshape(nt, 1, bn))
    tau = kref.kth_value(scores.reshape(m, n_pre * bn), k)
    return jnp.where(jnp.isfinite(tau), tau, -jnp.inf)
