"""Pivot (reference point) selection for LAESA-style bound pruning.

The quality of the Eq. 13 pruning bound depends on how well the pivots
"cover" the dataset in angle space: a candidate is pruned when some pivot z
has ``ub_mult(sim(q,z), sim(y,z)) < tau``, which is tightest when z is nearly
collinear with q or y.  We use greedy max-min (farthest-first / k-center)
selection in arc distance, the standard choice for metric indexes, plus a
cheap random fallback.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import Array


def normalize(x: Array, eps: float = 1e-12) -> Array:
    """L2-normalize along the last axis (safe for zero rows)."""
    n = jnp.linalg.norm(x, axis=-1, keepdims=True)
    return x / jnp.maximum(n, eps)


@functools.partial(jax.jit, static_argnames=("n_pivots", "first"))
def select_pivots_maxmin(db: Array, n_pivots: int, *, first: int = 0) -> Array:
    """Greedy farthest-first pivot selection (returns pivot *indices*).

    Iteratively picks the point whose maximum similarity to the already
    selected pivots is smallest (i.e. the angularly farthest point).  Runs in
    O(n_pivots * n * d) as one jitted ``lax.fori_loop`` over the rows as
    given: each row's similarity is scaled by its inverse norm, so no
    normalized corpus-sized copy is ever held beside the input.

    Args:
      db: [n, d] database (need not be normalized).
      n_pivots: number of pivots to select (>= 1).
      first: index of the initial pivot (deterministic by default).
    """
    db = db.astype(jnp.float32)
    n = db.shape[0]
    inv_norm = 1.0 / jnp.maximum(jnp.linalg.norm(db, axis=-1), 1e-12)

    def body(i, state):
        idx, max_sim = state
        # similarity of every point to the i-1'th chosen pivot
        prev = db[idx[i - 1]] * inv_norm[idx[i - 1]]
        sims = jnp.dot(db, prev,
                       precision=jax.lax.Precision.HIGHEST) * inv_norm
        max_sim = jnp.maximum(max_sim, sims)
        # next pivot: the point least similar to all chosen so far
        nxt = jnp.argmin(max_sim)
        idx = idx.at[i].set(nxt)
        return idx, max_sim

    idx0 = jnp.zeros((n_pivots,), jnp.int32).at[0].set(first)
    max_sim0 = jnp.full((n,), -jnp.inf, jnp.float32)
    idx, _ = jax.lax.fori_loop(1, n_pivots, body, (idx0, max_sim0))
    return idx


def select_pivots_random(n: int, n_pivots: int, seed: int = 0) -> Array:
    """Uniform random pivot indices (cheap baseline).

    ``n_pivots`` is clamped to ``n``: asking for more pivots than points is
    a degenerate-but-reachable configuration (tiny shards route here, see
    ``repro.core.distributed``), and ``choice(replace=False)`` would raise.
    """
    rng = np.random.default_rng(seed)
    n_pivots = max(1, min(n_pivots, n))
    return jnp.asarray(rng.choice(n, size=n_pivots, replace=False).astype(np.int32))


def suggest_bound_pivots(n: int, d: int) -> int:
    """Pivot-table depth for the joint ``eq13_multi`` bound (see
    :mod:`repro.core.bounds`).

    ``d`` pivots span the whole space — the joint projection bound then
    *equals* the exact score (it prunes perfectly but costs a full matmul to
    evaluate), while shallow tables lose all power on uniform high-d data
    (the per-pivot residuals stay near 1).  ``7d/8`` keeps a usable
    orthogonal remainder and is where the uniform-regime block pruning
    plateaus on the pruning bench; clamped to ``n - 1`` so tiny corpora
    stay non-degenerate.
    """
    return max(1, min(7 * d // 8, max(1, n - 1)))


def orthonormal_pivot_basis(pivots, jitter: float = 1e-6) -> np.ndarray:
    """Orthonormalized pivot basis ``U = R^{-1} Z`` for the joint bound.

    ``Z`` [P, d] are the (unit) pivot rows, ``G = Z Z^T`` their Gram, and
    ``R`` the lower Cholesky factor of ``G + jitter*I``.  The rows of ``U``
    are the first ``P`` vectors of a Gram–Schmidt basis of the *lifted*
    pivots ``z~_i = (z_i, sqrt(jitter)*e_i)`` (whose Gram is exactly
    ``G + jitter*I``), so for any unit ``x`` the coordinate vector
    ``alpha = U @ x`` satisfies ``|alpha| <= 1`` and the joint upper bound
    of :func:`repro.core.bounds.ub_joint` is valid — including for
    duplicate or linearly dependent pivots, where the jitter keeps the
    factorization defined (DESIGN.md §3.8).

    Because ``R`` is lower triangular and the maxmin selection is nested
    (greedy), the first ``k`` rows of ``U`` are exactly the basis that a
    ``k``-pivot table would have built: one full-width table serves every
    prefix ``n_pivots <= P``.

    Host-side float64 numpy (build-time only); escalates the jitter ×10
    until the factorization succeeds.
    """
    z = np.asarray(pivots, np.float64)
    p = z.shape[0]
    gram = z @ z.T
    eps = float(jitter)
    for _ in range(24):
        try:
            chol = np.linalg.cholesky(gram + eps * np.eye(p))
            break
        except np.linalg.LinAlgError:
            eps *= 10.0
    else:  # pragma: no cover - float64 PSD + jitter cannot get here
        raise np.linalg.LinAlgError("pivot Gram not factorizable")
    from scipy.linalg import solve_triangular

    return solve_triangular(chol, z, lower=True)
