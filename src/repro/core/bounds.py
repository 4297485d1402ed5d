"""Triangle-inequality bounds for Cosine similarity (Schubert, SISAP 2021).

All functions are elementwise over arrays of *similarities*:

    a = sim(x, z)    b = sim(z, y)        a, b in [-1, 1]

and return a bound on ``sim(x, y)``.  Equation numbers follow the paper.

The recommended pair (paper §5) is :func:`lb_mult` / :func:`ub_mult`::

    sim(x,y) >= a*b - sqrt((1-a^2)(1-b^2))      (Eq. 10, tight)
    sim(x,y) <= a*b + sqrt((1-a^2)(1-b^2))      (Eq. 13, tight)

These are mathematically equivalent to the arccos forms (Eq. 9) but avoid
trigonometric calls entirely — on TPU the arccos form would lower to slow VPU
polynomial approximations while the Mult form is pure mul/sub/rsqrt.

Numerical notes (paper §4.2): the ``1 - sim^2`` radicands are clamped at zero.
When cancellation would occur (sim -> 1) the sqrt term itself vanishes, so the
clamp does not change the value, it only guards against producing NaN from a
tiny negative radicand in floating point.

Every function here has a float64 numpy oracle twin in :mod:`repro.core.ref`;
the property tests in ``tests/test_bounds.py`` check validity (bounds never
cross the true similarity computed from explicit vectors) and the ordering
relations of the paper's Fig. 3.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import Array

__all__ = [
    "lb_euclid",
    "lb_euclid_fast",
    "lb_arccos",
    "lb_mult",
    "lb_mult_fast1",
    "lb_mult_fast2",
    "ub_mult",
    "ub_euclid",
    "ub_arccos",
    "pivot_lower_bound",
    "pivot_upper_bound",
    "LOWER_BOUNDS",
    "JOINT_SLACK",
    "ub_joint",
    "joint_row_upper_bound",
    "BOUND_PROVIDERS",
    "register_bound_provider",
    "block_upper_provider",
]


def _radicand(s: Array) -> Array:
    """``max(0, 1 - s^2)`` — clamped radicand, see module docstring."""
    return jnp.maximum(0.0, 1.0 - s * s)


def lb_euclid(a: Array, b: Array) -> Array:
    """Eq. (7): lower bound via the Euclidean / chord-length metric.

    ``sim(x,y) >= a + b - 1 - 2*sqrt((1-a)(1-b))``
    """
    rad = jnp.maximum(0.0, (1.0 - a) * (1.0 - b))
    return a + b - 1.0 - 2.0 * jnp.sqrt(rad)


def lb_euclid_fast(a: Array, b: Array) -> Array:
    """Eq. (8) "Eucl-LB": sqrt-free approximation of Eq. (7); loosest bound.

    ``sim(x,y) >= a + b + 2*min(a,b) - 3``
    """
    return a + b + 2.0 * jnp.minimum(a, b) - 3.0


def lb_arccos(a: Array, b: Array) -> Array:
    """Eq. (9): tight lower bound via arc length (angles add on the sphere).

    ``sim(x,y) >= cos(arccos(a) + arccos(b))``

    Mathematically identical to :func:`lb_mult`; kept for the reproduction of
    the paper's Table 2 / Fig. 5 comparisons.  Inputs are clipped to [-1, 1]
    so ``arccos`` stays defined under fp roundoff.
    """
    ca = jnp.arccos(jnp.clip(a, -1.0, 1.0))
    cb = jnp.arccos(jnp.clip(b, -1.0, 1.0))
    return jnp.cos(ca + cb)


def lb_mult(a: Array, b: Array) -> Array:
    """Eq. (10) "Mult" (recommended): tight, trigonometry-free lower bound.

    ``sim(x,y) >= a*b - sqrt((1-a^2)(1-b^2))``
    """
    return a * b - jnp.sqrt(_radicand(a) * _radicand(b))


def lb_mult_fast1(a: Array, b: Array) -> Array:
    """Eq. (11) "Mult-LB1": sqrt-free; best of the simplified bounds.

    ``sim(x,y) >= a*b + min(a,b)^2 - 1``
    """
    m = jnp.minimum(a, b)
    return a * b + m * m - 1.0


def lb_mult_fast2(a: Array, b: Array) -> Array:
    """Eq. (12) "Mult-LB2": sqrt-free; strictly inferior to Eq. (11).

    ``sim(x,y) >= 2*a*b - |a - b| - 1``
    """
    return 2.0 * a * b - jnp.abs(a - b) - 1.0


def ub_mult(a: Array, b: Array) -> Array:
    """Eq. (13): tight upper bound — the pruning workhorse for kNN search.

    ``sim(x,y) <= a*b + sqrt((1-a^2)(1-b^2))``
    """
    return a * b + jnp.sqrt(_radicand(a) * _radicand(b))


def ub_euclid(a: Array, b: Array) -> Array:
    """Upper bound via the chord metric (reverse of Eq. 7; looser than Eq. 13).

    From ``d_sqrtcos(x,y) >= |d(x,z) - d(z,y)|``:
    ``sim(x,y) <= a + b - 1 + 2*sqrt((1-a)(1-b))``
    """
    rad = jnp.maximum(0.0, (1.0 - a) * (1.0 - b))
    return a + b - 1.0 + 2.0 * jnp.sqrt(rad)


def ub_arccos(a: Array, b: Array) -> Array:
    """Arccos form of the upper bound: ``cos(|arccos(a) - arccos(b)|)``."""
    ca = jnp.arccos(jnp.clip(a, -1.0, 1.0))
    cb = jnp.arccos(jnp.clip(b, -1.0, 1.0))
    return jnp.cos(jnp.abs(ca - cb))


# ---------------------------------------------------------------------------
# Pivot-set (LAESA-style) bounds: combine bounds over several reference points.
# ---------------------------------------------------------------------------

def pivot_lower_bound(qp: Array, dp: Array, *, axis: int = -1) -> Array:
    """Best (largest) Eq. 10 lower bound over a set of pivots.

    Args:
      qp: similarities of the query to each pivot, shape ``[..., P]``.
      dp: similarities of the database object to each pivot, ``[..., P]``.
      axis: the pivot axis to reduce over.

    Returns ``max_p lb_mult(qp_p, dp_p)`` — every pivot yields a valid lower
    bound, so the max is a valid (and the tightest available) lower bound.
    """
    return jnp.max(lb_mult(qp, dp), axis=axis)


def pivot_upper_bound(qp: Array, dp: Array, *, axis: int = -1) -> Array:
    """Tightest (smallest) Eq. 13 upper bound over a set of pivots.

    ``min_p ub_mult(qp_p, dp_p)`` — the pruning rule of the block index:
    a candidate (or block) whose pivot upper bound falls below the running
    k-th best similarity cannot be a true neighbor.
    """
    return jnp.min(ub_mult(qp, dp), axis=axis)


#: name -> fn map in the paper's Table 1 order (used by benchmarks/tests).
LOWER_BOUNDS = {
    "euclidean": lb_euclid,       # Eq. 7
    "eucl_lb": lb_euclid_fast,    # Eq. 8
    "arccos": lb_arccos,          # Eq. 9
    "mult": lb_mult,              # Eq. 10 (recommended)
    "mult_lb1": lb_mult_fast1,    # Eq. 11
    "mult_lb2": lb_mult_fast2,    # Eq. 12
}


# ---------------------------------------------------------------------------
# Joint multi-pivot (simplex / projection) upper bound.
#
# With an orthonormalized pivot basis U (see
# :func:`repro.core.pivots.orthonormal_pivot_basis`), the coordinates
# alpha = U q and beta = U y of two unit vectors satisfy
#
#     sim(q, y) <= <alpha, beta> + sqrt((1 - |alpha|^2)(1 - |beta|^2))
#
# because the residuals of q and y orthogonal to span(U) have norms
# sqrt(1 - |alpha|^2) and sqrt(1 - |beta|^2) and can at best be parallel.
# At one pivot this IS Eq. 13; at P = d it degenerates to the exact score.
# Validity for duplicate / dependent pivots is by the jittered-lift
# argument recorded in DESIGN.md §3.8.
# ---------------------------------------------------------------------------

#: Additive guard for float32 accumulation in the joint bound's dot
#: products.  The paper's single-pivot bounds need no slack (their clamped
#: radicands only remove NaN), but the joint bound sums up to d products,
#: so a few ulps of headroom keep it a true upper bound in fp32.
JOINT_SLACK = 3e-5


def ub_joint(t: Array, a_nsq: Array, b_nsq: Array) -> Array:
    """Joint projection upper bound from precomputed pieces.

    Args:
      t: ``<alpha, beta>`` inner products of pivot-basis coordinates.
      a_nsq: ``|alpha|^2`` (must already be clamped to ``<= 1``).
      b_nsq: ``|beta|^2`` (likewise).
    """
    rad = jnp.maximum(0.0, 1.0 - a_nsq) * jnp.maximum(0.0, 1.0 - b_nsq)
    return t + jnp.sqrt(rad)


def joint_row_upper_bound(
    alpha: Array, beta: Array, beta_nsq: Array, *, slack: float = JOINT_SLACK
) -> Array:
    """Per-(query, row) joint bound table.

    Args:
      alpha: [M, J] query coordinates in the pivot basis.
      beta:  [N, J] database-row coordinates.
      beta_nsq: [N] precomputed ``|beta|^2`` at this prefix depth.

    Returns [M, N] float32 upper bounds on ``sim(q_m, y_n)``.
    """
    t = jnp.dot(alpha, beta.T, precision=jax.lax.Precision.HIGHEST)
    a_nsq = jnp.minimum(jnp.sum(alpha * alpha, axis=-1), 1.0)
    b_nsq = jnp.minimum(beta_nsq, 1.0)
    return ub_joint(t, a_nsq[:, None], b_nsq[None, :]) + slack


# ---------------------------------------------------------------------------
# Bound-provider contract.
#
# A provider maps (index, qn, qp, n_pivots) -> [M, NB] per-block upper
# bounds.  ``eq13`` is the classic single-formula interval bound (already
# intersected over the index's pivot-similarity intervals); ``eq13_multi``
# additionally intersects the joint n_pivots-deep projection cap — the min
# of valid upper bounds is a valid upper bound, so validity is inherited
# pointwise.  The registry keeps the family pluggable (e.g. a future
# Ptolemaic instance) without the engine knowing any formula.
# ---------------------------------------------------------------------------

#: name -> provider(index, qn, qp, n_pivots) -> [M, NB] block upper bounds.
BOUND_PROVIDERS: dict = {}


def register_bound_provider(name: str):
    """Decorator: register a block upper-bound provider under ``name``."""

    def deco(fn):
        BOUND_PROVIDERS[name] = fn
        return fn

    return deco


def block_upper_provider(name: str):
    """Look up a registered bound provider (KeyError lists known names)."""
    try:
        return BOUND_PROVIDERS[name]
    except KeyError:
        raise KeyError(
            f"unknown bound provider {name!r}; known: {sorted(BOUND_PROVIDERS)}"
        ) from None


@register_bound_provider("eq13")
def _eq13_provider(index, qn: Array, qp: Array, n_pivots: int = 0) -> Array:
    """Interval Eq. 13 bound, intersected over the index's pivots."""
    from repro.kernels import ref as kref  # local: keep core import-light

    return kref.block_bounds(qp, index.dp_min, index.dp_max)


@register_bound_provider("eq13_multi")
def _eq13_multi_provider(index, qn: Array, qp: Array, n_pivots: int) -> Array:
    """Eq. 13 intervals intersected with the joint n_pivots projection cap."""
    from repro.core.index import multipivot_block_cap  # local: avoid cycle
    from repro.kernels import ref as kref

    base = kref.block_bounds(qp, index.dp_min, index.dp_max)
    if n_pivots <= 0 or index.ortho is None:
        return base
    return jnp.minimum(base, multipivot_block_cap(index, qn, n_pivots=n_pivots))
