"""Pallas kernel: Eq. 13 block upper bounds over pivot intervals.

Computes ``ub[m, b] = min_p max_{s in [lo[b,p], hi[b,p]]} ub_mult(qp[m,p], s)``
— the pruning predicate of the block index — as a standalone kernel so the
bound evaluation itself runs at VPU rate with VMEM-resident tiles.

Pure elementwise + small reduction: the kernel exists because on TPU the
bound evaluation for millions of (query, block) pairs is the *second*
hot-spot after the score matmul, and fusing the min-over-pivots avoids
materializing the [M, NB, P] intermediate in HBM (P× traffic reduction —
this is the memory-bound term in the roofline).

Grid: (M/BM, NB/BB).  Tiles: qp [BM, P], lo/hi [P, BB] (the intervals
are transposed so blocks ride the lanes), out [BM, BB].
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import Array
from jax.experimental import pallas as pl

DEFAULT_BM = 256
DEFAULT_BB = 256


def _kernel(qp_ref, lo_ref, hi_ref, out_ref):
    out_ref[...] = _interval_ub(qp_ref, lo_ref, hi_ref)


def _kernel_cap(qp_ref, lo_ref, hi_ref, cap_ref, out_ref):
    # extra pivot-similarity operand: intersect the precomputed joint
    # multi-pivot cap tile — min of valid upper bounds stays valid
    out_ref[...] = jnp.minimum(_interval_ub(qp_ref, lo_ref, hi_ref),
                               cap_ref[...].astype(jnp.float32))


def _interval_ub(qp_ref, lo_ref, hi_ref):
    qp = qp_ref[...].astype(jnp.float32)          # [BM, P]
    lo = lo_ref[...].astype(jnp.float32)          # [P, BB]  (blocks on lanes)
    hi = hi_ref[...].astype(jnp.float32)
    ub = None
    # one [BM, BB] pass per pivot: a [BM, BB, P] intermediate would put the
    # few pivots on the 128 lanes and overflow VMEM at deployment tiles
    for p in range(qp.shape[1]):
        a = qp[:, p:p + 1]                        # [BM, 1]
        l = lo[p:p + 1, :]                        # [1, BB]
        h = hi[p:p + 1, :]
        rad_a = jnp.maximum(0.0, 1.0 - a * a)
        ub_l = a * l + jnp.sqrt(rad_a * jnp.maximum(0.0, 1.0 - l * l))
        ub_h = a * h + jnp.sqrt(rad_a * jnp.maximum(0.0, 1.0 - h * h))
        per_pivot = jnp.where((a >= l) & (a <= h), 1.0,
                              jnp.maximum(ub_l, ub_h))
        # inverted interval (l > h): the empty-block sentinel — bound is
        # -inf (keeps this kernel value-identical to kref.block_bounds on
        # indexes that carry all-padding blocks from online mutation)
        per_pivot = jnp.where(l > h, -jnp.inf, per_pivot)
        ub = per_pivot if ub is None else jnp.minimum(ub, per_pivot)
    return ub                                     # [BM, BB]


@functools.partial(jax.jit, static_argnames=("bm", "bb", "interpret"))
def block_bounds(
    qp: Array,
    dp_min: Array,
    dp_max: Array,
    ub_cap: Array | None = None,
    *,
    bm: int = DEFAULT_BM,
    bb: int = DEFAULT_BB,
    interpret: bool = False,
) -> Array:
    """[M, P] x [NB, P] -> [M, NB] block upper bounds (f32).

    M and NB are padded internally to tile multiples; P stays whole (pivot
    counts are small, 8–64) and is looped over inside the kernel.

    ``ub_cap`` [M, NB] (optional) is an extra per-(query, block) upper
    bound — the joint multi-pivot cap of DESIGN.md §3.8 — intersected with
    the interval bound inside the kernel (tightest wins; validity is the
    caller's obligation).
    """
    m, p = qp.shape
    nb = dp_min.shape[0]
    bm_, bb_ = min(bm, max(m, 8)), min(bb, max(nb, 8))
    mp = -(-m // bm_) * bm_
    nbp = -(-nb // bb_) * bb_
    qp_p = jnp.pad(qp, ((0, mp - m), (0, 0)))
    # pad blocks with degenerate interval [2, 2]^c -> inside=False and
    # ub <= ... values unused (sliced off below); any finite pad is fine.
    # intervals transposed to [P, NB]: blocks ride the lanes
    lo_p = jnp.pad(dp_min, ((0, nbp - nb), (0, 0)), constant_values=0.0).T
    hi_p = jnp.pad(dp_max, ((0, nbp - nb), (0, 0)), constant_values=0.0).T
    in_specs = [
        pl.BlockSpec((bm_, p), lambda i, j: (i, 0)),
        pl.BlockSpec((p, bb_), lambda i, j: (0, j)),
        pl.BlockSpec((p, bb_), lambda i, j: (0, j)),
    ]
    operands = [qp_p, lo_p, hi_p]
    kern = _kernel
    if ub_cap is not None:
        assert ub_cap.shape == (m, nb), (ub_cap.shape, m, nb)
        # padded cells are sliced off below; any finite pad is fine
        cap_p = jnp.pad(ub_cap.astype(jnp.float32),
                        ((0, mp - m), (0, nbp - nb)))
        in_specs.append(pl.BlockSpec((bm_, bb_), lambda i, j: (i, j)))
        operands.append(cap_p)
        kern = _kernel_cap
    out = pl.pallas_call(
        kern,
        grid=(mp // bm_, nbp // bb_),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm_, bb_), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, nbp), jnp.float32),
        interpret=interpret,
        name="block_bounds",
    )(*operands)
    return out[:m, :nb]
