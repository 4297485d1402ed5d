"""The plain reference the benchmark holds every answer to.

A blocked f32 brute force over the regenerated corpus: each chunk of rows
is normalized, scored against the queries by one ``Precision.HIGHEST``
matmul, and merged into a running top-k.  The control computes the same in
the precision below (``scores(..., three_pass=True)``).  A corpus split over several
chips is searched shard by shard, each on the device that holds it, and
merged on the host.  It imports nothing of the program and reads nothing
the program made: it sees only the seed's corpus and the queries.

``compare`` turns one batch of answers into the numbers that decide
``correct``; each has a limit in the configuration's file.

Copied from ``chip_smoke.py``'s reference and tie-aware comparison.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
#: similarity gap below the k-th best under which candidates count as tied,
#: so that their ids may permute (f32 rounding of two differently blocked
#: HIGHEST matmuls over the same rows is ~1e-7)
TIE = 1e-5
#: rows per reference chunk
CHUNK = 1 << 17


def _unit(x):
    return x / jnp.maximum(jnp.linalg.norm(x, axis=-1, keepdims=True), 1e-12)


def _split(x):
    hi = x.astype(jnp.bfloat16).astype(jnp.float32)
    return hi, (x - hi).astype(jnp.bfloat16).astype(jnp.float32)


def scores(q, rows, *, three_pass: bool = False):
    """``q @ rows.T`` at f32 ``Precision.HIGHEST`` or, for the control,
    in the three bf16 passes that ``Precision.HIGH`` runs on the chip
    (hi*hi + hi*lo + lo*hi, each product exact, summed in f32), written
    out so that it computes the same on any platform."""
    if not three_pass:
        return jnp.dot(q, rows.T, precision=HIGHEST)
    (qh, ql), (rh, rl) = _split(q), _split(rows)
    return (jnp.dot(qh, rh.T, precision=HIGHEST)
            + jnp.dot(qh, rl.T, precision=HIGHEST)
            + jnp.dot(ql, rh.T, precision=HIGHEST))


@functools.partial(jax.jit, static_argnames=("k", "three_pass"))
def brute_shard(rows, q, *, k: int, three_pass: bool = False):
    """Top-k of the queries ``q`` [m, d] over ``rows`` [n, d], both
    normalized here: sims and local row indices [m, k], descending."""
    n, m = rows.shape[0], q.shape[0]
    chunk = min(CHUNK, n)
    q = _unit(q)

    def body(c, carry):
        best_s, best_i = carry
        start = jnp.minimum(c * chunk, n - chunk)      # last chunk clamps
        blk = _unit(jax.lax.dynamic_slice_in_dim(rows, start, chunk))
        idx = start + jnp.arange(chunk, dtype=jnp.int32)
        s = scores(q, blk, three_pass=three_pass)
        s = jnp.where((idx >= c * chunk)[None, :], s, -jnp.inf)
        s, sel = jax.lax.top_k(s, k)
        cand_s = jnp.concatenate([best_s, s], axis=1)
        cand_i = jnp.concatenate([best_i, idx[sel]], axis=1)
        best_s, sel = jax.lax.top_k(cand_s, k)
        return best_s, jnp.take_along_axis(cand_i, sel, axis=1)

    init = (jnp.full((m, k), -jnp.inf, jnp.float32),
            jnp.full((m, k), -1, jnp.int32))
    return jax.lax.fori_loop(0, -(-n // chunk), body, init)


@jax.jit
def exact_shard(rows, q, local):
    """HIGHEST similarity of each query to each of its ids, given as local
    row indices of ``rows`` (-inf where an id is not a row here)."""
    n = rows.shape[0]
    ok = (local >= 0) & (local < n)
    r = _unit(rows[jnp.clip(local, 0, n - 1)])        # [m, k, d]
    s = jnp.einsum("md,mkd->mk", _unit(q), r, precision=HIGHEST)
    return jnp.where(ok, s, -jnp.inf)


def _on(x, rows):
    return jax.device_put(x, next(iter(rows.devices())))


def brute(shards, q: np.ndarray, k: int, *, three_pass: bool = False):
    """Top-k over every shard, merged: (sims [m, k], global ids [m, k]) on
    the host.  ``shards`` is a list of (device rows, first global row id)."""
    parts = [brute_shard(rows, _on(q, rows), k=k, three_pass=three_pass)
             for rows, _ in shards]
    s = np.concatenate([np.asarray(p[0]) for p in parts], axis=1)
    i = np.concatenate([np.asarray(p[1]) + off
                        for p, (_, off) in zip(parts, shards)], axis=1)
    top = np.argsort(-s, axis=1, kind="stable")[:, :k]
    return (np.take_along_axis(s, top, axis=1),
            np.take_along_axis(i, top, axis=1))


def exact(shards, q: np.ndarray, ids: np.ndarray) -> np.ndarray:
    """HIGHEST similarity of each query to each of its global ids."""
    parts = [exact_shard(rows, _on(q, rows), _on(ids - off, rows))
             for rows, off in shards]
    return np.max(np.stack([np.asarray(p) for p in parts]), axis=0)


def compare(sims, ids, ref_s, ref_i, own, *, k: int, n_rows: int) -> dict:
    """Readings of one set of answers against the reference.

    ``ref_s`` / ``ref_i`` are the reference's top ``k + 1``; ``own`` is the
    reference's score of each returned id.  Counts of answers that are
    wrong by more than rounding (each is an exact comparison):
      bad_rows    the wrong shape, a non-finite score, an id outside the
                  corpus, or a repeated id;
      id_mismatch an id set other than the reference's, where the gap below
                  the k-th best exceeds ``TIE``;
      sim_off     a similarity more than ``TIE`` from the reference's at
                  the same rank;
      score_off   a similarity more than ``TIE`` from the reference's score
                  of the returned id.
    Gaps, over every rank of every well-formed answer (the readings that
    set a precision limit):
      sim_gap     widest |returned - reference| similarity at one rank;
      score_gap   widest |returned similarity - reference score of its id|;
      mean_gap    mean |returned - reference| similarity at one rank;
      bias        |mean signed (returned - reference)| similarity.
    """
    sims, ids = np.asarray(sims, np.float64), np.asarray(ids)
    m = ref_s.shape[0]
    if sims.shape != (m, k) or ids.shape != (m, k):
        inf = float("inf")
        return {"bad_rows": m, "id_mismatch": m, "sim_off": m,
                "score_off": m, "sim_gap": inf, "score_gap": inf,
                "mean_gap": inf, "bias": inf}
    srt = np.sort(ids, axis=1)
    ok = ~(~np.isfinite(sims).all(axis=1)
           | ((ids < 0) | (ids >= n_rows)).any(axis=1)
           | (srt[:, 1:] == srt[:, :-1]).any(axis=1))
    clear = (ref_s[:, k - 1] - ref_s[:, k]) > TIE
    mismatch = sum(1 for r in np.flatnonzero(clear)
                   if set(ids[r].tolist()) != set(ref_i[r, :k].tolist()))
    diff = (sims - ref_s[:, :k].astype(np.float64))[ok]
    score = np.abs(sims - np.asarray(own, np.float64))[ok]
    if not diff.size:
        diff = score = np.array([[np.inf]])
    return {"bad_rows": int((~ok).sum()),
            "id_mismatch": int(mismatch),
            "sim_off": int((np.abs(diff) > TIE).any(axis=1).sum()),
            "score_off": int((score > TIE).any(axis=1).sum()),
            "sim_gap": float(np.abs(diff).max()),
            "score_gap": float(score.max()),
            "mean_gap": float(np.abs(diff).mean()),
            "bias": float(abs(diff.mean()))}
