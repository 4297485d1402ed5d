"""Seeded inputs of the chip benchmark, generated on the device.

A clustered corpus (unit cluster centres plus isotropic noise, not
normalized: the index build normalizes, as it would a user's embeddings)
and queries drawn from it (a share of perturbed corpus rows, the rest
random unit vectors).  The same seed gives the same corpus and queries,
bit for bit, so the reference can regenerate the corpus after the
program's state is freed.

The generator is a copy of the one in ``chip_smoke.py``, kept here so that
the benchmark's inputs cannot change with the program.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def seed_key(seed: int):
    """A PRNG key from any whole number: the low 32 bits seed it and the
    rest is folded in, so seeds past 2**32 stay distinct."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              seed >> 32)


def make_corpus(key, n: int, d: int, *, n_centers: int, noise: float,
                sharding=None):
    """``[n, d]`` f32 clustered mixture, placed by ``sharding`` (one
    device's default placement when ``None``)."""

    def gen(key):
        kc, kl, kn = jax.random.split(key, 3)
        c = jax.random.normal(kc, (n_centers, d), jnp.float32)
        c = c / jnp.linalg.norm(c, axis=1, keepdims=True)
        lab = jax.random.randint(kl, (n,), 0, n_centers)
        return c[lab] + noise * jax.random.normal(kn, (n, d), jnp.float32)

    return jax.jit(gen, out_shardings=sharding)(key)


def make_queries(key, db, m: int, *, near_share: float, near_noise: float):
    """``[m, d]`` unit queries: ``near_share`` of them perturbed corpus rows
    (``near_noise`` per coordinate after normalizing the row), the rest
    random unit vectors."""
    n_near = int(round(near_share * m))

    @jax.jit
    def gen(key, db):
        k1, k2, k3 = jax.random.split(key, 3)
        rows = db[jax.random.randint(k1, (n_near,), 0, db.shape[0])]
        rows = rows / jnp.linalg.norm(rows, axis=1, keepdims=True)
        near = rows + near_noise * jax.random.normal(k2, rows.shape,
                                                     jnp.float32)
        far = jax.random.normal(k3, (m - n_near, db.shape[1]), jnp.float32)
        q = jnp.concatenate([near, far])
        return q / jnp.linalg.norm(q, axis=1, keepdims=True)

    return gen(key, db)
