"""The whole MS MARCO corpus, split by rows over the mesh.

8,841,823 rows do not split evenly over four chips, and an array split by
rows over a mesh must.  So the corpus is drawn at the next multiple of
the shard count, with ``corpus.make_corpus``'s own generator, and the rows
past ``cfg["rows"]`` are set to zero in place: padding, which the
configuration's ``build.global_rows`` tells the engine to mark invalid.
The reference scores a zero row 0, far below any answer here, and counts
an id at or past ``cfg["rows"]`` as a bad row, so a returned pad row
fails ``correct``.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import corpus


@functools.partial(jax.jit, static_argnames="n", donate_argnums=0)
def _zero_tail(db, *, n: int):
    """Rows ``n`` and past set to zero, in place: elementwise, so each
    chip rewrites its own rows and nothing moves between chips."""
    row = jax.lax.broadcasted_iota(jnp.int32, db.shape, 0)
    return jnp.where(row < n, db, 0.0)


def generate(cfg: dict, seed: int, devices):
    """The seed's corpus, padded to a multiple of the shard count, split by
    rows over ``devices``; the key for the queries; the mesh."""
    shards, rows = int(cfg["shards"]), int(cfg["rows"])
    key_db, key_q = jax.random.split(corpus.seed_key(seed))
    mesh = Mesh(np.array(devices[:shards]), ("data",))
    db = corpus.make_corpus(key_db, -(-rows // shards) * shards,
                            int(cfg["dim"]),
                            n_centers=int(cfg["assumed"]["n_centers"]),
                            noise=float(cfg["assumed"]["noise"]),
                            sharding=NamedSharding(mesh, P("data")))
    return _zero_tail(db, n=rows), key_q, mesh
