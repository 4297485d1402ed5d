"""The jax entry points the repo routes through one module.

The installed jax (0.9.0, pinned in ``pyproject.toml``) provides all of
these natively; the wrappers stay so that every call site — and lint rule
R002, which keeps the raw APIs out of the rest of the tree — has one place
to change when jax moves them again.

The multi-host helpers (:func:`make_process_local_array`,
:func:`replicate_to_mesh`, :func:`multiprocess_cpu_init`) wrap the
process-local array-assembly surface the distributed paths rely on.
"""
from __future__ import annotations

import jax
import numpy as np

__all__ = ["shard_map", "optimization_barrier", "make_process_local_array",
           "replicate_to_mesh", "multiprocess_cpu_init"]


def optimization_barrier(x):
    """``jax.lax.optimization_barrier`` (differentiable natively)."""
    return jax.lax.optimization_barrier(x)


def shard_map(f, *, mesh, in_specs, out_specs, check_vma: bool = True,
              axis_names=None):
    """``jax.shard_map``; ``axis_names`` selects the manual axes
    (partial-manual mode)."""
    kw = dict(mesh=mesh, in_specs=in_specs, out_specs=out_specs,
              check_vma=check_vma)
    if axis_names is not None:
        kw["axis_names"] = axis_names
    return jax.shard_map(f, **kw)


def multiprocess_cpu_init(coordinator_address: str, num_processes: int,
                          process_id: int) -> None:
    """``jax.distributed.initialize`` for multi-process CPU workers (the
    CPU client's default cross-process collectives are gloo).  Call this
    before any other jax API touches devices."""
    jax.distributed.initialize(coordinator_address=coordinator_address,
                               num_processes=num_processes,
                               process_id=process_id)


def make_process_local_array(sharding, local_data: np.ndarray, global_shape):
    """``jax.make_array_from_process_local_data`` behind one call site.

    ``local_data`` holds this process's rows of a ``global_shape`` array
    sharded by ``sharding``: the process's addressable slices of the
    global array, concatenated in ascending global order along every
    dimension where ``local_data`` is smaller than the global shape (the
    upstream function's documented mapping).  Dimensions where the local
    and global sizes match are read at global coordinates (replicated
    data must therefore be identical on every process).
    """
    return jax.make_array_from_process_local_data(
        sharding, np.asarray(local_data), tuple(global_shape))


def replicate_to_mesh(x, mesh):
    """A fully-replicated global array from identical per-process host data.

    Single-process: plain ``jnp.asarray`` (no behavior change on the
    existing paths).  Multi-process: every process passes the same host
    array and receives one global array replicated over ``mesh`` — the
    form ``jit``/``shard_map`` require for replicated operands when the
    mesh spans processes.
    """
    import jax.numpy as jnp

    if jax.process_count() == 1:
        return jnp.asarray(x)
    from jax.sharding import NamedSharding, PartitionSpec
    x = np.asarray(x)
    return make_process_local_array(NamedSharding(mesh, PartitionSpec()), x,
                                    x.shape)
