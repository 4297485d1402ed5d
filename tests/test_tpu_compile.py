"""Every Pallas kernel of the search path compiles for a TPU v5e chip.

The interpreter that runs the kernels in the CPU tests does not enforce
Mosaic's rules (the (8, 128) block tiling, VMEM capacity, SMEM scalars);
only the TPU compiler does.  These tests compile the kernels at
deployment widths (d=768, one kernel tile of 256 rows, k=10 and k=100)
against a *described* v5e topology — no chip is needed — and check that
each compiled program holds the kernel as a ``tpu_custom_call``.

The topology is described inside a fixture (never at import), so every
pytest-xdist worker collects the same tests and only the worker that runs
this file loads the TPU compiler.  The persistent compilation cache is off
around the compiles: an entry written for a described chip cannot be read
back here.
"""
import math
import re
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.index import BlockIndex
from repro.kernels import bound_prune, cosine_topk, leaf_gather
from repro.search.backends import get_backend

D, BN, P = 768, 256, 16       # MS MARCO passage embeddings, kernel tile, pivots
M = 256                       # queries per search batch
NT = 256                      # db tiles per compile (65,536 rows)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture
def shape(one_chip):
    def make(*dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)
    return make


def _compiled_text(fn, *args, **kw):
    return jax.jit(fn, **kw).lower(*args).compile().as_text()


@pytest.mark.parametrize("k,variant", [(10, "engine"), (100, "engine"),
                                       (10, "joint_cap"),
                                       (10, "element_stats")])
def test_pruned_topk_compiles_for_v5e(k, variant, shape, no_persistent_cache):
    """The fused kernel with τ seeds, a best-first block order and per-row
    validity — the operands the kernel backend passes — plus the joint
    cap and element-counter variants."""
    n = NT * BN
    extra = {}
    args = [shape(M, D), shape(n, D), shape(M, P), shape(NT, P),
            shape(NT, P), shape(M), shape(M // cosine_topk.DEFAULT_BM, NT,
                                          dtype=jnp.int32),
            shape(n, dtype=jnp.bool_)]
    if variant == "joint_cap":
        args.append(shape(M, NT))
        extra["cap"] = True
    if variant == "element_stats":
        args.append(shape(n, P))
        extra["dp"] = True

    def search(qn, db, qp, lo, hi, tau, order, valid, *rest):
        rest = list(rest)
        return cosine_topk.pruned_topk(
            qn, db, qp, lo, hi, valid.sum(), tau_init=tau, block_order=order,
            row_valid=valid, ub_cap=rest.pop(0) if "cap" in extra else None,
            dp=rest.pop(0) if "dp" in extra else None, k=k, bn=BN,
            element_stats="dp" in extra, interpret=False)

    assert "tpu_custom_call" in _compiled_text(search, *args)


@pytest.mark.parametrize("with_cap", [False, True])
def test_block_bounds_compiles_for_v5e(with_cap, shape, no_persistent_cache):
    nb = 8635                          # 2.2M rows in 256-row tiles
    args = [shape(M, P), shape(nb, P), shape(nb, P)]
    if with_cap:
        args.append(shape(M, nb))
    text = _compiled_text(
        lambda *a: bound_prune.block_bounds(*a, interpret=False), *args)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("k", [10, 100])
def test_gathered_topk_compiles_for_v5e(k, shape, no_persistent_cache):
    """The tree backend's kernel leaf stage: surviving 256-row blocks
    gathered and searched by the same kernel."""
    n_pad, n_keep = NT * BN, 64
    nb = NT
    index = BlockIndex(shape(n_pad, D), shape(n_pad, P), shape(P, D),
                       shape(nb, P), shape(nb, P),
                       shape(n_pad, dtype=jnp.bool_),
                       shape(n_pad, dtype=jnp.int32))

    def leaves(index, keep, qn, qp, tau):
        return leaf_gather.gathered_topk(index, keep, qn, qp, tau,
                                         n_keep=n_keep, k=k, interpret=False)

    text = _compiled_text(leaves, index, shape(n_keep, dtype=jnp.int32),
                          shape(M, D), shape(M, P), shape(M))
    assert "tpu_custom_call" in text


def _db_consumers(hlo: str, db_shape: str) -> list[tuple[str, str, str]]:
    """``(name, opcode, result type)`` of every instruction of the entry
    computation that reads the ``db_shape`` parameter, through bitcasts
    (which are followed, not listed)."""
    entry = hlo[hlo.index("\nENTRY "):]
    inst = re.compile(r"^\s*(?:ROOT )?(%[\w.\-]+) = (.+?) ([\w\-]+)\((.*)$")
    lines = [m.groups() for m in map(inst.match, entry.splitlines()) if m]
    names = {n for n, ty, op, _ in lines
             if op == "parameter" and ty.startswith(db_shape + "{")}
    assert len(names) == 1, names
    out = []
    for n, ty, op, rest in lines:
        args = set(re.findall(r"%[\w.\-]+", rest.split(")")[0]))
        if args & names:
            if op == "bitcast":
                names.add(n)
            else:
                out.append((n, op, ty))
    return out


@pytest.fixture(scope="module")
def fused_search(one_chip):
    """``compiled(m, d)``: the engine's one-dispatch kernel search, as the
    engine builds it with its defaults, compiled for ``m`` queries over
    2^20 rows of width ``d`` (once per shape, for every test of the file).
    """
    done = {}

    def compiled(m, d):
        if (m, d) not in done:
            def shape(*dims, dtype=jnp.float32):
                return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)
            n, p, bs, k = 1 << 20, P, 128, 10
            eng = types.SimpleNamespace(
                _note_trace=lambda: None, bm=cosine_topk.DEFAULT_BM, bn=BN,
                sort_queries=True, warm_start=True, best_first=True,
                margin=4e-7, interpret=False, warm_start_blocks=None,
                n_pivots=0)
            fused = get_backend("kernel").make_fused(
                eng, k, prune=True, element_stats=False, donate=False)
            nb = n // bs
            index = BlockIndex(shape(n, d), shape(n, p), shape(p, d),
                               shape(nb, p), shape(nb, p),
                               shape(n, dtype=jnp.bool_),
                               shape(n, dtype=jnp.int32))
            done[m, d] = fused.lower(index, shape(m, d)).compile()
        return done[m, d]
    return compiled


@pytest.mark.parametrize("d", [96, 768])
def test_fused_kernel_search_reads_the_stored_corpus(d, fused_search,
                                                     no_persistent_cache):
    """The engine's one-dispatch kernel search (query prep, bound, τ
    prescan, best-first order, ``pruned_topk``, id map), as the engine
    builds it with its defaults, lays no part of the corpus out again:
    the database parameter feeds bitcasts and the kernels' custom calls,
    and nothing corpus-sized besides.  At d=96 the runtime stores
    ``[N, 96]`` column-major, so the kernel and the prescan read ``db.T``.
    At d=768 the stored row-major corpus feeds the kernel directly, and
    the XLA prescan gathers ``m`` 256-row tiles (``[m, 256, d]``); with
    2^20 rows that is an eighth of the corpus at m=256, so no temporary
    reaches it."""
    n = 1 << 20
    compiled = fused_search(M, d)
    db_bytes = n * d * 4
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < db_bytes // 8, (temp, db_bytes)
    consumers = _db_consumers(compiled.as_text(), f"f32[{n},{d}]")
    kernels = [c for c in consumers if c[1] == "custom-call"]
    assert len(kernels) == (1 if d % 128 == 0 else 2)    # + the prescan
    for name, op, ty in consumers:
        assert op in ("custom-call", "fusion"), (name, op, ty)
        if op == "fusion":
            dims = re.match(r"f32\[([\d,]+)\]", ty).group(1).split(",")
            assert math.prod(map(int, dims)) * 4 <= db_bytes // 8, (name, ty)


@pytest.mark.parametrize("m,d", [(64, 768), (256, 768), (256, 96)])
def test_fused_kernel_search_sizes_the_query_tile_to_the_batch(
        m, d, fused_search, no_persistent_cache):
    """``bm`` (128) is the largest query tile: a served 64-query microbatch
    reaches ``pruned_topk`` as ``f32[64, d]`` and runs one 64-row tile,
    with nothing padding it to 128 rows; a 256-query batch runs two
    128-row tiles, at d=768 (rows) and d=96 (read as ``db.T``).  The
    kernel's results say the grid: the top-k block is ``[tiles x rows,
    128]`` and the ``computed`` counter ``[tiles, 4096]``."""
    tiles, rows = -(-m // cosine_topk.DEFAULT_BM), min(m, 128)
    text = fused_search(m, d).as_text()
    (call,) = re.findall(r"%pruned_topk[\w.]* = \((.*?)\) custom-call\("
                         r".*?operand_layout_constraints=\{(.*?)\}, \w+=", text)
    results = re.findall(r"[fs]32\[[\d,]*\]", call[0])
    operands = re.findall(r"[fs]32\[[\d,]*\]", call[1])
    # block_order, m_valid, τ seeds, then the queries
    assert operands[:4] == [f"s32[{tiles},4096]", "s32[1]",
                            f"f32[{tiles * rows},1]", f"f32[{m},{d}]"]
    assert results == [f"f32[{tiles * rows},128]", f"s32[{tiles * rows},128]",
                       f"s32[{tiles},4096]", f"s32[{tiles}]"]
    assert f"f32[{cosine_topk.DEFAULT_BM},{d}]" not in text or m > 128


@pytest.fixture(scope="module")
def four_chips(topo):
    from jax.sharding import Mesh
    return Mesh(np.array(topo.devices).reshape(-1), ("data",))


def test_four_chip_search_runs_the_kernel_in_every_shard(four_chips,
                                                         no_persistent_cache):
    """The sharded engine's one jitted callee over a 2x2 host at the MS
    MARCO shard shape (2,210,456 rows a chip, padded to 128-row blocks):
    every chip runs ``pruned_topk`` over its own shard and the answers
    meet in one all-gather.  No part of the shard is copied per call (the
    scan inner kept a best-first permutation of the whole shard): the
    temporaries stay under an eighth of the shard, and the shard feeds
    only bitcasts, the kernel and fusions at most that size."""
    from jax.sharding import NamedSharding, PartitionSpec

    from repro.core.distributed import make_sharded_search
    s, n, d, k = four_chips.devices.size, 17_270 * 128, D, 10
    stacked = NamedSharding(four_chips, PartitionSpec("data"))

    def leaf(*dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct((s,) + dims, dtype, sharding=stacked)

    nb = n // 128
    index = BlockIndex(leaf(n, d), leaf(n, P), leaf(P, d), leaf(nb, P),
                       leaf(nb, P), leaf(n, dtype=jnp.bool_),
                       leaf(n, dtype=jnp.int32), leaf(P, d), leaf(n, P),
                       leaf(n, P))
    queries = jax.ShapeDtypeStruct(
        (M, d), jnp.float32,
        sharding=NamedSharding(four_chips, PartitionSpec()))
    fn = make_sharded_search(
        four_chips, with_stats=True, inner="kernel", warm_start=True,
        best_first=True, n_pivots=0, bm=cosine_topk.DEFAULT_BM, bn=BN,
        interpret=False)
    compiled = fn.lower(index, queries, k).compile()
    text = compiled.as_text()
    assert len(re.findall(r'custom_call_target="tpu_custom_call"', text)) == 1
    assert re.search(r"pruned_topk", text)
    assert len(re.findall(r"= \S+ all-gather(?:-start)?\(", text)) == 1
    shard_bytes = n * d * 4
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < shard_bytes // 8, (temp, shard_bytes)
    consumers = _db_consumers(text, f"f32[1,{n},{d}]")
    assert [c[1] for c in consumers].count("custom-call") == 1, consumers
    for name, op, ty in consumers:
        assert op in ("custom-call", "fusion"), (name, op, ty)
        if op == "fusion":
            dims = re.match(r"f32\[([\d,]+)\]", ty).group(1).split(",")
            assert math.prod(map(int, dims)) * 4 <= shard_bytes // 8, (
                name, ty)
