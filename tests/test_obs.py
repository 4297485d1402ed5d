"""repro.obs: spans record only under a profiler session, nest with their
parent and ids, land on the trace's host plane, and the engine, the front
end and the kernel feed them."""
import asyncio
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro import obs
from repro.search import SearchEngine
from repro.serve.frontend import ContinuousBatcher
from tests.conftest import clustered


@pytest.fixture(autouse=True)
def fresh():
    obs.reset()
    yield
    obs.reset()


def _engine(rng, n=256, d=16, **kw):
    return SearchEngine.build(clustered(rng, n, d), n_pivots=4,
                              block_size=32, **kw)


async def _traffic(batcher, queries, gap_s):
    """Queries submitted ``gap_s`` apart; every answer awaited."""
    tasks = []
    for q in queries:
        tasks.append(asyncio.ensure_future(batcher.submit(q)))
        await asyncio.sleep(gap_s)
    out = await asyncio.wait_for(asyncio.gather(*tasks), timeout=60)
    await asyncio.wait_for(batcher.close(), timeout=60)
    return out


def _serve(eng, queries, *, max_batch=4, max_wait_ms=5.0, gap_s=0.002):
    batcher = ContinuousBatcher(eng, k=3, max_batch=max_batch,
                                max_wait_ms=max_wait_ms)
    return batcher, asyncio.run(_traffic(batcher, queries, gap_s))


def test_no_records_outside_a_profiler_session(rng):
    eng = _engine(rng)
    db = np.asarray(eng.index.db[:8])
    jax.block_until_ready(eng.search(jnp.asarray(db), 5)[:2])
    batcher, out = _serve(eng, db)
    assert len(out) == 8 and batcher.n_queries == 8
    assert not obs.enabled()
    assert obs.records() == []


def test_nested_spans_record_their_parent_and_ids(tmp_path):
    with jax.profiler.trace(str(tmp_path)):
        with obs.span("outer", batch=7) as sp:
            with obs.span("inner", k=3):
                obs.record("stamped", 1, 2, extra=1)
            sp.note(late="yes")
    (outer,) = obs.records("outer")
    (inner,) = obs.records("inner")
    (stamped,) = obs.records("stamped")
    assert outer.parent is None and outer.ids == {"batch": 7, "late": "yes"}
    assert inner.parent == "outer" and inner.ids == {"batch": 7, "k": 3}
    assert stamped.parent == "inner"
    assert stamped.ids == {"batch": 7, "k": 3, "extra": 1}
    assert outer.start_ns <= inner.start_ns <= inner.end_ns <= outer.end_ns


def test_spans_land_by_name_on_the_host_plane_of_the_trace(rng, tmp_path):
    eng = _engine(rng)
    q = jnp.asarray(np.asarray(eng.index.db[:4]))
    jax.block_until_ready(eng.search(q, 5)[:2])        # warm: no trace
    with jax.profiler.trace(str(tmp_path)):
        with obs.span("test.outer", batch=1):
            jax.block_until_ready(eng.search(q, 5)[:2])
    (path,) = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                        recursive=True)
    host = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host"):
            for line in plane.lines:
                for ev in line.events:
                    host.setdefault(ev.name, dict(ev.stats))
    assert {"test.outer", "engine.search", "engine.dispatch"} <= set(host)
    assert host["test.outer"]["batch"] == 1
    assert host["engine.search"]["k"] == 5
    (search,) = obs.records("engine.search")
    assert search.parent == "test.outer"
    assert search.ids["retraced"] == 0 and search.ids["batch"] == 1
    assert search.ids["stats"].n_queries == 4


@pytest.mark.parametrize("backend,d,want", [("kernel", 16, "cols"),
                                             ("kernel", 128, "rows"),
                                             ("scan", 16, None)])
def test_engine_search_names_the_kernels_db_layout(backend, d, want, rng,
                                                   tmp_path):
    """The kernel backend reads a corpus whose width is not a multiple of
    128 as ``db.T``; ``engine.search`` says which orientation ran, on its
    profiler event and its record.  Other backends give no ``db_layout``."""
    eng = _engine(rng, d=d, backend=backend)
    q = jnp.asarray(np.asarray(eng.index.db[:4]))
    jax.block_until_ready(eng.search(q, 5)[:2])        # warm: no trace
    with jax.profiler.trace(str(tmp_path)):
        jax.block_until_ready(eng.search(q, 5)[:2])
    (search,) = obs.records("engine.search")
    assert search.ids.get("db_layout") == want
    (path,) = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                        recursive=True)
    stats = [dict(ev.stats) for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host") for line in plane.lines
             for ev in line.events if ev.name == "engine.search"]
    assert [st.get("db_layout") for st in stats] == [want]


@pytest.mark.parametrize("backend,m,want", [("kernel", 4, 8),
                                             ("kernel", 64, 64),
                                             ("kernel", 130, 72),
                                             ("scan", 64, None)])
def test_engine_search_names_the_kernels_query_tile(backend, m, want, rng,
                                                    tmp_path):
    """``engine.search`` carries ``bm``, the height of the query tile the
    kernel ran for the call's ``m`` rows: at the engine's ``bm`` of 128, one
    8-row tile for 4 queries, one 64-row tile for 64, two 72-row tiles for
    130.  Other backends give no ``bm``."""
    eng = _engine(rng, backend=backend)
    q = jnp.asarray(np.asarray(eng.index.db[:m]))
    with jax.profiler.trace(str(tmp_path)):
        jax.block_until_ready(eng.search(q, 5)[:2])
    (search,) = obs.records("engine.search")
    assert search.ids["m"] == m
    assert search.ids.get("bm") == want


def test_engine_traces_counts_one_trace_per_new_shape(rng):
    eng = _engine(rng)
    q = jnp.asarray(np.asarray(eng.index.db[:6]))
    before = obs.counters().get("engine.traces", 0)
    _, _, st = eng.search(q, 4)
    assert obs.counters()["engine.traces"] - before == 1 == st.retraces
    _, _, st = eng.search(q, 4)
    assert obs.counters()["engine.traces"] - before == 1
    assert st.retraces == 0


def test_a_traced_batcher_run_ties_each_query_to_its_microbatch(rng,
                                                                 tmp_path):
    eng = _engine(rng, backend="scan")
    db = np.asarray(eng.index.db[:24])
    _serve(eng, db[:4])                                 # warm the shape
    with jax.profiler.trace(str(tmp_path)):
        batcher, out = _serve(eng, db, gap_s=0.003)
    assert len(out) == 24
    device = {r.ids["batch"]: r for r in obs.records("frontend.device")}
    assert len(device) == batcher.n_batches > 1
    waits = obs.records("frontend.queue_wait")
    assert len(waits) == 24
    assert all(w.ids["batch"] in device for w in waits)
    assert all(w.end_ns <= device[w.ids["batch"]].end_ns for w in waits)
    coalesce = {r.ids["batch"]: r for r in obs.records("frontend.coalesce")}
    holds = obs.records("frontend.hold")
    assert holds
    for h in holds:
        c = coalesce[h.ids["batch"]]
        assert h.parent == "frontend.coalesce"
        assert c.start_ns <= h.start_ns <= h.end_ns <= c.end_ns
    for name in ("engine.search", "frontend.fetch", "frontend.hop"):
        recs = obs.records(name)
        assert len(recs) == len(device), name
        for r in recs:
            d = device[r.ids["batch"]]
            assert r.parent in ("frontend.device", "engine.search")
            assert d.start_ns <= r.start_ns <= r.end_ns <= d.end_ns, name
    assert len(obs.records("frontend.pad")) == len(device)
    assert len(obs.records("frontend.resolve")) == len(device)
