"""The readers of the program's own spans and counters on a hand-built run:
each reads its number from the window's records, and returns None where
the program has no ``repro.obs``, holds no records, or holds a count of
calls other than the window's."""
import sys
from types import SimpleNamespace

import jax.numpy as jnp
import pytest

import run
from repro import obs
from repro.search import SearchStats

MS = 1_000_000                      # ns


@pytest.fixture(autouse=True)
def fresh():
    obs.reset()
    yield
    obs.reset()


def keep(name, start_ms, end_ms, **ids):
    obs.record(name, int(start_ms * MS), int(end_ms * MS), **ids)


def closed(calls, device_ops=(("pruned_topk.1", 0.9), ("fusion.3", 0.05))):
    return SimpleNamespace(
        traffic={"loop": "closed"}, out=SimpleNamespace(calls=calls),
        trace={"busy_s": [1.0], "device_ops": [list(o) for o in device_ops],
               "window_s": 1.1}, chips=1)


def served(calls):
    return SimpleNamespace(traffic={"loop": "open"},
                           out=SimpleNamespace(calls=calls), trace=None,
                           chips=1)


def stats(rounds):
    return SearchStats(backend="kernel", n_queries=256, k=10, n_blocks=8,
                       merge_rounds=jnp.float32(rounds))


def three_calls():
    for i, (ms, retraced, rounds) in enumerate(
            [(0.5, 0, 1.5), (0.7, 1, 2.5), (0.6, 0, 2.0)]):
        keep("engine.search", 10 * i, 10 * i + ms, backend="kernel",
             retraced=retraced, stats=stats(rounds))


def two_batches():
    """Microbatches 4 and 5 of a served window."""
    for b, t in ((4, 0.0), (5, 30.0)):
        keep("frontend.coalesce", t, t + 3, batch=b)
        keep("frontend.hold", t + 1, t + 2.5, batch=b)
        keep("frontend.device", t + 4, t + 25, batch=b)
        keep("engine.dispatch", t + 5, t + 6, batch=b)
        keep("frontend.fetch", t + 6, t + 24, batch=b)
    for b, wait in ((4, 2.0), (4, 6.0), (5, 1.0), (5, 3.0), (5, 20.0)):
        keep("frontend.queue_wait", 0, wait, batch=b)


def test_batch_readers_read_the_window_calls():
    three_calls()
    run_ = closed(3)
    assert run.read_metric("engine.host_ms.batch", run_) == pytest.approx(0.6)
    assert run.read_metric("engine.retraces.batch", run_) == 1
    assert run.read_metric("kernels.merge_rounds.batch",
                           run_) == pytest.approx(2.0)
    assert run.read_metric("kernels.pruned_topk_share.batch",
                           run_) == pytest.approx(90.0)


def test_served_readers_read_the_window_microbatches():
    two_batches()
    run_ = served(2)
    assert run.read_metric("frontend.queue_wait_ms.served",
                           run_) == pytest.approx(3.0)
    # fetch of 4 ends at 24 ms, dispatch of 5 ends at 36 ms
    assert run.read_metric("frontend.turnaround_ms.served",
                           run_) == pytest.approx(12.0)
    assert run.read_metric("frontend.hold_ms.served",
                           run_) == pytest.approx(1.5)


PROGRAM = ["engine.host_ms.batch", "engine.retraces.batch",
           "kernels.merge_rounds.batch"]
SERVED = ["frontend.queue_wait_ms.served", "frontend.turnaround_ms.served",
          "frontend.hold_ms.served"]


@pytest.mark.parametrize("name", PROGRAM + SERVED)
def test_no_records_read_none(name):
    assert run.read_metric(name, closed(3) if name in PROGRAM
                           else served(2)) is None


@pytest.mark.parametrize("name", PROGRAM + SERVED)
def test_a_count_other_than_the_window_calls_reads_none(name):
    three_calls()
    two_batches()
    assert run.read_metric(name, closed(4) if name in PROGRAM
                           else served(3)) is None


@pytest.mark.parametrize("name", PROGRAM + SERVED)
def test_a_program_without_repro_obs_reads_none(name, monkeypatch):
    three_calls()
    two_batches()
    monkeypatch.setitem(sys.modules, "repro.obs", None)
    assert run.read_metric(name, closed(3) if name in PROGRAM
                           else served(2)) is None


def test_merge_rounds_read_none_where_the_backend_has_no_kernel():
    for i in range(2):
        keep("engine.search", i, i + 1, retraced=0,
             stats=SearchStats(backend="scan", n_queries=256, k=10,
                               n_blocks=8))
    assert run.read_metric("kernels.merge_rounds.batch", closed(2)) is None


@pytest.mark.parametrize("ops", [[], [["fusion.3", 0.5]]])
def test_kernel_share_reads_none_without_the_kernel_or_a_trace(ops):
    assert run.read_metric("kernels.pruned_topk_share.batch",
                           closed(3, ops)) is None
    no_trace = closed(3)
    no_trace.trace = None
    assert run.read_metric("kernels.pruned_topk_share.batch",
                           no_trace) is None


@pytest.mark.parametrize("name", PROGRAM + ["kernels.pruned_topk_share.batch"])
def test_batch_readers_read_nothing_in_a_served_run(name):
    three_calls()
    assert run.read_metric(name, served(3)) is None
