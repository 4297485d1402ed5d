"""Continuous-batching front end for a live search engine.

Serving traffic arrives one query at a time, but every layer below —
the fused dispatch cache, the Pallas kernel grid, the τ prescan — is
built for batches: a [1, d] search wastes the whole query-tile axis and
pays a full dispatch per request.  :class:`ContinuousBatcher` closes the
gap with the standard continuous-batching loop: concurrent
:meth:`submit` calls land in a queue, a single worker coalesces them
into microbatches bounded by ``max_batch`` (amortization ceiling) and
``max_wait_ms`` (latency floor), runs **one** engine search per
microbatch, and resolves each caller's future with its own row of the
result.

Microbatches are zero-padded to exactly ``max_batch`` rows before the
search, so every dispatch reuses one fused-cache signature
(``SearchStats.retraces == 0`` after the first batch) no matter how many
requests happened to coalesce.  Padding rows cost compute but never
correctness — their results are sliced off before futures resolve.

The engine itself is not thread-safe against concurrent mutation, so the
worker serializes all device work through a single executor thread;
online inserts/deletes (:meth:`SearchEngine.online`) interleave safely
*between* microbatches by going through :meth:`run`, the same
single-thread funnel.

While a profiler session records, each microbatch's steps are
:mod:`repro.obs` spans carrying its ``batch`` sequence number
(``frontend.coalesce`` with its ``frontend.hold`` waits,
``frontend.pad``, ``frontend.device`` around the device thread's
``engine.search`` and ``frontend.fetch``, ``frontend.hop``,
``frontend.resolve``), and each query's time in the queue is a
``frontend.queue_wait`` record; docs/search-api.md "Tracing" lists them.
"""
from __future__ import annotations

import asyncio
import contextvars
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from repro import obs

__all__ = ["ContinuousBatcher"]


class ContinuousBatcher:
    """Coalesce concurrent single-query searches into engine microbatches.

    Args:
      engine: a :class:`repro.search.SearchEngine` (any single-host
        backend).
      k: top-k depth every submitted query is answered with (one k keeps
        one fused-cache signature).
      max_batch: microbatch width; also the padded batch shape every
        dispatch uses.
      max_wait_ms: how long the worker holds an underfull microbatch open
        for stragglers after the first query arrives.

    Use as an async context manager, or call :meth:`close` explicitly.
    """

    def __init__(self, engine, k: int, *, max_batch: int = 32,
                 max_wait_ms: float = 2.0):
        if max_batch < 1:
            raise ValueError("max_batch must be >= 1")
        self.engine = engine
        self.k = int(k)
        self.max_batch = int(max_batch)
        self.max_wait = float(max_wait_ms) / 1e3
        #: microbatches dispatched / queries served (occupancy telemetry)
        self.n_batches = 0
        self.n_queries = 0
        self._seq = 0                   # microbatch id on the trace
        self._queue: asyncio.Queue = asyncio.Queue()
        self._pool = ThreadPoolExecutor(max_workers=1)
        self._worker: asyncio.Task | None = None
        self._loop: asyncio.AbstractEventLoop | None = None
        self._closed = False

    # ------------------------------------------------------------- metrics
    @property
    def occupancy(self) -> float:
        """Mean fraction of each dispatched microbatch that was real
        queries (1.0 = every batch full)."""
        if self.n_batches == 0:
            return 0.0
        return self.n_queries / (self.n_batches * self.max_batch)

    # ----------------------------------------------------------- lifecycle
    async def __aenter__(self) -> "ContinuousBatcher":
        return self

    async def __aexit__(self, *exc) -> None:
        await self.close()

    async def close(self) -> None:
        """Stop the worker after the queue drains; reject new submits."""
        if self._closed:
            return
        self._closed = True
        if self._worker is not None:
            if self._loop is asyncio.get_running_loop():
                await self._queue.join()
                self._worker.cancel()
                try:
                    await self._worker
                except asyncio.CancelledError:
                    pass
            # else: the worker's loop already died (sequential asyncio.run
            # reuse) and took the task with it — nothing left to drain
            self._worker = None
        self._pool.shutdown(wait=True)

    # ------------------------------------------------------------- serving
    async def submit(self, query):
        """Search one query ``[d]``; returns ``(sims [k], ids [k])`` as
        numpy arrays once its microbatch has run."""
        if self._closed:
            raise RuntimeError("batcher is closed")
        q = np.asarray(query, np.float32)
        if q.ndim != 1:
            raise ValueError(f"submit takes one query [d], got {q.shape}")
        loop = asyncio.get_running_loop()
        if self._worker is not None and self._loop is not loop:
            # the worker belongs to another event loop.  If that loop is
            # still running this is genuine cross-loop use — refuse loudly.
            # Otherwise the loop died (the common sequential-asyncio.run
            # reuse): the old worker task and its queue are dead, and a
            # submit enqueued onto them would hang forever — re-create
            # both on the caller's loop (the executor thread is
            # loop-agnostic and keeps the engine serialized throughout).
            if self._loop is not None and self._loop.is_running():
                raise RuntimeError(
                    "batcher is already serving another running event "
                    "loop; one ContinuousBatcher binds to one loop at a "
                    "time")
            self._worker = None
            self._queue = asyncio.Queue()
        if self._worker is None:
            self._loop = loop
            self._worker = loop.create_task(self._run_worker())
        fut = loop.create_future()
        self._queue.put_nowait(
            (q, fut, time.perf_counter_ns() if obs.enabled() else 0))
        return await fut

    async def run(self, fn, *args):
        """Run ``fn(*args)`` on the batcher's device thread, serialized
        against search dispatches — the safe slot for online mutations
        (``engine.online().insert(...)``) while traffic is live."""
        loop = asyncio.get_running_loop()
        return await loop.run_in_executor(self._pool, fn, *args)

    # -------------------------------------------------------------- worker
    async def _run_worker(self) -> None:
        loop = asyncio.get_running_loop()
        while True:
            first = await self._queue.get()
            seq = self._seq
            self._seq += 1
            on = obs.enabled()
            with obs.span("frontend.coalesce", batch=seq):
                batch = [first]
                deadline = loop.time() + self.max_wait
                while len(batch) < self.max_batch:
                    timeout = deadline - loop.time()
                    if timeout <= 0 and self._queue.empty():
                        break
                    hold = (obs.span("frontend.hold", batch=seq)
                            if on and self._queue.empty() else obs.OFF)
                    try:
                        with hold:
                            batch.append(await asyncio.wait_for(
                                self._queue.get(), max(timeout, 0.0)))
                    except asyncio.TimeoutError:
                        break
            b = len(batch)
            with obs.span("frontend.pad", batch=seq):
                q = np.zeros((self.max_batch, batch[0][0].shape[0]),
                             np.float32)
                for i, (qi, _, _) in enumerate(batch):
                    q[i] = qi
            try:
                with obs.span("frontend.device", batch=seq):
                    call = (self._search, q, on)
                    if on:
                        handed = time.perf_counter_ns()
                        # the device thread's spans sit in this one
                        call = (contextvars.copy_context().run,) + call
                    running = loop.run_in_executor(self._pool, *call)
                    if on:
                        # recorded while the device thread works
                        for _, _, queued in batch:
                            if queued:
                                obs.record("frontend.queue_wait", queued,
                                           handed, batch=seq)
                    sims, ids, _stats, back = await running
                    if on:
                        obs.record("frontend.hop", back,
                                   time.perf_counter_ns())
                with obs.span("frontend.resolve", batch=seq):
                    self.n_batches += 1
                    self.n_queries += b
                    for i, (_, fut, _) in enumerate(batch):
                        if not fut.done():
                            fut.set_result((sims[i], ids[i]))
            except Exception as e:                    # noqa: BLE001
                for _, fut, _ in batch:
                    if not fut.done():
                        fut.set_exception(e)
            finally:
                for _ in batch:
                    self._queue.task_done()

    def _search(self, q: np.ndarray, on: bool):
        """The device thread's part of a microbatch: the search and the
        host copy of its answers, and (while tracing) when it returned."""
        sims, ids, stats = self.engine.search(q, self.k)
        with obs.span("frontend.fetch"):
            sims, ids = np.asarray(sims), np.asarray(ids)
        return sims, ids, stats, time.perf_counter_ns() if on else 0
