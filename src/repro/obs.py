"""Spans and counters: the program's own record of where its host time goes.

A span is on exactly while a profiler session records
(``jax.profiler.trace`` / ``start_trace``).  Then each one is a
``jax.profiler.TraceAnnotation`` on the host plane of the same trace as
the device's ops, so both share the profiler's clock, and also a
:class:`Record` in a bounded in-memory buffer that :func:`records` reads.
With no session, :func:`span` returns one shared no-op object after a
single ``TraceAnnotation.is_enabled()`` check, records nothing, and adds
no host sync.

A record carries the name of the span it sat in (the enclosing span of
the same thread or asyncio task) and its ids over those of the enclosing
span, so the engine's spans under a front-end microbatch carry its
``batch``.  Ids given when a span opens also go on its profiler event;
ids added with ``note`` go on the record alone and may hold lazy device
values (a call's ``SearchStats``), which nothing reads before a reader
asks.

Counters (:func:`count`) are plain integers, always on.

    with obs.span("engine.search", backend="kernel", k=10) as sp:
        ...
        sp.note(stats=stats)
    obs.records("engine.search")     # [Record(name, start_ns, end_ns, ...)]
    obs.counters()["engine.traces"]

The span and counter names the program uses, and what reads each, are
listed in docs/search-api.md ("Tracing").
"""
from __future__ import annotations

import contextvars
import threading
import time
from collections import deque
from typing import NamedTuple

import jax

__all__ = ["OFF", "Record", "count", "counters", "enabled", "record",
           "records", "reset", "span"]

#: records kept; the oldest are dropped beyond it
MAX_RECORDS = 1 << 17

enabled = jax.profiler.TraceAnnotation.is_enabled


class Record(NamedTuple):
    name: str
    start_ns: int           # time.perf_counter_ns()
    end_ns: int
    parent: str | None      # the enclosing span's name
    ids: dict


_records: deque = deque(maxlen=MAX_RECORDS)
_counters: dict = {}
_count_lock = threading.Lock()
#: the innermost open span of this thread or asyncio task
_current: contextvars.ContextVar = contextvars.ContextVar(
    "repro_obs_span", default=None)


class _Off:
    """The span while no profiler session records."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def note(self, **ids):
        pass


#: the one no-op span, for a site that has already asked :func:`enabled`
OFF = _Off()


class _Span:
    __slots__ = ("name", "ids", "_parent", "_token", "_ann", "_t0")

    def __init__(self, name: str, ids: dict):
        self.name = name
        self.ids = ids

    def __enter__(self):
        self._ann = jax.profiler.TraceAnnotation(self.name, **self.ids)
        self._parent = _current.get()
        if self._parent is not None:
            self.ids = {**self._parent.ids, **self.ids}
        self._token = _current.set(self)
        self._ann.__enter__()
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter_ns()
        self._ann.__exit__(*exc)
        _current.reset(self._token)
        _records.append(Record(
            self.name, self._t0, t1,
            None if self._parent is None else self._parent.name, self.ids))
        return False

    def note(self, **ids):
        self.ids.update(ids)


def span(name: str, **ids):
    """A context manager timing its body as ``name``; a no-op unless a
    profiler session records."""
    return _Span(name, ids) if enabled() else OFF


def record(name: str, start_ns: int, end_ns: int, **ids) -> None:
    """Keep a span whose ends were stamped apart (``time.perf_counter_ns``),
    such as one that starts on one thread and ends on another.  It goes on
    the record alone, under the enclosing span; callers stamp only while
    :func:`enabled`."""
    parent = _current.get()
    if parent is not None:
        ids = {**parent.ids, **ids}
    _records.append(Record(name, start_ns, end_ns,
                           None if parent is None else parent.name, ids))


def records(name: str | None = None) -> list:
    """The kept records named ``name`` (all of them for ``None``), oldest
    first."""
    return [r for r in list(_records) if name is None or r.name == name]


def count(name: str, n: int = 1) -> None:
    """Add ``n`` to the counter ``name``."""
    with _count_lock:
        _counters[name] = _counters.get(name, 0) + n


def counters() -> dict:
    """A copy of every counter."""
    with _count_lock:
        return dict(_counters)


def reset() -> None:
    """Drop every record and counter."""
    _records.clear()
    with _count_lock:
        _counters.clear()
