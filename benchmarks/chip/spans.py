"""The program's own spans over the traced window, for the metric readers.

The program records a span (``repro.obs``) only while a profiler session
runs, and the harness runs one around the window alone, so every record
the process holds after a traced run is the window's.  Each function
returns ``None`` where the program records nothing: a commit without
``repro.obs``, or a run with no profiler session.
"""
from __future__ import annotations

import importlib


def records(name: str):
    """The program's records named ``name``, oldest first; ``None`` where it
    keeps no such record or has no ``repro.obs``."""
    try:
        obs = importlib.import_module("repro.obs")
    except ImportError:
        return None
    return obs.records(name) or None


def per_call(run, name: str):
    """The records named ``name``, where there is one for each of the
    window's calls (``run.out.calls``), else ``None``."""
    found = records(name)
    if found is None or len(found) != run.out.calls:
        return None
    return found


def ms(rec) -> float:
    """A record's duration in ms."""
    return (rec.end_ns - rec.start_ns) / 1e6
