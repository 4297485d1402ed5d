"""Peaks of each chip, and the least time a search's needed work takes.

The work is reckoned from shapes and from the share of rows the search
did not prune, never from a kernel's grid, so it reads the same whatever
implements the search: 2*m*d flops for every unpruned row, the unpruned
rows read once per call, the queries read and the answers written.  The
least time is the larger of the flops over the chip's peak rate and the
bytes over its memory bandwidth; a share of it over the measured busy
time cannot pass 100% unless the time leaves out part of the work.
"""
from __future__ import annotations

import json
import os

F32 = 4
#: bytes of one answer slot: an f32 similarity and an int32 id
SLOT = 8


def peak(device_kind: str) -> dict:
    """The peak table's entry for ``device_kind``; a chip missing from the
    table is an error, never a default."""
    with open(os.path.join(os.path.dirname(__file__), "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json (known: {sorted(table)})")
    return table[device_kind]


def search_work(*, m: int, d: int, k: int, rows: int,
                prune_frac: float) -> tuple[float, float]:
    """(flops, bytes) one chip needs for one search call of ``m`` queries
    over its ``rows`` rows, of which ``prune_frac`` were proved unneeded."""
    unpruned = rows * (1.0 - prune_frac)
    flops = 2.0 * m * d * unpruned
    nbytes = F32 * unpruned * d + F32 * m * d + SLOT * m * k
    return flops, nbytes


def least_time(flops: float, nbytes: float, peaks: dict) -> tuple[float, str]:
    """(seconds, the roof that binds: "flops" or "bytes")."""
    t_flops = flops / peaks["flops_per_s"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return (t_flops, "flops") if t_flops >= t_bytes else (t_bytes, "bytes")
