"""The 95th percentile, over every query of the window, of the time from
when a query was due to when its answer reached the client (ms), read
from the open loop's own host clock."""


def read(run):
    return run.out.values.get("query_p95_ms")
