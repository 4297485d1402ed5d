"""Median, over the window's queries, of the time from ``submit`` putting a
query on the front end's queue to its microbatch being handed to the
device thread (the ``frontend.queue_wait`` spans, ms)."""
import statistics

import spans


def read(run):
    if (run.traffic.get("loop") != "open"
            or spans.per_call(run, "frontend.device") is None):
        return None
    waits = spans.records("frontend.queue_wait")
    if waits is None:
        return None
    return statistics.median(spans.ms(r) for r in waits)
