"""Host time of one ``SearchEngine.search`` call, from entry to return with
its lazy answers (the ``engine.search`` span), averaged over the window's
calls of a closed loop (ms)."""
import statistics

import spans


def read(run):
    if run.traffic.get("loop") != "closed":
        return None
    calls = spans.per_call(run, "engine.search")
    if calls is None:
        return None
    return statistics.fmean(spans.ms(r) for r in calls)
