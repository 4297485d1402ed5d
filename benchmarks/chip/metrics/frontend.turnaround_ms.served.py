"""Host time from one microbatch's answers reaching the host (end of its
``frontend.fetch``) to the next microbatch's search being dispatched (end
of its ``engine.dispatch``), averaged over consecutive microbatches of
the window (ms): the front end's hop back, resolve, coalescing, hold and
padding, and the engine's dispatch, between two device calls."""
import statistics

import spans


def read(run):
    if (run.traffic.get("loop") != "open"
            or spans.per_call(run, "frontend.device") is None):
        return None
    fetched = {r.ids.get("batch"): r.end_ns
               for r in spans.records("frontend.fetch") or ()}
    sent = {r.ids.get("batch"): r.end_ns
            for r in spans.records("engine.dispatch") or ()}
    gaps = [sent[b + 1] - t for b, t in fetched.items()
            if b is not None and b + 1 in sent]
    if not gaps:
        return None
    return statistics.fmean(gaps) / 1e6
