"""Jit traces the engine made inside the window (``engine.traces`` counter
increments, read per call as the ``engine.search`` span's ``retraced``):
0 when every call reused its compiled search."""
import spans


def read(run):
    if run.traffic.get("loop") != "closed":
        return None
    calls = spans.per_call(run, "engine.search")
    if calls is None:
        return None
    return sum(r.ids.get("retraced") or 0 for r in calls)
