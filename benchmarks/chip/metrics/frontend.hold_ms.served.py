"""Time the front end held a microbatch open for stragglers with its queue
empty (the ``frontend.hold`` spans), per microbatch of the window (ms)."""
import spans


def read(run):
    if run.traffic.get("loop") != "open":
        return None
    batches = spans.per_call(run, "frontend.coalesce")
    if batches is None:
        return None
    held = sum(spans.ms(r) for r in spans.records("frontend.hold") or ())
    return held / len(batches)
