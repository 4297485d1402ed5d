"""``ContinuousBatcher`` occupancy over the window: real queries over the
padded microbatch slots dispatched."""


def read(run):
    if run.traffic.get("loop") != "open":
        return None
    return getattr(run.out, "occupancy", None)
