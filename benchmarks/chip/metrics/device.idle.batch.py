"""Share of the traced window in which the chips ran no operation, over a
closed loop of batches (%); averaged over the chips the cell uses."""


def read(run):
    if run.trace is None or run.traffic.get("loop") != "closed":
        return None
    busy = sum(run.trace["busy_s"]) / run.chips
    return 100.0 * (1.0 - busy / run.trace["window_s"])
