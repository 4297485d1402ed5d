"""Spread of the chips' busy time in the traced window, (max - min) over
max (%), over a closed loop of batches on a corpus split over chips: the
slowest shard sets every call, so this is what the stragglers cost."""


def read(run):
    if (run.trace is None or run.traffic.get("loop") != "closed"
            or run.chips < 2):
        return None
    busy = run.trace["busy_s"]
    if len(busy) < 2 or not max(busy):
        return None
    return 100.0 * (max(busy) - min(busy)) / max(busy)
