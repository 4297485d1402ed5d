"""Share of each chip's busy time in the traced window spent in
collective operations (the merge's all-gather), the largest over the
chips (%), over a closed loop of batches on a corpus split over chips."""


def read(run):
    if (run.trace is None or run.traffic.get("loop") != "closed"
            or run.chips < 2):
        return None
    shares = [100.0 * c / b for c, b in zip(run.trace["collective_s"],
                                             run.trace["busy_s"]) if b]
    return max(shares) if shares else None
