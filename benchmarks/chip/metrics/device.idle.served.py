"""Share of the traced window in which the chip ran no operation, under
open-loop single queries through the front end (%)."""


def read(run):
    if run.trace is None or run.traffic.get("loop") != "open":
        return None
    busy = sum(run.trace["busy_s"]) / run.chips
    return 100.0 * (1.0 - busy / run.trace["window_s"])
