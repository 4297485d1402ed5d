"""The least time a search call's needed work takes on the chip, over the
chip's busy time per call in the traced window (%).

The work comes from shapes and the unpruned share of rows
(``roofline.search_work``), never from a kernel's grid; busy time is the
union of every device operation the calls dispatched, averaged over the
chips, each of which searches its own share of the rows.
"""
import statistics

import roofline


def read(run):
    if (run.trace is None or run.traffic.get("loop") != "closed"
            or not run.out.calls):
        return None
    cfg, traffic = run.config, run.traffic
    flops, nbytes = roofline.search_work(
        m=int(traffic["batch"]), d=int(cfg["dim"]), k=int(traffic["k"]),
        rows=int(cfg["rows"]) / int(cfg["shards"]),
        prune_frac=statistics.fmean(run.out.prune_fracs))
    least, _ = roofline.least_time(flops, nbytes, run.peaks)
    busy_per_call = sum(run.trace["busy_s"]) / run.chips / run.out.calls
    return 100.0 * least / busy_per_call
