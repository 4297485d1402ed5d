"""``SearchStats.block_prune_frac``, the share of (query tile, row block)
pairs the bound proved unneeded, averaged over the window's calls."""
import statistics


def read(run):
    fracs = getattr(run.out, "prune_fracs", None)
    if run.traffic.get("loop") != "closed" or not fracs:
        return None
    return statistics.fmean(fracs)
