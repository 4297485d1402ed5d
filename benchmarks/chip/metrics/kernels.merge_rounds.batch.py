"""``SearchStats.merge_rounds``: rounds of the ``pruned_topk`` kernel's
top-k merge per computed (query tile, db tile) pair, averaged over the
window's calls."""
import statistics

import jax

import spans


def read(run):
    if run.traffic.get("loop") != "closed":
        return None
    calls = spans.per_call(run, "engine.search")
    if calls is None:
        return None
    rounds = [getattr(r.ids.get("stats"), "merge_rounds", None)
              for r in calls]
    if any(v is None for v in rounds):
        return None
    return statistics.fmean(float(v) for v in jax.device_get(rounds))
