"""Share of the chips' busy time in the traced window spent in the
``pruned_topk`` kernel (%), over a closed loop of batches."""


def read(run):
    if run.trace is None or run.traffic.get("loop") != "closed":
        return None
    kernel = sum(s for name, s in run.trace["device_ops"]
                 if name.startswith("pruned_topk"))
    busy = sum(run.trace["busy_s"])
    if not kernel or not busy:
        return None
    return 100.0 * kernel / busy
