"""Estimator: mean host time inside one step dispatch in the window, from the
registry's ``train_step_seconds``.  It times the enqueue, not the step: a few
milliseconds where the host is the limit, and where the device is the limit
also the wait for a free slot in the runtime's queue of steps in flight."""


def read(run):
    hists = run["window"]["registry"]["histograms"]
    rows = [h for name, h in hists.items()
            if name.startswith("train_step_seconds")]
    count = sum(h["count"] for h in rows)
    if not count:
        return None
    return 1e3 * sum(h["total"] for h in rows) / count
