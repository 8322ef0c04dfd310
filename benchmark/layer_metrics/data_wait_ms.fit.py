"""Data tier: mean time the step loop waited on the prefetch queue for a batch
in the window, from the registry's ``data_stage_seconds{stage="wait"}``.  Near
the gap between steps where the host's gather and upload are the limit, near
nothing where the device is.  ``None`` where the run had no host data tier to
time (a device-resident fit, or a program that does not time it)."""

SERIES = 'data_stage_seconds{stage="wait"}'


def read(run):
    h = run["window"]["registry"]["histograms"].get(SERIES)
    if not h or not h["count"]:
        return None
    return 1e3 * h["total"] / h["count"]
