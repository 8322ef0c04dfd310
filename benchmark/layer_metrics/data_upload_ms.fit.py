"""Data tier: mean time the prefetch thread took to hand a batch to the device
in the window (``asarray`` and ``device_put`` under the batch's sharding), from
the registry's ``data_stage_seconds{stage="upload"}``.  It times the call: what
of the layout change and the copy the runtime does on the calling thread.
``None`` where the run had no host data tier to time (a device-resident fit,
or a program that does not time it)."""

SERIES = 'data_stage_seconds{stage="upload"}'


def read(run):
    h = run["window"]["registry"]["histograms"].get(SERIES)
    if not h or not h["count"]:
        return None
    return 1e3 * h["total"] / h["count"]
