"""Data tier: mean time the prefetch thread took to assemble a batch on the
host in the window (the source's ``next()``: the permutation's fancy index),
from the registry's ``data_stage_seconds{stage="gather"}``.  ``None`` where the
run had no host data tier to time (a device-resident fit, or a program that
does not time it)."""

SERIES = 'data_stage_seconds{stage="gather"}'


def read(run):
    h = run["window"]["registry"]["histograms"].get(SERIES)
    if not h or not h["count"]:
        return None
    return 1e3 * h["total"] / h["count"]
