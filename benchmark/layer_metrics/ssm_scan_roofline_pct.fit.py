"""Kernels, the program's own: the chunked scans' share of their roofline.
The least time a chip could take for what the configuration asks of them in
one step (``work()["ssm_scan"]``: the larger of FLOPs over peak FLOP/s and
bytes over peak bytes/s, forward and backward, no recomputation counted) over
the device time a step spent under ``zoo:ssm/scan``, whatever implements the
scan there.  A configuration that names no such work, or a trace that holds
no such operation, reads nothing."""

from harness.trace_reduce import scope_ms


def read(run):
    work, peaks = run["work"].get("ssm_scan"), run["peaks"]
    ms = scope_ms(run, "zoo:ssm/scan")
    if not work or peaks is None or not ms:
        return None
    bound = max(work["flops"] / run["chips"] / peaks["flops_per_s"],
                work["bytes"] / run["chips"] / peaks["bytes_per_s"])
    return 100.0 * bound / (ms / 1e3)
