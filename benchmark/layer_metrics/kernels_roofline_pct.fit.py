"""Kernels: the least time a chip could take for its share of one step (the
larger of FLOPs over peak FLOP/s and unavoidable bytes over peak bytes/s,
both from the configuration's ``work``) over the device-busy time per step.
Busy time and the number of steps both come from the trace: the profiler is
started and stopped on finished steps, so what it holds is whole executions
of the step program."""


def bound_seconds(run):
    w, peaks, chips = run["work"], run["peaks"], run["chips"]
    by_flops = w["flops"] / chips / peaks["flops_per_s"]
    by_bytes = w["bytes"] / chips / peaks["bytes_per_s"]
    return max(by_flops, by_bytes), ("flops" if by_flops >= by_bytes
                                     else "bytes")


def read(run):
    red = run["trace"]
    if red is None or run["peaks"] is None or not red.get("steps"):
        return None
    busy_per_step = red["busy_s"] / red["steps"]
    if busy_per_step <= 0:
        return None
    return 100.0 * bound_seconds(run)[0] / busy_per_step
