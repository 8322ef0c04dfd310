"""Device: peak memory of the fullest chip after the window: the larger of
the runtime's ``peak_bytes_in_use`` (live buffers) and ``peak_bytes_reserved``
(a running program's temporaries), the run's ``memory_peak_bytes``."""


def read(run):
    peak = run["memory_peak_bytes"]
    return peak / 2 ** 30 if peak else None
