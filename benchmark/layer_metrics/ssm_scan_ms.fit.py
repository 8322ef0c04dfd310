"""Model step: device milliseconds a step under the chunked scans' scope,
``zoo:ssm/scan`` (``nn/layers/ssm.py``: from the split of the convolved xBC
to y before the gate): the self time of the first device's operations whose
JAX name stack holds the marker, forward and backward together, over the
trace's steps.  No such operation: nothing."""

from harness.trace_reduce import scope_ms


def read(run):
    return scope_ms(run, "zoo:ssm/scan")
