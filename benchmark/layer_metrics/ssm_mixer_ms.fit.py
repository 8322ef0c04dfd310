"""Model step: device milliseconds a step under the Mamba-2 mixers' scope,
``zoo:ssm/mixer`` (``nn/layers/ssm.py``: projections, convolution, scan,
gated norm), the scan's scope nested in it: the self time of the first
device's operations whose JAX name stack holds the marker, forward and
backward together, over the trace's steps.  No such operation: nothing."""

from harness.trace_reduce import scope_ms


def read(run):
    return scope_ms(run, "zoo:ssm/mixer")
