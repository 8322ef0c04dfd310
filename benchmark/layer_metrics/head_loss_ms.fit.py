"""Model step: device milliseconds a step under the heads' and the exit
loss's scope, ``zoo:lm/head_loss`` (``nn/objectives.py``): the self time of
the first device's operations whose JAX name stack holds the marker, forward
and backward together, over the trace's steps.  No such operation: nothing."""

from harness.trace_reduce import scope_ms


def read(run):
    return scope_ms(run, "zoo:lm/head_loss")
