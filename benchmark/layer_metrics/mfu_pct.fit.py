"""Model step: the whole step's share of the chips' peak, from the trace
alone.  The step program ran ``steps`` times on the first device; from the
first execution's start to the last one's start the device did ``steps - 1``
steps, idle gaps between them included.  Their FLOPs (the configuration's own
``work``) over that span and the chips' peak."""


def read(run):
    red, peaks = run["trace"], run["peaks"]
    if red is None or peaks is None or red.get("steps", 0) < 2:
        return None
    flops = run["work"]["flops"] * (red["steps"] - 1)
    return 100.0 * flops / (red["step_span_s"] * run["chips"]
                            * peaks["flops_per_s"])
