"""Model step: 95th percentile of the start-to-start gap between successive
executions of the step program on the first device, from the trace."""

import numpy as np


def read(run):
    red = run["trace"]
    if red is None or not red.get("step_gaps_ms"):
        return None
    return float(np.percentile(red["step_gaps_ms"], 95))
