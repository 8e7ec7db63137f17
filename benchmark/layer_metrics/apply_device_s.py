"""Device seconds of a step's optimizer update: the executions of the program
``jit_tft_apply`` on the device plane's ``XLA Modules`` line; it starts as the
step's unit ends, and counts for the unit that launched it. Median over the
traced units but the last (its run is cut where the trace stops), mean over groups."""

import scope_metrics

NAME, UNIT, SOURCE = "apply_device_s", "s", "device_trace"
LAYER = "device compute"
MOVES = "step_p50_s"


def compute(run):
    return scope_metrics.program_seconds(run, "tft_apply")
