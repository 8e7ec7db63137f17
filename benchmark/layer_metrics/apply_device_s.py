"""Device seconds of a step's optimizer update: the executions of the program
``jit_tft_apply`` on the device plane's ``XLA Modules`` line; it starts as the
step's unit ends, and counts for the step that dispatched it. Median over the
traced steps (the last one's is cut where the trace stops), mean over groups."""

import program_spans

NAME, UNIT, SOURCE = "apply_device_s", "s", "device_trace"
LAYER = "device compute"
MOVES = "step_p50_s"


def compute(run):
    return program_spans.per_step_median(run, lambda t: t.program_seconds("tft_apply"))
