"""Device seconds of a step's forward and backward pass: the executions of the
program ``jit_tft_grads`` on the device plane's ``XLA Modules`` line. Median
over the traced steps, mean over groups."""

import program_spans

NAME, UNIT, SOURCE = "grads_device_s", "s", "device_trace"
LAYER = "device compute"
MOVES = "step_p50_s"


def compute(run):
    return program_spans.per_step_median(run, lambda t: t.program_seconds("tft_grads"))
