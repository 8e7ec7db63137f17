"""Device seconds of a step's forward and backward pass: the executions of the
program ``jit_tft_grads`` on the device plane's ``XLA Modules`` line that the
step's unit launched. Median over the traced units but the last, mean over groups."""

import scope_metrics

NAME, UNIT, SOURCE = "grads_device_s", "s", "device_trace"
LAYER = "device compute"
MOVES = "step_p50_s"


def compute(run):
    return scope_metrics.program_seconds(run, "tft_grads")
