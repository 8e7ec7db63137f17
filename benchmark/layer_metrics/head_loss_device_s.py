"""Device seconds a step spends under the program's scope ``head_loss`` (the output
head's matmul, log-softmax and loss; forward and backward): SELF time of the
``XLA Ops`` events whose ``op_name`` names it. Median over the traced units, mean
over groups."""

import scope_metrics

NAME, UNIT, SOURCE = "head_loss_device_s", "s", "device_trace"
LAYER = "device compute"
MOVES = "step_p50_s"


def compute(run):
    return scope_metrics.device_seconds(run, "head_loss")
