"""Device seconds a step in what of a state-space mixer is elementwise (scopes ``conv``: the causal depthwise
convolution over x | B | C with its bias and SiLU; ``gates``: dt's 64 float32 columns, the step and the log-decay;
``gated_norm``: the skip, the gate and the norm over all 4096 channels), forward, recomputed forward and backward
(``ssd_scopes.py``)."""

import ssd_scopes

NAME, UNIT, SOURCE = "ssd_conv_gate_device_s", "s", "device_trace"
LAYER = "device compute"
MOVES = "step_p50_s"


def compute(run):
    return ssd_scopes.seconds(run, NAME)
