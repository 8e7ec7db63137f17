"""Device seconds a step in the 16 held SiLU-gated experts' grouped matmuls on 2·S rows a sequence (scope ``moe/experts``:
gate, up and down products over the rows routed to the held block and the gate's product), forward, recomputed forward and
backward (``sdar_scopes.py``)."""

import sdar_scopes

NAME, UNIT, SOURCE = "moe_held16_experts_device_s", "s", "device_trace"
LAYER = "device compute"
MOVES = "step_p50_s"


def compute(run):
    return sdar_scopes.seconds(run, NAME)
