"""Device seconds a step in the 8 held ReLU-gated experts' grouped matmuls (scope ``moe/experts``: gate, up and down
products over the rows routed to the held block, the ReLU and the gate's product), forward, recomputed forward and backward
(``smallthinker_scopes.py``)."""

import smallthinker_scopes

NAME, UNIT, SOURCE = "moe_reglu_experts_device_s", "s", "device_trace"
LAYER = "device compute"
MOVES = "step_p50_s"


def compute(run):
    return smallthinker_scopes.seconds(run, NAME)
