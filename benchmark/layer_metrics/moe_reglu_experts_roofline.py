"""The held ReLU-gated experts' share of their roofline: the configuration's count for ``moe`` less the router's product
(6 x 8 / 64 = 0.75 passes of an expert's three 2560 x 768 matrices a token, forward and backward: 3.48 TFLOP = 17.7 ms at
b2 x s16384) over the bf16 peak, or the held experts' weights read twice and their gradient written plus the activations at
the scope's edge over the HBM bandwidth (5.5 ms), whichever is longer — over ``moe_reglu_experts_device_s``
(``moe_scopes.experts_roofline``). Never clamped."""

import smallthinker_scopes

NAME, UNIT, SOURCE = "moe_reglu_experts_roofline", "%", "device_trace"
LAYER = "device compute"
MOVES = "step_p50_s"


def compute(run):
    return smallthinker_scopes.roofline(run, NAME)
