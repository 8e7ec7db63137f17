"""The experts' share of their roofline: the least time the published peaks
allow what the algorithm needs of them a step — ``opcount.py``'s count for
``moe`` less the router's matmul (k experts' three matrices a token, forward
and backward; recomputation and padding not counted) over the bf16 peak, or all
E experts' weights and the activations at the scope's edge over the HBM
bandwidth, whichever is longer — over ``moe_experts_device_s``
(``moe_scopes.experts_roofline``). Never clamped."""

import moe_scopes

NAME, UNIT, SOURCE = "moe_experts_roofline", "%", "device_trace"
LAYER = "device compute"
MOVES = "step_p50_s"


def compute(run):
    return moe_scopes.experts_roofline(run)
