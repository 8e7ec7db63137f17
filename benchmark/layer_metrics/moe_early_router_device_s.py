"""Device seconds a step in the router that reads the layer's normed input AHEAD of the mixer (scope ``moe/router``: the
2560 x 64 product in float32, the softmax over 64, the 6 largest renormalised, the load's count and balance term; forward,
its own checkpoint's recomputation and the backward through ``h1`` into the layer's input) (``smallthinker_scopes.py``)."""

import smallthinker_scopes

NAME, UNIT, SOURCE = "moe_early_router_device_s", "s", "device_trace"
LAYER = "device compute"
MOVES = "step_p50_s"


def compute(run):
    return smallthinker_scopes.seconds(run, NAME)
