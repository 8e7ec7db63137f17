"""Device seconds a step in everything of ``moe`` that is not the held experts' matmuls, on 2·S rows a sequence: ``router`` (the
2048 x 128 product in float32, the softmax over 128, the 8 largest renormalised, the load's count), ``dispatch`` (ordering
65 536 x 8 row-expert pairs by expert, gathering the held ones), ``combine`` and what of ``moe`` names none of them
(``sdar_scopes.py``). With ``moe_held16_experts_device_s`` it adds up to ``ffn_device_s``."""

import sdar_scopes

NAME, UNIT, SOURCE = "moe_held16_route_device_s", "s", "device_trace"
LAYER = "device compute"
MOVES = "step_p50_s"


def compute(run):
    return sdar_scopes.seconds(run, NAME)
