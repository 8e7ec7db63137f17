"""Device seconds a step in everything of ``moe`` that is not the experts'
matmuls: the scopes ``router`` (matmul, softmax, top-k, balance term),
``dispatch`` (sorting the token-expert rows by expert, counting, gathering)
and ``combine`` (putting the rows back, weighting and summing per token), and
what of ``moe`` names none of them (``moe_scopes.py``). The price of routing:
with ``moe_experts_device_s`` it adds up to ``ffn_device_s``."""

import moe_scopes

NAME, UNIT, SOURCE = "moe_route_device_s", "s", "device_trace"
LAYER = "device compute"
MOVES = "step_p50_s"


def compute(run):
    return moe_scopes.route_seconds(run)
