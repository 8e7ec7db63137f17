"""Device seconds a step in the gated shared expert (scope ``moe/shared``: a dense SwiGLU every token passes
through beside its routed experts, and inside the scope the ``sigmoid(h·shared_scale)`` that scales its output a
token); with ``moe_routed_top10_device_s`` it adds up to ``ffn_device_s`` (``gdn_scopes.py``)."""

import gdn_scopes

NAME, UNIT, SOURCE = "moe_shared_gated_device_s", "s", "device_trace"
LAYER = "device compute"
MOVES = "step_p50_s"


def compute(run):
    return gdn_scopes.seconds(run, NAME)
