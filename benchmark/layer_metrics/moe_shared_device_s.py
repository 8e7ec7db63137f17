"""Device seconds a step in the shared experts (scope ``moe/shared``: a dense SwiGLU
every token passes through beside its routed experts); with
``moe_routed_device_s`` and the dense layers' ``ffn`` it adds up to
``ffn_device_s`` (``hybrid_scopes.py``)."""

import hybrid_scopes

NAME, UNIT, SOURCE = "moe_shared_device_s", "s", "device_trace"
LAYER = "device compute"
MOVES = "step_p50_s"


def compute(run):
    return hybrid_scopes.shared_seconds(run)
