"""Device seconds a step in the routed side of the sparse layers: ``router``, ``dispatch``, the held
``experts``' grouped matmuls, ``combine`` and what of ``moe`` names none (``hybrid_scopes.routed_seconds``):
32 of 256 experts held, 8 chosen a token. With the ``shared`` expert and the leading dense layer's
``ffn`` it adds up to ``ffn_device_s``."""

import hybrid_scopes

NAME, UNIT, SOURCE = "moe_held_device_s", "s", "device_trace"
LAYER = "device compute"
MOVES = "step_p50_s"


def compute(run):
    return hybrid_scopes.routed_seconds(run)
