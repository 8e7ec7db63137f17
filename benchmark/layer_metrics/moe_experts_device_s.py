"""Device seconds a step in the experts' grouped matmuls: the program's scope
``experts`` inside ``moe`` (three ``jax.lax.ragged_dot``s over the rows sorted
by expert and the silu gate between them; forward, recomputed forward and
backward). SELF time of the ``XLA Ops`` events whose ``op_name`` names it
(``moe_scopes.py``). With ``moe_route_device_s`` it adds up to ``ffn_device_s``."""

import moe_scopes

NAME, UNIT, SOURCE = "moe_experts_device_s", "s", "device_trace"
LAYER = "device compute"
MOVES = "step_p50_s"


def compute(run):
    return moe_scopes.experts_seconds(run)
