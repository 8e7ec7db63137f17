"""Device seconds a step in the latent attention's causal softmax core alone (scope
``attn/mla/mla_core``: keys 192 wide, values 128), forward, recomputed forward and
backward (``hybrid_scopes.py``)."""

import hybrid_scopes

NAME, UNIT, SOURCE = "mla_core_device_s", "s", "device_trace"
LAYER = "device compute"
MOVES = "step_p50_s"


def compute(run):
    return hybrid_scopes.core_seconds(run, "mla_core")
