"""Device seconds a step in the causal softmax core of a latent attention with positions
(scope ``attn/.../mla/mla_core``: keys 192 wide — 128 per head beside the 64 rotated lanes
every head shares — values 128), every layer's call and the multi-token-prediction
module's: forward, recomputed forward and backward (``hybrid_scopes.core_seconds``)."""

import hybrid_scopes

NAME, UNIT, SOURCE = "mla_rope_core_device_s", "s", "device_trace"
LAYER = "device compute"
MOVES = "step_p50_s"


def compute(run):
    return hybrid_scopes.core_seconds(run, "mla_core")
