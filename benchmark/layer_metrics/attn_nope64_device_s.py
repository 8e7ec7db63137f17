"""Device seconds a step in the unrotated softmax layer of a state-space hybrid (scope ``attn/global``: 32 query heads
over 8 key/value heads of 64 lanes, scores scaled by a field, the causal core, the output projection), forward, recomputed
forward and backward; ``None`` for a trace without ``attn/ssd`` (``ssd_scopes.py``)."""

import ssd_scopes

NAME, UNIT, SOURCE = "attn_nope64_device_s", "s", "device_trace"
LAYER = "device compute"
MOVES = "step_p50_s"


def compute(run):
    return ssd_scopes.seconds(run, NAME)
