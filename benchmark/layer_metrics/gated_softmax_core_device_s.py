"""Device seconds a step in the gated softmax layer's causal core (scope ``attn/global/global_core``: the
flash kernel at 256-wide heads, 16 query heads over 2 key/value heads, forward once and backward); a part of
``attn_gated_softmax_device_s`` (``gdn_scopes.py``)."""

import gdn_scopes

NAME, UNIT, SOURCE = "gated_softmax_core_device_s", "s", "device_trace"
LAYER = "device compute"
MOVES = "step_p50_s"


def compute(run):
    return gdn_scopes.seconds(run, NAME)
