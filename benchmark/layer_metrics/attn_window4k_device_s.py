"""Device seconds a step in the rotated window layers (scope ``attn/window``: the four projections, the whole-head
rotation of q and k, the causal core under a band of 4096 keys, the output projection), forward, recomputed forward and
backward. With ``attn_nope_global_device_s`` and what of ``attn`` names neither it adds up to ``attn_device_s``
(``smallthinker_scopes.py``)."""

import smallthinker_scopes

NAME, UNIT, SOURCE = "attn_window4k_device_s", "s", "device_trace"
LAYER = "device compute"
MOVES = "step_p50_s"


def compute(run):
    return smallthinker_scopes.seconds(run, NAME)
