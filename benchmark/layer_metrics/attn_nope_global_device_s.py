"""Device seconds a step in the global layers WITHOUT positions (scope ``attn/global``: the four projections, 28 query
heads over 4 key/value heads of 128, no rotation, the causal core over every key j <= i, the output projection), forward,
recomputed forward and backward. With ``attn_window4k_device_s`` and what of ``attn`` names neither (the layers' first norm
and residual) it adds up to ``attn_device_s`` (``smallthinker_scopes.py``)."""

import smallthinker_scopes

NAME, UNIT, SOURCE = "attn_nope_global_device_s", "s", "device_trace"
LAYER = "device compute"
MOVES = "step_p50_s"


def compute(run):
    return smallthinker_scopes.seconds(run, NAME)
