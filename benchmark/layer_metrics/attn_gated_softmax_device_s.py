"""Device seconds a step in the gated softmax layer whole (scope ``attn/global`` of a model whose query
projection carries an output gate: the projections — ``W_q`` twice as wide —, the per-head norms of q and k, the
rotation of 64 of 256 lanes, the causal core, the gate on its output, the output projection), forward, recomputed
forward and backward. With ``attn_gdn_device_s`` and what of ``attn`` names neither it adds up to ``attn_device_s``
(``gdn_scopes.py``)."""

import gdn_scopes

NAME, UNIT, SOURCE = "attn_gated_softmax_device_s", "s", "device_trace"
LAYER = "device compute"
MOVES = "step_p50_s"


def compute(run):
    return gdn_scopes.seconds(run, NAME)
