"""Device seconds a step in the Gated DeltaNet mixers (scope ``attn/gdn``: the projections, the short
convolution, the decay and the two gates, the chunked delta rule, the output norm and the output projection),
forward, recomputed forward and backward. With ``attn_gated_softmax_device_s`` and what of ``attn`` names neither
(the layers' first norm and residual) it adds up to ``attn_device_s`` (``gdn_scopes.py``)."""

import gdn_scopes

NAME, UNIT, SOURCE = "attn_gdn_device_s", "s", "device_trace"
LAYER = "device compute"
MOVES = "step_p50_s"


def compute(run):
    return gdn_scopes.seconds(run, NAME)
