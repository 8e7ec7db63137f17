"""Device seconds a step in the Mamba-2 state-space mixers (scope ``attn/ssd``: the two projections, the short
convolution with its bias, the step and the log-decay, the chunked rule, the skip, the gate and the norm over all channels),
forward, recomputed forward and backward. With ``attn_nope64_device_s`` and what of ``attn`` names neither (the layers'
first norm and residual) it adds up to ``attn_device_s`` (``ssd_scopes.py``)."""

import ssd_scopes

NAME, UNIT, SOURCE = "attn_ssd_device_s", "s", "device_trace"
LAYER = "device compute"
MOVES = "step_p50_s"


def compute(run):
    return ssd_scopes.seconds(run, NAME)
