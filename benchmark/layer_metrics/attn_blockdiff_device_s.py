"""Device seconds a step in the block-diffusion softmax layers (scope ``attn/blockdiff``: the four projections over the 2·S
rows ``[noised ; clean]``, 32 query heads over 4 key/value heads of 128, the q / k norms head by head, the rotation by
positions that repeat, the staircase core, the output projection), forward, recomputed forward and backward. With what of
``attn`` names it not (the layers' first norm and residual) it adds up to ``attn_device_s`` (``sdar_scopes.py``)."""

import sdar_scopes

NAME, UNIT, SOURCE = "attn_blockdiff_device_s", "s", "device_trace"
LAYER = "device compute"
MOVES = "step_p50_s"


def compute(run):
    return sdar_scopes.seconds(run, NAME)
