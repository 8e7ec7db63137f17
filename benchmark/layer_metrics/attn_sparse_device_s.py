"""Device seconds a step in the softmax layers behind a learned key selector (scope ``attn/sparse``: the four projections, 32
query heads over 4 key/value heads of 128, the q / k norms head by head, the rotation, the selector's scores, thresholds and
table, the core under the table, the selector's own term, the output projection), forward, recomputed forward and backward.
With what of ``attn`` names it not (the layers' first norm and residual) it adds up to ``attn_device_s`` (``keye_scopes.py``)."""

import keye_scopes

NAME, UNIT, SOURCE = "attn_sparse_device_s", "s", "device_trace"
LAYER = "device compute"
MOVES = "step_p50_s"


def compute(run):
    return keye_scopes.seconds(run, NAME)
