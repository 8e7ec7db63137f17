"""Device seconds a step in the selector's scores (scope ``attn/sparse/indexer``: its three projections from the layer's normed
input behind a ``stop_gradient`` — 16 heads of 64 over ONE key head — their rotation, and the scores ``I`` of every causal pair
a block of 512 queries at a time, ``ops/pallas/indexer``), forward and recomputed forward; the scores' backward is the term's
(``indexer_loss_device_s``), whose forward pass emits it (``keye_scopes.py``)."""

import keye_scopes

NAME, UNIT, SOURCE = "indexer_device_s", "s", "device_trace"
LAYER = "device compute"
MOVES = "step_p50_s"


def compute(run):
    return keye_scopes.seconds(run, NAME)
