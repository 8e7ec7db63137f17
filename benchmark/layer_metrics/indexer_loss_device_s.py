"""Device seconds a step in the selector's own term (scope ``attn/sparse/indexer_loss``: a block of 512 queries at a time the
scores again, the target p — the 32 heads' probabilities on the selected pairs from the core's row statistics, summed in VMEM:
``head_probs`` — the KL term, and in the recomputed forward the selector's closed-form gradient through ``indexer_scores_bwd``),
forward and recomputed forward; the backward only scales what the forward emitted (``keye_scopes.py``)."""

import keye_scopes

NAME, UNIT, SOURCE = "indexer_loss_device_s", "s", "device_trace"
LAYER = "device compute"
MOVES = "step_p50_s"


def compute(run):
    return keye_scopes.seconds(run, NAME)
