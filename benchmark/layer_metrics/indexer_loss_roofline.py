"""The selector's term's share of its roofline: its target — one score-sized product on the SELECTED pairs over the 32 query
heads, forward only — and the backward of the selector's scores over every causal pair (16 heads of 64, twice their forward),
over the bf16 peak, or the core's q and k, the selector's rotated projections and their cotangents over the HBM bandwidth,
whichever is longer (at b1 x s16384 x 6 layers: 4.90 TFLOP = 24.9 ms) — over ``indexer_loss_device_s``
(``opcounts/keye_sparse.py``, ``keye_scopes.py``). The pass computes the scores and the target twice a step (``remat``) and the
target on every causal tile: it reads low by construction. Never clamped."""

import keye_scopes

NAME, UNIT, SOURCE = "indexer_loss_roofline", "%", "device_trace"
LAYER = "device compute"
MOVES = "step_p50_s"


def compute(run):
    return keye_scopes.roofline(run, NAME)
