"""The selected core's share of its roofline: the two score-sized products on the SELECTED pairs — Σ_t min(t + 1, 2048) a
sequence, 1 920.06 a query at 16 384 positions — 32 heads of 128 lanes, forward and backward, over the bf16 peak, or q, k, v,
the output and their gradients at the core's edge over the HBM bandwidth, whichever is longer (at b1 x s16384 x 6 layers: the
operations, 9.28 TFLOP = 47.1 ms against 6.6 ms of bytes) — over ``sparse_core_device_s`` (``opcounts/keye_sparse.py``,
``keye_scopes.py``). Counted on selected pairs while the kernel walks every causal tile: the dense walk's own ceiling is
1 920.06 / 8 448 = 23 % of what a core that touches only selected pairs reaches. Never clamped."""

import keye_scopes

NAME, UNIT, SOURCE = "sparse_core_roofline", "%", "device_trace"
LAYER = "device compute"
MOVES = "step_p50_s"


def compute(run):
    return keye_scopes.roofline(run, NAME)
