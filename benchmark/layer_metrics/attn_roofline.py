"""The scope ``attn``'s share of its roofline: the least time the published peaks
allow what ``opcount.py`` counts for it a step — the four projections and causal
attention at half the square, forward and backward, recomputation not counted,
over the bf16 peak; or the weights and the activations at the scope's edge over
the HBM bandwidth, whichever is longer (at b8 x s2048: the operations, 63 ms
against 3 ms) — over ``attn_device_s``. Scores a program writes out to memory are
its own traffic, not the algorithm's, and show as a lower share."""

import scope_metrics

NAME, UNIT, SOURCE = "attn_roofline", "%", "device_trace"
LAYER = "device compute"
MOVES = "step_p50_s"


def compute(run):
    return scope_metrics.roofline(run, "attn")
