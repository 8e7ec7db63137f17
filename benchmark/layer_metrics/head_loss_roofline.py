"""The scope ``head_loss``'s share of its roofline: the least time the published
peaks allow what ``opcount.py`` counts for the output head a step — its
operations forward and backward over the bf16 peak, or the table and the
activations at the scope's edge over the HBM bandwidth, whichever is longer (at
b8 x s2048: the operations) — over ``head_loss_device_s``. Logits a program
writes out to memory are its own traffic and show as a lower share."""

import scope_metrics

NAME, UNIT, SOURCE = "head_loss_roofline", "%", "device_trace"
LAYER = "device compute"
MOVES = "step_p50_s"


def compute(run):
    return scope_metrics.roofline(run, "head_loss")
