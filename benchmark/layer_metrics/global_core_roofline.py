"""The global core's share of its roofline: causal attention's two score-sized products at
(s + 1) / 2 keys a query, forward and backward, over the bf16 peak, or q, k, v, the output and their
gradients at the core's edge over the HBM bandwidth, whichever is longer (at s8192: the operations)
— over ``global_core_device_s`` (``opcounts/laguna.py``, ``window_scopes.core_roofline``). Never clamped."""

import window_scopes

NAME, UNIT, SOURCE = "global_core_roofline", "%", "device_trace"
LAYER = "device compute"
MOVES = "step_p50_s"


def compute(run):
    return window_scopes.core_roofline(run, "global_core")
