"""The share of its roofline of the causal core of a state-space hybrid's softmax layer: causal attention's two
score-sized products at (s + 1) / 2 keys a query over the bf16 peak, or q, k, v, the output and their gradients at the core's
edge over the HBM bandwidth, whichever is longer, over ``nope64_core_device_s`` (``opcounts/granite_hybrid.py``,
``ssd_scopes.core_roofline``). Never clamped."""

import ssd_scopes

NAME, UNIT, SOURCE = "nope64_core_roofline", "%", "device_trace"
LAYER = "device compute"
MOVES = "step_p50_s"


def compute(run):
    return ssd_scopes.core_roofline(run, NAME)
