"""The MLA core's share of its roofline: causal attention's two score-sized products
at half the square with keys nope + rope and values dv wide, forward and backward,
over the bf16 peak, or q, k, v, the output and their gradients over the HBM
bandwidth, whichever is longer (at s8192: the operations) — over
``mla_core_device_s`` (``opcounts/kimi_linear.py``, ``hybrid_scopes.core_roofline``).
Never clamped."""

import hybrid_scopes

NAME, UNIT, SOURCE = "mla_core_roofline", "%", "device_trace"
LAYER = "device compute"
MOVES = "step_p50_s"


def compute(run):
    return hybrid_scopes.core_roofline(run, "mla_core")
