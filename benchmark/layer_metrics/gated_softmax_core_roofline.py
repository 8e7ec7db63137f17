"""The gated softmax core's share of its roofline: causal attention's two score-sized products at
(s + 1) / 2 keys a query, 16 heads of 256 lanes, forward and backward, over the bf16 peak, or q, k, v, the output and
their gradients at the core's edge over the HBM bandwidth, whichever is longer (at s8192: the operations, 16.7 ms
against 0.55) — over ``gated_softmax_core_device_s`` (``opcounts/qwen3_next.py``, ``gdn_scopes.core_roofline``).
Never clamped."""

import gdn_scopes

NAME, UNIT, SOURCE = "gated_softmax_core_roofline", "%", "device_trace"
LAYER = "device compute"
MOVES = "step_p50_s"


def compute(run):
    return gdn_scopes.core_roofline(run, "gated_softmax_core_device_s")
