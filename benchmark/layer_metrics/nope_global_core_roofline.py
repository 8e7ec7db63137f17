"""The unrotated global core's share of its roofline: causal attention's two score-sized products at (s + 1) / 2 keys
a query, 28 heads of 128 lanes, forward and backward, over the bf16 peak, or q, k, v, the output and their gradients at the
core's edge over the HBM bandwidth, whichever is longer (at b2 x s16384: the operations, 11.54 TFLOP = 58.6 ms against
2.0 ms of bytes) — over ``nope_global_core_device_s`` (``opcounts/smallthinker.py``, ``window_scopes.core_roofline``).
Never clamped."""

import smallthinker_scopes

NAME, UNIT, SOURCE = "nope_global_core_roofline", "%", "device_trace"
LAYER = "device compute"
MOVES = "step_p50_s"


def compute(run):
    return smallthinker_scopes.roofline(run, NAME)
