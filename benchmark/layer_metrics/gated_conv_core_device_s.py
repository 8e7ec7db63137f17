"""Device seconds a step in the gated short convolution's elementwise core alone (scope
``attn/gated_conv/gated_conv_core``: ``B ⊙ X``, the causal depthwise taps, ``C ⊙ ·``, and their gradients),
forward, recomputed forward and backward — what XLA leaves of it outside the two projections' own ops."""

import subscopes

NAME, UNIT, SOURCE = "gated_conv_core_device_s", "s", "device_trace"
LAYER = "device compute"
MOVES = "step_p50_s"


def compute(run):
    by = subscopes.seconds(run, "attn", ("gated_conv_core",))
    return by.get("gated_conv_core") if by else None
