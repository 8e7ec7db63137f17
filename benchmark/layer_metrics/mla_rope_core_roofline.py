"""The rotated latent attention's core's share of its roofline: causal attention's two
score-sized products over (s + 1) / 2 keys a query with keys nope + rope and values dv
wide, every layer's and the module's, forward and backward, over the bf16 peak, or q, k, v,
the output and their gradients at the core's edge over the HBM bandwidth, whichever is
longer (at s8192: the operations) — over ``mla_rope_core_device_s``
(``opcounts/joyai_flash.py``, ``hybrid_scopes.core_roofline``). Never clamped."""

import hybrid_scopes

NAME, UNIT, SOURCE = "mla_rope_core_roofline", "%", "device_trace"
LAYER = "device compute"
MOVES = "step_p50_s"


def compute(run):
    return hybrid_scopes.core_roofline(run, "mla_core")
