"""The KDA core's share of its roofline: the least time the published peaks allow
what the recurrence needs a step — three D x D matrix-vector products a head a
token forward, times three with the backward, over the bf16 peak; or q, k, v, the
float32 log-decay, the output and their gradients at the core's edge over the HBM
bandwidth, whichever is longer (at b2 x s8192 and 32 x 128: the bytes) — over
``kda_core_device_s`` (``opcounts/kimi_linear.py``, ``hybrid_scopes.core_roofline``).
Never clamped."""

import hybrid_scopes

NAME, UNIT, SOURCE = "kda_core_roofline", "%", "device_trace"
LAYER = "device compute"
MOVES = "step_p50_s"


def compute(run):
    return hybrid_scopes.core_roofline(run, "kda_core")
