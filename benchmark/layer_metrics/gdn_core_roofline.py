"""The Gated DeltaNet core's share of its roofline: the least time the published peaks allow what the
RECURRENCE needs a step, whatever implements it — three D x D matrix-vector products a value head a token forward,
times three with the backward, over the bf16 peak; or q and k over the 16 key heads, v and the output over the 32
value heads, the float32 log-decay and write strength a head, and their gradients at the core's edge over the HBM
bandwidth, whichever is longer (at b2 x s8192: the bytes, 3.98 ms against 2.36) — over ``gdn_core_device_s``
(``opcounts/qwen3_next.py``, ``gdn_scopes.core_roofline``). Never clamped."""

import gdn_scopes

NAME, UNIT, SOURCE = "gdn_core_roofline", "%", "device_trace"
LAYER = "device compute"
MOVES = "step_p50_s"


def compute(run):
    return gdn_scopes.core_roofline(run, "gdn_core_device_s")
