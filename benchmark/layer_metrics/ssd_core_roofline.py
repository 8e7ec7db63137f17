"""The state-space core's share of its roofline: the least time the published peaks allow what the CHUNKED rule needs a
step at the program's chunk, whatever implements it — C·Bᵀ once a chunk for all heads, a head's apply, write and read,
times three with the backward, over the bf16 peak; or Δ·x, B, C, the float32 log-decay, the output and their gradients
at the core's edge and one state a block over the HBM bandwidth, whichever is longer (at b1 x s8192 and chunk 128: the
bytes, 4.45 ms against 3.57) — over ``ssd_core_device_s`` (``opcounts/granite_hybrid.py``,
``ssd_scopes.core_roofline``). Never clamped."""

import ssd_scopes

NAME, UNIT, SOURCE = "ssd_core_roofline", "%", "device_trace"
LAYER = "device compute"
MOVES = "step_p50_s"


def compute(run):
    return ssd_scopes.core_roofline(run, NAME)
