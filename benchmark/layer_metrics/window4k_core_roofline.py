"""The band-4096 core's share of its roofline: the two score-sized products over the band's exact mean of keys a query
(3 584.125 at s16384, window 4096), 28 heads of 128 lanes in three layers, forward and backward, over the bf16 peak, or q, k,
v, the output and their gradients at the core's edge over the HBM bandwidth, whichever is longer (at b2 x s16384: the
operations, 15.15 TFLOP = 76.9 ms against 5.9 ms of bytes) — over ``window4k_core_device_s`` (``opcounts/smallthinker.py``,
``window_scopes.core_roofline``). Never clamped."""

import smallthinker_scopes

NAME, UNIT, SOURCE = "window4k_core_roofline", "%", "device_trace"
LAYER = "device compute"
MOVES = "step_p50_s"


def compute(run):
    return smallthinker_scopes.roofline(run, NAME)
