"""The banded core's share of its roofline: the two score-sized products over the band's exact mean
of keys a query (496.03 at s8192, window 512), forward and backward, over the bf16 peak, or q, k, v, the
output and their gradients at the core's edge over the HBM bandwidth, whichever is longer — over
``window_core_device_s`` (``opcounts/laguna.py``, ``window_scopes.core_roofline``). Never clamped."""

import window_scopes

NAME, UNIT, SOURCE = "window_core_roofline", "%", "device_trace"
LAYER = "device compute"
MOVES = "step_p50_s"


def compute(run):
    return window_scopes.core_roofline(run, "window_core")
