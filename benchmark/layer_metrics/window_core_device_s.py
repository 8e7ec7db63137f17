"""Device seconds a step in the banded causal softmax core alone (scope ``attn/window/window_core``:
the kernel's calls under the window layers, a group of query heads reading one key/value head),
forward, recomputed forward and backward (``window_scopes.py``)."""

import window_scopes

NAME, UNIT, SOURCE = "window_core_device_s", "s", "device_trace"
LAYER = "device compute"
MOVES = "step_p50_s"


def compute(run):
    return window_scopes.core_seconds(run, "window_core")
