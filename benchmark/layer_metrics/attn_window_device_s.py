"""Device seconds a step in the ``window`` layers' mixers (q over the layer's own query heads, k and v over
the shared key/value heads, the rotation, the banded core, the output projection): the scope
``attn/window`` (``window_scopes.py``). With ``attn_global_device_s`` and what of ``attn`` names neither
it adds up to ``attn_device_s``."""

import window_scopes

NAME, UNIT, SOURCE = "attn_window_device_s", "s", "device_trace"
LAYER = "device compute"
MOVES = "step_p50_s"


def compute(run):
    return window_scopes.kind_seconds(run, "window")
