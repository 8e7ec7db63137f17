"""Device seconds a step in the ``global`` layers' mixers (the projections, the partial YaRN rotation,
the causal core over the whole prefix, the output projection): the scope ``attn/global``
(``window_scopes.py``). With ``attn_window_device_s`` and what of ``attn`` names neither it adds up to
``attn_device_s``."""

import window_scopes

NAME, UNIT, SOURCE = "attn_global_device_s", "s", "device_trace"
LAYER = "device compute"
MOVES = "step_p50_s"


def compute(run):
    return window_scopes.kind_seconds(run, "global")
