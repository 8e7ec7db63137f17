"""Device seconds a step in the global layers' causal softmax core alone (scope
``attn/global/global_core``: the kernel's calls over the whole prefix, a group of query heads reading one
key/value head), forward, recomputed forward and backward (``window_scopes.py``)."""

import window_scopes

NAME, UNIT, SOURCE = "global_core_device_s", "s", "device_trace"
LAYER = "device compute"
MOVES = "step_p50_s"


def compute(run):
    return window_scopes.core_seconds(run, "global_core")
