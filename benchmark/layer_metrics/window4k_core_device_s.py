"""Device seconds a step in the window layers' causal core (scope ``window_core`` inside ``attn/window``: the banded flash
kernel's forward, once a layer a step, and its backward; a band of 4096 is nine key tiles of 512 a query tile for eight
useful) (``smallthinker_scopes.py``)."""

import smallthinker_scopes

NAME, UNIT, SOURCE = "window4k_core_device_s", "s", "device_trace"
LAYER = "device compute"
MOVES = "step_p50_s"


def compute(run):
    return smallthinker_scopes.seconds(run, NAME)
