"""Device seconds a step in the causal core of a state-space hybrid's softmax layer (scope ``global_core``: the flash
kernel at 64 lanes, four query heads reading one key/value head in place), forward and backward (``ssd_scopes.py``)."""

import ssd_scopes

NAME, UNIT, SOURCE = "nope64_core_device_s", "s", "device_trace"
LAYER = "device compute"
MOVES = "step_p50_s"


def compute(run):
    return ssd_scopes.seconds(run, NAME)
