"""Device seconds a step in the unrotated global layers' causal core (scope ``global_core`` inside ``attn/global``: the
flash kernel's forward, once a layer a step, and its backward; groups of 7 query heads a key/value head, 16 384 keys of 128
lanes resident at s16384) (``smallthinker_scopes.py``)."""

import smallthinker_scopes

NAME, UNIT, SOURCE = "nope_global_core_device_s", "s", "device_trace"
LAYER = "device compute"
MOVES = "step_p50_s"


def compute(run):
    return smallthinker_scopes.seconds(run, NAME)
