"""Device seconds a step in the core under the selection (scope ``attn/sparse/sparse_core``: the flash kernel's forward and
backward calls under the int8 table of selected pairs — a DENSE walk of the causal tiles that steps over a tile with no
selected pair; the forward runs once a layer a step, its output and row statistics kept across ``remat``) (``keye_scopes.py``)."""

import keye_scopes

NAME, UNIT, SOURCE = "sparse_core_device_s", "s", "device_trace"
LAYER = "device compute"
MOVES = "step_p50_s"


def compute(run):
    return keye_scopes.seconds(run, NAME)
