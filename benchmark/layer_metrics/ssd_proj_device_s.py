"""Device seconds a step in the state-space mixers' two projections (scopes ``ssd_in``, 2048 -> z | x B C ahead of
the block scan, and ``ssd_out``, 4096 -> 2048 inside it), forward, recomputed forward and backward (``ssd_scopes.py``)."""

import ssd_scopes

NAME, UNIT, SOURCE = "ssd_proj_device_s", "s", "device_trace"
LAYER = "device compute"
MOVES = "step_p50_s"


def compute(run):
    return ssd_scopes.seconds(run, NAME)
