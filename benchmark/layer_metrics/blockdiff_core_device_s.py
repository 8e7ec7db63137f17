"""Device seconds a step in the staircase core (scope ``attn/blockdiff/blockdiff_core``: the flash kernel's forward and its
one backward over 16 384 rows a sequence, n² + 2n = 288 of the 1 024 tiles of 512 x 512 visited a head at s8192; under
``remat`` "all" the forward runs once) (``sdar_scopes.py``)."""

import sdar_scopes

NAME, UNIT, SOURCE = "blockdiff_core_device_s", "s", "device_trace"
LAYER = "device compute"
MOVES = "step_p50_s"


def compute(run):
    return sdar_scopes.seconds(run, NAME)
