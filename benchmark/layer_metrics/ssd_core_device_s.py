"""Device seconds a step in the state-space mixers' core (scope ``ssd_core``: ``ops/kda.ssd_chunked`` — the sums of
the log-decay between the positions of a chunk, C·Bᵀ once a chunk for all heads, a head's apply of the pairs, its write into
and its read of the state, the scan over chunks), forward, recomputed forward and backward (``ssd_scopes.py``)."""

import ssd_scopes

NAME, UNIT, SOURCE = "ssd_core_device_s", "s", "device_trace"
LAYER = "device compute"
MOVES = "step_p50_s"


def compute(run):
    return ssd_scopes.seconds(run, NAME)
