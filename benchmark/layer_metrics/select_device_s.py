"""Device seconds a step in the selection (scope ``attn/sparse/select``: a query's threshold — the k-th largest of its causal
scores, exact, by a search over the float32 bits, eight reads of a block's scores — the int8 table of selected pairs and which
of its tiles are live), forward and recomputed forward; nothing of it is differentiated (``keye_scopes.py``)."""

import keye_scopes

NAME, UNIT, SOURCE = "select_device_s", "s", "device_trace"
LAYER = "device compute"
MOVES = "step_p50_s"


def compute(run):
    return keye_scopes.seconds(run, NAME)
