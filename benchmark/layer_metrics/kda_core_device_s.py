"""Device seconds a step in the chunked gated delta-rule recurrence alone (scope
``attn/kda/kda_core``: the decayed intra-chunk products, the triangular solve,
the scan over chunks, the outputs), forward, recomputed forward and backward
(``hybrid_scopes.py``)."""

import hybrid_scopes

NAME, UNIT, SOURCE = "kda_core_device_s", "s", "device_trace"
LAYER = "device compute"
MOVES = "step_p50_s"


def compute(run):
    return hybrid_scopes.core_seconds(run, "kda_core")
