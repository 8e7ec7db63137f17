"""Device seconds a step in the chunked delta rule of the Gated DeltaNet layers (scope
``attn/gdn/gdn_core``: ``ops/kda.gdn_chunked`` — the key heads repeated over their value heads, the pairs of a
chunk, the triangular inverse, the scan over chunks that carries the state), forward, recomputed forward and
backward; a part of ``attn_gdn_device_s`` (``gdn_scopes.py``)."""

import gdn_scopes

NAME, UNIT, SOURCE = "gdn_core_device_s", "s", "device_trace"
LAYER = "device compute"
MOVES = "step_p50_s"


def compute(run):
    return gdn_scopes.seconds(run, NAME)
