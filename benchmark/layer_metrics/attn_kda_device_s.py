"""Device seconds a step in the ``kda`` mixers (gated delta-rule linear attention:
projections, short convolution, gates, the chunked core, output norm and gate),
forward, recomputed forward and backward: the scope ``attn/kda``
(``hybrid_scopes.py``). With ``attn_mla_device_s`` and what of ``attn`` names
neither (the layers' first norm and residual) it adds up to ``attn_device_s``."""

import hybrid_scopes

NAME, UNIT, SOURCE = "attn_kda_device_s", "s", "device_trace"
LAYER = "device compute"
MOVES = "step_p50_s"


def compute(run):
    return hybrid_scopes.mixer_seconds(run, "kda")
