"""Device seconds a step in the ``mla`` mixers (latent attention without positions:
the q, latent and per-head projections, the causal core, the output projection):
the scope ``attn/mla`` (``hybrid_scopes.py``). With ``attn_kda_device_s`` and what
of ``attn`` names neither it adds up to ``attn_device_s``."""

import hybrid_scopes

NAME, UNIT, SOURCE = "attn_mla_device_s", "s", "device_trace"
LAYER = "device compute"
MOVES = "step_p50_s"


def compute(run):
    return hybrid_scopes.mixer_seconds(run, "mla")
