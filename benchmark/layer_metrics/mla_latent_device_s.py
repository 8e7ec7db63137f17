"""Device seconds a step under ``attn/mla`` OUTSIDE the core: the query's low-rank pair
with its norm (``q_lora``), the key/value latent's (``kv_lora``), the two rotations
(``rope``) and the output projection (``mla_mtp_scopes.py``). With
``mla_rope_core_device_s`` and what of ``attn`` names no kind (the layer's first norm,
copies at the scope's edge) it adds up to ``attn_device_s``."""

import mla_mtp_scopes

NAME, UNIT, SOURCE = "mla_latent_device_s", "s", "device_trace"
LAYER = "device compute"
MOVES = "step_p50_s"


def compute(run):
    return mla_mtp_scopes.latent_seconds(run)
