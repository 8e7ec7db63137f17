"""Device seconds a step of the multi-token-prediction module: every op that carries the
name ``mtp`` inside its top-level scope — the two input norms and ``eh_proj`` (``embed``),
the module's layer (``attn``, ``moe``), its final norm and its pass of the head
(``head_loss``) — forward, recomputed forward and backward (``mla_mtp_scopes.py``). Already
inside the six scopes' seconds: a part of them, not a seventh."""

import mla_mtp_scopes

NAME, UNIT, SOURCE = "mtp_device_s", "s", "device_trace"
LAYER = "device compute"
MOVES = "step_p50_s"


def compute(run):
    return mla_mtp_scopes.module_seconds(run)
