"""Device seconds a step in the ``conv`` layers' mixers (the input projection to ``B``, ``C`` and ``X``, the
gated short convolution between them, the output projection; forward, recomputed forward and backward): the
scope ``attn/gated_conv`` with the core nested in it. With the attention layer's ``attn/global`` and what of
``attn`` names neither (the layers' first norms, copies at the scope's edge) it adds up to ``attn_device_s``."""

import subscopes

NAME, UNIT, SOURCE = "attn_gated_conv_device_s", "s", "device_trace"
LAYER = "device compute"
MOVES = "step_p50_s"


def compute(run):
    by = subscopes.seconds(run, "attn", ("gated_conv", "global"))
    return by.get("gated_conv") if by else None
