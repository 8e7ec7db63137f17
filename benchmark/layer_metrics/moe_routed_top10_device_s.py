"""Device seconds a step in the routed side of the expert layers: ``router`` (a softmax over 512, the 10
largest renormalised), ``dispatch``, the 32 held ``experts``' grouped matmuls, ``combine`` and what of ``moe`` names
none (the layers' second norm); ``None`` where the program has no ``shared`` scope to tell apart. With
``moe_shared_gated_device_s`` it adds up to ``ffn_device_s`` (``gdn_scopes.py``)."""

import gdn_scopes

NAME, UNIT, SOURCE = "moe_routed_top10_device_s", "s", "device_trace"
LAYER = "device compute"
MOVES = "step_p50_s"


def compute(run):
    return gdn_scopes.seconds(run, NAME)
