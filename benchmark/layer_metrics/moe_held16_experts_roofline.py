"""The 16 held experts' share of their roofline: 2 rows a trained token x 8 x 16 / 128 = 1 pass x 6 x 3 x 2048 x 768
operations, 6 layers, over the bf16 peak, or the held experts' weights three times and five ``[32 768, 2048]`` a layer over
the HBM bandwidth, whichever is longer (at b2 x s8192: the operations, 5.57 TFLOP = 28.3 ms against 8.2 ms of bytes) — over
``moe_held16_experts_device_s`` (``opcounts/sdar_moe.py``, ``sdar_scopes.py``). Never clamped."""

import sdar_scopes

NAME, UNIT, SOURCE = "moe_held16_experts_roofline", "%", "device_trace"
LAYER = "device compute"
MOVES = "step_p50_s"


def compute(run):
    return sdar_scopes.roofline(run, NAME)
