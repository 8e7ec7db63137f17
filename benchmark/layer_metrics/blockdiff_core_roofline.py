"""The staircase core's share of its roofline: the two score-sized products on the pairs the mask lets through — S² + S·D of
the (2S)², S + D = 8 196 a trained token a head — 32 heads of 128 lanes, forward and backward, over the bf16 peak, or q, k, v,
the output and their gradients over 2·S rows at the core's edge over the HBM bandwidth, whichever is longer (at b2 x s8192 x
6 layers: the operations, 39.6 TFLOP = 201 ms against 13.3 ms of bytes) — over ``blockdiff_core_device_s``
(``opcounts/sdar_moe.py``, ``sdar_scopes.py``). Counted on LIVE pairs: the tiling's own ceiling is 256.1 / 288 = 89 % of
what a core that wastes no tile reaches. Never clamped."""

import sdar_scopes

NAME, UNIT, SOURCE = "blockdiff_core_roofline", "%", "device_trace"
LAYER = "device compute"
MOVES = "step_p50_s"


def compute(run):
    return sdar_scopes.roofline(run, NAME)
