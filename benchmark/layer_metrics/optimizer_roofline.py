"""The scope ``optimizer``'s share of its roofline, which memory bounds: the least
bytes AdamW has to move a step (``opcount.bytes_per_step_optimizer``: 24 B of f32
state and a gradient in the compute dtype for each parameter held, 26 B with
bf16) over the published HBM bandwidth, over ``optimizer_device_s``. ``tft_apply``
is handed f32 gradients by the exchange and moves 28 B: it reads 7 % lower than
the same update inside ``tft_fused`` for that."""

import scope_metrics

NAME, UNIT, SOURCE = "optimizer_roofline", "%", "device_trace"
LAYER = "device compute"
MOVES = "step_p50_s"


def compute(run):
    return scope_metrics.roofline(run, "optimizer")
