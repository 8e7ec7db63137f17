"""Device seconds a step spends under the program's scope ``optimizer`` (the AdamW
update, in ``tft_apply`` or inside ``tft_fused``): SELF time of the ``XLA Ops``
events whose ``op_name`` names it. Median over the traced units (the last one's
``apply`` is cut where the trace stops), mean over groups."""

import scope_metrics

NAME, UNIT, SOURCE = "optimizer_device_s", "s", "device_trace"
LAYER = "device compute"
MOVES = "step_p50_s"


def compute(run):
    return scope_metrics.device_seconds(run, "optimizer")
