"""Device seconds a step spends under the program's scope ``embed`` (the token
gather and, backward, the scatter-add into the table's gradient): SELF time of
the ``XLA Ops`` events whose ``op_name`` names it. Median over the traced units,
mean over groups."""

import scope_metrics

NAME, UNIT, SOURCE = "embed_device_s", "s", "device_trace"
LAYER = "device compute"
MOVES = "step_p50_s"


def compute(run):
    return scope_metrics.device_seconds(run, "embed")
