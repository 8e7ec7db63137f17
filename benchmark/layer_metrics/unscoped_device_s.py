"""Device seconds a step spends in ops whose ``op_name`` names none of the
program's scopes: the casts and slices of the stacked parameters, the final norm,
and the copies XLA adds inside the layer loop under the ``while``'s own name.
With the scope metrics it adds up to the device-busy step. Median over the traced
units, mean over groups."""

import scope_metrics
import xplane_meta

NAME, UNIT, SOURCE = "unscoped_device_s", "s", "device_trace"
LAYER = "device compute"
MOVES = "step_p50_s"


def compute(run):
    return scope_metrics.device_seconds(run, xplane_meta.UNSCOPED)
