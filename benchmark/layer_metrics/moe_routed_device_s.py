"""Device seconds a step in everything of ``moe`` but the shared expert: ``router``,
``dispatch``, the held ``experts``' grouped matmuls, ``combine`` and what of ``moe``
names none (``hybrid_scopes.py``). The price of routing and of the experts held
under the share."""

import hybrid_scopes

NAME, UNIT, SOURCE = "moe_routed_device_s", "s", "device_trace"
LAYER = "device compute"
MOVES = "step_p50_s"


def compute(run):
    return hybrid_scopes.routed_seconds(run)
