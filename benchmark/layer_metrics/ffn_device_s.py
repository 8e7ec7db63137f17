"""Device seconds a step spends in the feed-forward block: the program's scope
``ffn`` (dense SwiGLU) or ``moe`` (router, dispatch, experts, combine),
whichever the configuration has (``opcount.ffn_scope``); forward, recomputed
forward and backward. SELF time of the ``XLA Ops`` events whose ``op_name`` names
it. Median over the traced units, mean over groups."""

import opcount
import scope_metrics

NAME, UNIT, SOURCE = "ffn_device_s", "s", "device_trace"
LAYER = "device compute"
MOVES = "step_p50_s"


def compute(run):
    tc = run.config["program"]["transformer_config"]
    return scope_metrics.device_seconds(run, opcount.ffn_scope(tc))
