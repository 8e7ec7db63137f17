"""Device seconds a step spends in the feed-forward blocks: the program's scope
``ffn`` (dense SwiGLU) or ``moe`` (router, dispatch, experts, combine) — whichever
the configuration has, and the two together where it has both, a dense layer ahead
of expert layers (``ffn_scopes`` of the configuration's count, ``opcount.for_config``);
forward, recomputed forward and backward. SELF time of the ``XLA Ops`` events whose
``op_name`` names it. Median over the traced units, mean over groups."""

import opcount
import scope_metrics

NAME, UNIT, SOURCE = "ffn_device_s", "s", "device_trace"
LAYER = "device compute"
MOVES = "step_p50_s"


def compute(run):
    tc = run.config["program"]["transformer_config"]
    return scope_metrics.device_seconds_of(run, opcount.for_config(run.config).ffn_scopes(tc))
