"""The feed-forward blocks' share of their roofline: the least time the published
peaks allow what the configuration's count (``opcount.for_config``) holds for the
scope ``ffn`` or ``moe`` a step (both, each with its own nearer bound, where a
configuration has both) — its operations (with experts: the router and the k
experts a token passes through; forward and backward, recomputation and padding not
counted) over the bf16 peak, or its weights (with experts: all held) and the
activations at the scope's edge over the HBM bandwidth, whichever is longer — over
``ffn_device_s``."""

import opcount
import scope_metrics

NAME, UNIT, SOURCE = "ffn_roofline", "%", "device_trace"
LAYER = "device compute"
MOVES = "step_p50_s"


def compute(run):
    tc = run.config["program"]["transformer_config"]
    return scope_metrics.roofline(run, *opcount.for_config(run.config).ffn_scopes(tc))
