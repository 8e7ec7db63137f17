"""Model FLOP/s utilisation: the operations the forward and backward passes
need per token (the sum over scopes of the configuration's count,
``opcount.for_config``; ``opcount.py``: 6 x matmul parameters without the embed
gather table, causal attention at half the square, recomputation not
counted) x this run's tokens/s (the stretch of the window after the
profiler stopped) over chips x the device_kind's published bf16 peak."""

import opcount
from measure import tokens_per_s

NAME, UNIT, SOURCE = "mfu_pct", "%", "host_clock"
LAYER = "device compute"
MOVES = "step_p50_s"


def compute(run):
    if run.peaks is None:
        return None  # a rehearsal on a device without published peaks
    tc = run.config["program"]["transformer_config"]
    per_token = sum(opcount.for_config(run.config).flops_per_token_by_scope(tc, int(run.traffic["seq"])).values())
    chips = run.config["layout"]["groups"] * run.config["layout"]["chips_per_group"]
    rate = tokens_per_s(run.results, skip_traced=True)
    return 100.0 * per_token * rate / (chips * run.peaks["bf16_flops_per_s"])
