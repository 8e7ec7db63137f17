"""The gated convolution's core's share of its roofline: the least time the published peaks allow what the
core needs a step — ``B``, ``X`` and ``C`` read and ``y`` written, and in the backward those and ``y``'s gradient
read and three gradients written, in the compute dtype, over the HBM bandwidth; or its 2K + 1 elementwise
operations a channel a token, times three, over the bf16 peak, whichever is longer (the bytes, by four orders)
— over ``gated_conv_core_device_s`` (``opcounts/lfm2_moe.py``). Never clamped. A count without the core, a program
without the scope or a trace without it gives None."""

import opcount
import subscopes

NAME, UNIT, SOURCE = "gated_conv_core_roofline", "%", "device_trace"
LAYER = "device compute"
MOVES = "step_p50_s"
CORE = "gated_conv_core"


def compute(run):
    by = subscopes.seconds(run, "attn", (CORE,))
    seconds = by.get(CORE) if by else None
    counts = opcount.for_config(run.config)
    if run.peaks is None or not seconds or not hasattr(counts, "core_flops_per_token"):
        return None
    tc = run.config["program"]["transformer_config"]
    batch, seq = int(run.traffic["batch"]), int(run.traffic["seq"])
    flops, moved = counts.core_flops_per_token(tc, seq), counts.core_bytes_per_step(tc, batch, seq)
    if CORE not in flops or CORE not in moved:
        return None
    least = max(
        flops[CORE] * batch * seq / run.peaks["bf16_flops_per_s"],
        moved[CORE] / run.peaks["hbm_bytes_per_s"],
    )
    chips = int(run.config["layout"]["chips_per_group"])
    return 100.0 * least / (seconds * chips)
