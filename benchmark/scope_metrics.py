"""What the per-layer metrics of a named scope share: the scope's device
seconds a step from the reduced trace (``reduce_trace.reduce_planes``'s
``by_scope`` rows), and its share of its roofline from ``opcount.py``'s counts.

A traced unit owns the program runs the host launched before the next unit's
start, so the ``optimizer`` of a step's ``apply``, which starts as the unit ends
and runs on into the next, counts to its own step. The last traced unit's may be cut
where the trace stops, so of several traced units the last is left out (a loop
that traces one unit blocks at its end: ``loops/fused.py``). A group's seconds
are the mean over its chips, and a sharded group's chips share the step's
operations and its state: a share divides by ``chips_per_group``.
"""

from __future__ import annotations

from typing import Optional

import opcount
from measure import median


def device_seconds(run, scope: str) -> Optional[float]:
    """Device seconds a step spends in ``scope``: median over the traced
    units but the last of several, mean over groups; None where no such unit
    ran an op of it."""
    per = int(run.traffic["steps_per_unit"])

    def whole(units):
        return units[:-1] if len(units) > 1 else units

    return run.per_group_mean([
        median([
            u["by_scope"][scope] / per
            for u in whole(r["trace"]["units"]) if scope in u.get("by_scope", {})
        ])
        for r in run.results if r.get("trace")
    ])


def roofline(run, scope: str) -> Optional[float]:
    """The least time the published peaks allow the scope a step — its
    counted operations over the bf16 peak or its counted bytes over the HBM
    bandwidth, whichever is longer (``opcount.py``: what the algorithm needs,
    not what the program executes) — as a share of the scope's device seconds."""
    tc = run.config["program"]["transformer_config"]
    batch, seq = int(run.traffic["batch"]), int(run.traffic["seq"])
    seconds = device_seconds(run, scope)
    if run.peaks is None or not seconds:
        return None  # a rehearsal on a device without published peaks
    flops = opcount.flops_per_token_by_scope(tc, seq).get(scope, 0.0) * batch * seq
    moved = opcount.bytes_per_step_by_scope(tc, batch, seq).get(scope, 0.0)
    least = max(flops / run.peaks["bf16_flops_per_s"], moved / run.peaks["hbm_bytes_per_s"])
    if not least:
        return None  # a scope this configuration does not have
    chips = int(run.config["layout"]["chips_per_group"])
    return 100.0 * least / (seconds * chips)
