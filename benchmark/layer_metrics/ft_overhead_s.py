"""The part of a fault-tolerant step in which the chip waits for the FT
loop: a traced step's host span minus the device-busy time inside it
(trace), median over the traced steps, mean over groups. In the traced run
``grads`` blocks on its result, so nothing of the host trip hides behind
device time. The tax against the FUSED program is read across the two
one-chip cells, not here."""

from measure import median

NAME, UNIT, SOURCE = "ft_overhead_s", "s", "device_trace"
LAYER = "ft loop"
MOVES = "step_p50_s"


def compute(run):
    if run.traffic["loop"] == "fused":
        return None
    per = int(run.traffic["steps_per_unit"])
    return run.per_group_mean([
        median([(u["dur_s"] - u["busy_s"]) / per for u in r["trace"]["units"]])
        for r in run.results if r.get("trace")
    ])
