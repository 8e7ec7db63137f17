"""Device-busy seconds of one step: the union of the intervals in which an
op ran on the chip inside a traced unit, over the unit's steps; median
over the traced units, mean over groups."""

from measure import median

NAME, UNIT, SOURCE = "device_step_s", "s", "device_trace"
LAYER = "device compute"
MOVES = "step_p50_s"


def compute(run):
    per = int(run.traffic["steps_per_unit"])
    return run.per_group_mean([
        median([u["busy_s"] / per for u in r["trace"]["units"]])
        for r in run.results if r.get("trace")
    ])
