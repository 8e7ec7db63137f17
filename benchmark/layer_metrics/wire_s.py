"""Main-thread seconds a step waits for the cross-group ring: the
program's LEDGER ``wire`` phase, median over the window's steps, mean over
groups. Exists only where there is a wire: more than one group."""

NAME, UNIT, SOURCE = "wire_s", "s", "program_span"
LAYER = "collectives (CollectivesTcp)"
MOVES = "step_p50_s"


def compute(run):
    if run.config["layout"]["groups"] < 2:
        return None
    return run.ledger_phase_median(["wire"])
