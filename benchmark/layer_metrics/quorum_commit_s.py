"""Seconds a step waits for the quorum and for the commit vote: the
program's LEDGER ``quorum_wait`` + ``commit_barrier`` phases, median over
the window's steps, mean over groups."""

NAME, UNIT, SOURCE = "quorum_commit_s", "s", "program_span"
LAYER = "manager and native core"
MOVES = "step_p50_s"


def compute(run):
    return run.ledger_phase_median(["quorum_wait", "commit_barrier"])
