"""Seconds a step spends in ``Manager._prepare_commit`` (the program's
``tft.commit.prepare`` span): the drain of the step's pending work before the
vote. With ``quorum_commit_s`` it splits the commit barrier into drain and
RPC. Median over the traced steps, mean over groups."""

import program_spans

NAME, UNIT, SOURCE = "commit_prepare_s", "s", "program_span"
LAYER = "manager and native core"
MOVES = "step_p50_s"


def compute(run):
    name = program_spans.PREFIX + "commit.prepare"
    return program_spans.per_step_median(run, lambda t: t.seconds(name))
