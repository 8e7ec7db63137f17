"""Seconds a step blocks in ``float(loss)`` (the program's ``tft.loss_sync``
span in ``FTTrainer.step``). Median over the traced steps, mean over
groups."""

import program_spans

NAME, UNIT, SOURCE = "loss_sync_s", "s", "program_span"
LAYER = "ft loop"
MOVES = "step_p50_s"


def compute(run):
    name = program_spans.PREFIX + "loss_sync"
    return program_spans.per_step_median(run, lambda t: t.seconds(name))
