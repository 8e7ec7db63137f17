"""Pieces the gradient tree of a step's exchange came in (the stat ``pieces`` of
the program's ``tft.exchange.counters``, ``ddp.py``; PR 59): ``TrainStep.grads``
as a chain of programs hands the head's gradient on first and a layer's as its
program ends, a piece a bucket, so the host moves one while the chip computes the
next; 1 is a tree that one program gave whole (nothing of the exchange lies under
the backward). Median over the traced steps, mean over groups; a program without
the stat gives None and the line leaves the metric out."""

import program_spans

NAME, UNIT, SOURCE = "exchange_pieces", "pieces", "program_counter"
LAYER = "gradient exchange (host path)"
MOVES = "step_p50_s"


def compute(run):
    return program_spans.exchange_counter_median(run, "pieces")
