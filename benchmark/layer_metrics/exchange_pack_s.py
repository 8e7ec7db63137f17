"""Seconds a step spends packing buckets on the main thread (the program's
``tft.exchange.pack`` spans, ``ddp.py``: ``ascontiguousarray`` / ``reshape`` /
``concatenate`` or ``.copy()`` into a freshly allocated buffer). Median over
the traced steps, mean over groups."""

import program_spans

NAME, UNIT, SOURCE = "exchange_pack_s", "s", "program_span"
LAYER = "gradient exchange (host path)"
MOVES = "step_p50_s"


def compute(run):
    name = program_spans.PREFIX + "exchange.pack"
    return program_spans.per_step_median(run, lambda t: t.seconds(name))
