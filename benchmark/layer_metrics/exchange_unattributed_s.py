"""Seconds of a step's ``tft.exchange`` span that none of the program's spans
nested in it on the main thread covers: what the split of the exchange does
not explain. Median over the traced steps, mean over groups."""

import program_spans

NAME, UNIT, SOURCE = "exchange_unattributed_s", "s", "program_span"
LAYER = "gradient exchange (host path)"
MOVES = "step_p50_s"


def compute(run):
    name = program_spans.PREFIX + "exchange"
    return program_spans.per_step_median(run, lambda t: t.self_seconds(name))
