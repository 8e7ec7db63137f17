"""Seconds a step spends putting averaged pieces back on the device (the
program's ``tft.exchange.h2d`` spans around ``scatter``'s ``device_put`` loop,
``ddp.py``): on the collectives op thread, or on the main thread for a bucket
whose ring was done before its continuation was attached. Median over the
traced steps, mean over groups."""

import program_spans

NAME, UNIT, SOURCE = "exchange_h2d_s", "s", "program_span"
LAYER = "gradient exchange (host path)"
MOVES = "step_p50_s"


def compute(run):
    name = program_spans.PREFIX + "exchange.h2d"
    return program_spans.per_step_median(run, lambda t: t.seconds(name))
