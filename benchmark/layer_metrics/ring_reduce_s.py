"""Seconds a step the native ring spent in its reduce passes over scratch after a
hop (``ring_reduce_s`` of ``tft.exchange.counters``; each op the mean over its
parallel stripes): the second pass over the bytes a pull brought, which
ROADMAP S2 (ii) names. Median over the traced steps, mean over groups."""

import exchange_account

NAME, UNIT, SOURCE = "ring_reduce_s", "s", "program_counter"
LAYER = "collectives (CollectivesTcp)"
MOVES = "step_p50_s"


def compute(run):
    return exchange_account.over_groups(run, exchange_account.counter("ring_reduce_s"), "mean")
