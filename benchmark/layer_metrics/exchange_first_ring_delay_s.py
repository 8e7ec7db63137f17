"""Seconds from the start of the main thread's ``tft.exchange`` to the start of
the step's first ``tft.exchange.ring`` on the collectives op thread: the D2H
issue, the plan, the first bucket's landing wait and its pack, and the op
thread's wake-up. Per group the median over the traced steps, then the MOST
over groups (PERF.md §7, open since PR 33 (i))."""

import exchange_account

NAME, UNIT, SOURCE = "exchange_first_ring_delay_s", "s", "program_span"
LAYER = "gradient exchange (host path)"
MOVES = "step_p50_s"


def compute(run):
    return exchange_account.over_groups(run, exchange_account.first_ring_delay, "max")
