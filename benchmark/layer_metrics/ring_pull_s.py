"""Seconds a step the native ring spent in its ``process_vm_readv`` loops, pulling
the left neighbour's chunk (``ring_pull_s`` of ``tft.exchange.counters``; each
op the mean over its parallel stripes). Median over the traced steps, mean
over groups."""

import exchange_account

NAME, UNIT, SOURCE = "ring_pull_s", "s", "program_counter"
LAYER = "collectives (CollectivesTcp)"
MOVES = "step_p50_s"


def compute(run):
    return exchange_account.over_groups(run, exchange_account.counter("ring_pull_s"), "mean")
