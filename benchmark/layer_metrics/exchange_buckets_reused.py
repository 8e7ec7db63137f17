"""Buckets of a step's gradient exchange that were packed into the buffer kept
from the step before (the stat ``buckets_reused`` of the program's
``tft.exchange.counters``, ``ddp.py``); a bucket that is not reused packs into
freshly mapped pages, at 4 µs a page (PR 24). Median over the traced steps, mean
over groups; 0 is a reading (nothing kept), not a missing one."""

import program_spans

NAME, UNIT, SOURCE = "exchange_buckets_reused", "buckets", "program_counter"
LAYER = "gradient exchange (host path)"
MOVES = "step_p50_s"


def compute(run):
    return program_spans.exchange_counter_median(run, "buckets_reused")
