"""Buckets of a step's gradient exchange whose average the data plane took
inside its ring (the stat ``buckets_avg_in_ring`` of the program's
``tft.exchange.counters``, ``ddp.py``; PR 33): the chunk's owner divides where
it writes the final values, so no pass over the summed bucket follows the
ring. A bucket that is not averaged there is divided by NumPy on the
collectives' one op thread, 0.23-0.29 s a step at four groups (PR 33). Median
over the traced steps, mean over groups; 0 is a reading (every bucket divided
after its ring), not a missing one."""

import program_spans

NAME, UNIT, SOURCE = "exchange_buckets_avg_in_ring", "buckets", "program_counter"
LAYER = "collectives (CollectivesTcp)"
MOVES = "step_p50_s"


def compute(run):
    return program_spans.exchange_counter_median(run, "buckets_avg_in_ring")
