"""Whether a step's gradient exchange landed its device-to-host copies on pages
the allocator kept from the step before (the stat ``d2h_pages_kept`` of the
program's ``tft.exchange.counters``, ``ddp._landing_on_kept_pages``): 1 where
it did, 0 where every copy faulted freshly mapped pages in, at 4 µs a page
(PR 27: 0.49 s of a 1.5 s step at one group, 8 s of 10 at four). Median over
the traced steps, mean over groups; 0 is a reading, not a missing one."""

import program_spans

NAME, UNIT, SOURCE = "exchange_d2h_pages_kept", "exchanges", "program_counter"
LAYER = "gradient exchange (host path)"
MOVES = "step_p50_s"


def compute(run):
    return program_spans.exchange_counter_median(run, "d2h_pages_kept")
