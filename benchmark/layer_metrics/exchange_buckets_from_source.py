"""Buckets of a step's gradient exchange whose contribution the ring read from
where the device-to-host copies landed it, with no pack on the main thread (the
stat ``buckets_from_source`` of the program's ``tft.exchange.counters``,
``ddp.py``; PR 39): the data plane's account counts the allreduces it ran with
a source. A bucket that is not read there is packed into its bucket buffer by
``np.copyto`` first, a pass over its bytes by one thread, 0.46-1.06 s a step at
four groups (PR 38). Median over the traced steps, mean over groups; 0 is a
reading (every bucket packed), not a missing one."""

import program_spans

NAME, UNIT, SOURCE = "exchange_buckets_from_source", "buckets", "program_counter"
LAYER = "gradient exchange (host path)"
MOVES = "step_p50_s"


def compute(run):
    return program_spans.exchange_counter_median(run, "buckets_from_source")
