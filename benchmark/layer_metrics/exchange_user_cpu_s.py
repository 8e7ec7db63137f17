"""User CPU seconds of the process during a step's exchange: ``utime_s`` of
``tft.exchange.counters`` (the ``getrusage(RUSAGE_SELF)`` delta around
``tft.exchange``; all threads). The runtime's own threads are in it, so the
host-side work on the gradients that no span of the program covers — libtpu
converting the device layout after the D2H — shows here, as CPU seconds far
above the exchange's wall seconds. Median over the traced steps, mean over
groups."""

import program_spans

NAME, UNIT, SOURCE = "exchange_user_cpu_s", "s", "program_counter"
LAYER = "gradient exchange (host path)"
MOVES = "step_p50_s"


def compute(run):
    return program_spans.exchange_counter_median(run, "utime_s")
