"""Kernel CPU seconds of the process during a step's exchange: ``stime_s`` of
``tft.exchange.counters`` (the ``getrusage(RUSAGE_SELF)`` delta around
``tft.exchange``; all threads). Page-faulting and copying through the kernel
show here. Median over the traced steps, mean over groups."""

import program_spans

NAME, UNIT, SOURCE = "exchange_sys_cpu_s", "s", "program_counter"
LAYER = "gradient exchange (host path)"
MOVES = "step_p50_s"


def compute(run):
    return program_spans.exchange_counter_median(run, "stime_s")
