"""Seconds JAX spent TRACING programs in Python before the window opened, slowest
group: the stat ``trace_s`` of the program's ``tft.build.counters``
(``build_account.py``) — outermost traces only, so the thousands of jitted
functions met inside a trace are not counted twice. No cache keeps it: it does
not fall in a warm start."""

import build_account

NAME, UNIT, SOURCE = "build_trace_s_in_setup", "s", "program_counter"
LAYER = "compile cache"
MOVES = "setup_s"


def compute(run):
    return build_account.slowest_group(run, "trace_s")
