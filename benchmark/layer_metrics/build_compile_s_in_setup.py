"""Seconds of the backend stages that did NOT hit the persistent compile cache
before the window opened — XLA compiling — slowest group: the stat
``compile_s`` of the program's ``tft.build.counters`` (``build_account.py``).
An exact 0 is a reading: a warm start compiles nothing."""

import build_account

NAME, UNIT, SOURCE = "build_compile_s_in_setup", "s", "program_counter"
LAYER = "compile cache"
MOVES = "setup_s"


def compute(run):
    return build_account.slowest_group(run, "compile_s")
