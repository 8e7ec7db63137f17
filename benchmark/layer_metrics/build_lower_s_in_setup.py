"""Seconds JAX spent lowering traced programs to MLIR before the window opened
(Pallas kernels go through Mosaic here), slowest group: the stat ``lower_s`` of
the program's ``tft.build.counters`` (``build_account.py``). No cache keeps it."""

import build_account

NAME, UNIT, SOURCE = "build_lower_s_in_setup", "s", "program_counter"
LAYER = "compile cache"
MOVES = "setup_s"


def compute(run):
    return build_account.slowest_group(run, "lower_s")
