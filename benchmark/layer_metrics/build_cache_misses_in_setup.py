"""Programs built before the window opened that the persistent compile cache did
not hold and was given, the most over groups: the stat ``cache_misses`` of the
program's ``tft.build.counters`` (``build_account.py``). An exact 0 is a
reading — the counter a warm ``setup_s`` rests on."""

import build_account

NAME, UNIT, SOURCE = "build_cache_misses_in_setup", "builds", "program_counter"
LAYER = "compile cache"
MOVES = "setup_s"


def compute(run):
    return build_account.slowest_group(run, "cache_misses")
