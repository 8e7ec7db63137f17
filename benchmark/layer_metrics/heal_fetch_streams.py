"""The most ranges a healer of the bootstrap heal had in flight at once
(``heal_stats.streams``), the LEAST over the healers: the counter the gain of
fetching ranges side by side rests on. An exact 1 is a reading (the ranges
went one after the other); a program older than the counter reads ``None``."""

import heal_stats

NAME, UNIT, SOURCE = "heal_fetch_streams", "streams", "program_counter"
LAYER = "checkpoint transport (heal)"
MOVES = "setup_s"


def compute(run):
    return min(heal_stats.of_healers(run, "streams"), default=None)
