"""Seconds the slowest healer of the bootstrap heal took from its source's
window opening to the last byte of the state in its own memory
(``heal_stats.stages.recv_s``: the destination's allocation and the striped
fetch). A part of ``bootstrap_heal_s``."""

import heal_stats

NAME, UNIT, SOURCE = "heal_recv_s", "s", "program_span"
LAYER = "checkpoint transport (heal)"
MOVES = "setup_s"


def compute(run):
    return max(heal_stats.of_healers(run, "stages", "recv_s"), default=None)
