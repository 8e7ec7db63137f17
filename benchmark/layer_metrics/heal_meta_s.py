"""Seconds the slowest healer of the bootstrap heal waited for its source's
window (``heal_stats.stages.meta_s``): the source pulls the state off its
device leaf by leaf and digests every leaf before the first byte may move, so
this is the source's staging as a healer feels it. A part of
``bootstrap_heal_s``."""

import heal_stats

NAME, UNIT, SOURCE = "heal_meta_s", "s", "program_span"
LAYER = "checkpoint transport (heal)"
MOVES = "setup_s"


def compute(run):
    return max(heal_stats.of_healers(run, "stages", "meta_s"), default=None)
