"""Seconds of all build stages (trace, lower, load or compile) of the programs
named ``tft_*`` alone — ``tft_fused``, ``tft_grads``, ``tft_apply``: what a
respawned group's own programs cost before its first commit, apart from the
harness's reference check and eager ops — slowest group: the stat
``step_program_s`` of the program's ``tft.build.counters``
(``build_account.py``)."""

import build_account

NAME, UNIT, SOURCE = "build_step_program_s_in_setup", "s", "program_counter"
LAYER = "compile cache"
MOVES = "setup_s"


def compute(run):
    return build_account.slowest_group(run, "step_program_s")
