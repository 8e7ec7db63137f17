"""Wall seconds of the first call of each of ``TrainStep``'s programs until it
returned, summed, slowest group: the stat ``first_call_s`` of the program's
``tft.build.counters`` (``build_account.py``) — the build as a caller feels
it. Less ``build_step_program_s_in_setup`` it is what JAX does between its
stages with no event."""

import build_account

NAME, UNIT, SOURCE = "step_program_first_call_s", "s", "program_counter"
LAYER = "compile cache"
MOVES = "setup_s"


def compute(run):
    return build_account.slowest_group(run, "first_call_s")
