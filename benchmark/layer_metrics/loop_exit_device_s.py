"""Device seconds a step in the exit gate of a looped stack (scope ``head_loss/exit``: the ``[d, 1]`` projection of
each loop step's normed state in float32, the exit distribution, its entropy, the step's statistics, and their
backward); part of ``head_loss_device_s``, beside the ``ut_steps`` passes of the unembed (``loop_scopes.py``)."""

import loop_scopes

NAME, UNIT, SOURCE = "loop_exit_device_s", "s", "device_trace"
LAYER = "device compute"
MOVES = "step_p50_s"


def compute(run):
    return loop_scopes.seconds(run, NAME)
