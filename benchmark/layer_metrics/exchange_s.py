"""Seconds a step spends in ``allreduce_gradients`` (the wrapper span the
traced run puts around the name ``parallel/ft.py`` imported): median over
the window's steps, mean over groups. ``exchange_bytes`` is on an earlier
line of the output."""

NAME, UNIT, SOURCE = "exchange_s", "s", "program_span"
LAYER = "gradient exchange (host path)"
MOVES = "step_p50_s"


def compute(run):
    return run.span_median("ft.exchange")
