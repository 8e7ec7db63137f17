"""Seconds a step's chip sits idle while the main thread blocks in a bucket's
``np.asarray`` (the program's ``tft.exchange.d2h_wait`` spans, ``ddp.py``)
until the bucket's leaves are host arrays: the transfer, and after it the
runtime converting the device layout on its own host threads — at this size
nearly all of the wait (PERF.md §5). Idle seconds only, so the first bucket's
wait for the ``grads`` program to end is not counted. Median over the traced
steps, mean over groups."""

import program_spans

NAME, UNIT, SOURCE = "exchange_d2h_wait_s", "s", "program_span"
LAYER = "gradient exchange (host path)"
MOVES = "step_p50_s"


def compute(run):
    name = program_spans.PREFIX + "exchange.d2h_wait"

    def per_step(trace):
        idle = trace.has(name) and trace.idle_seconds(
            [name], program_spans.is_cpu_rehearsal(run)
        )
        return idle[name] if idle else None

    return program_spans.per_step_median(run, per_step)
