"""Seconds a step's buckets spend in the ring itself on the collectives op
thread (the program's ``tft.exchange.ring`` spans around the body of
``CollectivesTcp.allreduce``'s ``run()``) — which ``wire_s``, the main
thread's tail wait, is not. 0.0 where the exchange ran and nothing rode a
ring. Median over the traced steps, mean over groups."""

import program_spans

NAME, UNIT, SOURCE = "exchange_ring_s", "s", "program_span"
LAYER = "collectives (CollectivesTcp)"
MOVES = "step_p50_s"


def compute(run):
    exchange = program_spans.PREFIX + "exchange"

    def per_step(trace):
        ring = trace.seconds(exchange + ".ring")
        if ring is None and trace.has(exchange):
            ring = [0.0] * len(trace.units)
        return ring

    return program_spans.per_step_median(run, per_step)
