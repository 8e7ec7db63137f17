"""Seconds a step the native ring stood waiting for a neighbour: own descriptor
sent until the left neighbour's arrived, own ack sent until the right
neighbour's arrived (``ring_wait_s`` of the program's ``tft.exchange.counters``:
the growth of the data plane's account over the exchange, each op the mean
over its parallel stripes; native/dataplane.cc ``cma_hop``). Exposed waiting,
not bytes. 0 on plane tcp, whose pump cannot tell waiting from moving. Median
over the traced steps, mean over groups."""

import exchange_account

NAME, UNIT, SOURCE = "ring_neighbour_wait_s", "s", "program_counter"
LAYER = "collectives (CollectivesTcp)"
MOVES = "step_p50_s"


def compute(run):
    return exchange_account.over_groups(run, exchange_account.counter("ring_wait_s"), "mean")
