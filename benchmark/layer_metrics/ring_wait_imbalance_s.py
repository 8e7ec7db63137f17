"""``ring_wait_s`` (see ``ring_neighbour_wait_s``), the most less the least over
groups of each group's median over the traced steps. Near 0 the ring is bound
by itself; large, ONE group paces the others — the one that waits least, whose
exchange reaches its rings last."""

import exchange_account

NAME, UNIT, SOURCE = "ring_wait_imbalance_s", "s", "program_counter"
LAYER = "collectives (CollectivesTcp)"
MOVES = "step_p50_s"


def compute(run):
    return exchange_account.over_groups(run, exchange_account.counter("ring_wait_s"), "spread")
