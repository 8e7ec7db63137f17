"""GB (1e9 B) a step of the pack's leaf copies whose destination lies 1 to 1023 B
ahead of their source modulo 4096, the window of 4K aliasing
(``pack_aliased_bytes`` of ``tft.exchange.counters``; ``ddp._pack_account``): per
group the median over the traced steps, then the MOST over groups. An exact 0
is a reading: no process packs inside the window."""

import exchange_account

NAME, UNIT, SOURCE = "exchange_pack_aliased_gb", "GB", "program_counter"
LAYER = "gradient exchange (host path)"
MOVES = "step_p50_s"


def compute(run):
    gb = exchange_account.over_groups(run, exchange_account.counter("pack_aliased_bytes"), "max")
    return None if gb is None else gb / 1e9
