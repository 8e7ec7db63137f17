"""GB (1e9 B) a group's host path copies a step, each byte read and written once:
the landing of the device-to-host copies (``bytes_d2h``), the pack
(``pack_bytes``), the ring's pulls and reduce passes (``ring_pull_bytes``,
``ring_reduce_bytes``) and the host-to-device puts (``h2d_bytes``), all of
``tft.exchange.counters``. At world 4 and N bytes of gradients: 1 + 1 + 2 x 3/4
+ 3/4 + 1 = 5.25 N. Median over the traced steps, mean over groups."""

import exchange_account

NAME, UNIT, SOURCE = "exchange_copied_gb", "GB", "program_counter"
LAYER = "gradient exchange (host path)"
MOVES = "step_p50_s"


def compute(run):
    gb = exchange_account.over_groups(run, exchange_account.counter(*exchange_account.COPIES), "mean")
    return None if gb is None else gb / 1e9
