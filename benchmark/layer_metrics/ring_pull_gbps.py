"""The rate of the ring's pulls: ``ring_pull_bytes`` / ``ring_pull_s`` of
``tft.exchange.counters``, in GB/s (1e9 B) — the share of the host's memory bus
the pulls get beside four packs. The seconds are a mean over parallel stripes
and the bytes their sum, so this is the rate of a group's stripes together.
Median over the traced steps, mean over groups; no reading where nothing was
pulled (plane tcp)."""

import exchange_account

NAME, UNIT, SOURCE = "ring_pull_gbps", "GB/s", "program_counter"
LAYER = "collectives (CollectivesTcp)"
MOVES = "step_p50_s"


def compute(run):
    return exchange_account.over_groups(run, exchange_account.rate("ring_pull_bytes", "ring_pull_s"), "mean")
