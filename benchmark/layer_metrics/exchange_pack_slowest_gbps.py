"""The pack's rate in the process that packs slowest: ``pack_bytes`` / ``pack_s``
of ``tft.exchange.counters`` in GB/s (1e9 B), per group the median over the
traced steps, then the LEAST over groups — the process that drew the slow
level of the pack's address lottery (PERF.md §2), which a mean over groups
averages away."""

import exchange_account

NAME, UNIT, SOURCE = "exchange_pack_slowest_gbps", "GB/s", "program_counter"
LAYER = "gradient exchange (host path)"
MOVES = "step_p50_s"


def compute(run):
    return exchange_account.over_groups(run, exchange_account.rate("pack_bytes", "pack_s"), "min")
