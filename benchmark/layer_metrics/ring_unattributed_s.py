"""Seconds of a step's ``tft.exchange.ring`` spans that the rings' accounts
(``tft.exchange.ring.account``: waiting, pulling, reducing, pumping, codec) do
not name: stripe dispatch, stripe imbalance (an account is the mean over
parallel stripes, the span waits for the slowest) and the Python around the
native call. Median over the traced steps, mean over groups."""

import exchange_account

NAME, UNIT, SOURCE = "ring_unattributed_s", "s", "program_span"
LAYER = "collectives (CollectivesTcp)"
MOVES = "step_p50_s"


def compute(run):
    return exchange_account.over_groups(run, exchange_account.ring_unattributed, "mean")
