"""The four-group exchange by cause: what the readers of the ring's account
and of the host copies' bytes share (PR 38).

The program says, per step, on ``tft.exchange.counters`` (``ddp.py``): the
bytes each host copy moved (``bytes_d2h``, ``pack_bytes``, ``h2d_bytes``), the
bytes of the pack's copies inside the 4K-aliasing window
(``pack_aliased_bytes``), and what the data plane's account of its rings grew
by over the exchange (``ring_wait_s``, ``ring_pull_s``, ``ring_reduce_s``,
``ring_pump_s``, ``ring_pull_bytes``, ``ring_reduce_bytes``); per ring, on a
zero-length ``tft.exchange.ring.account`` after it on the op thread
(``collectives.py``), the account of that one allreduce.

A level of the pack or a late ring is a property of ONE process, which a mean
over groups averages away. So here a metric is, per group, the median over the
traced steps (over ``program_spans.load`` / ``Trace.stat`` /
``Trace.seconds``), and then the reduction over groups its reader names: the
``mean`` (``run.per_group_mean``), the ``max``, the ``min``, or the ``spread``
(max − min). A program without the stat (an older commit), an untraced run and
a trace without the spans give ``None``, never an error; an exact 0 is a
reading.
"""

from __future__ import annotations

from typing import Callable, List, Optional, Sequence

import program_spans
from measure import median

EXCHANGE = program_spans.PREFIX + "exchange"
COUNTERS = EXCHANGE + ".counters"
RING = EXCHANGE + ".ring"
ACCOUNT = RING + ".account"
# the seconds of a ring its account names (cma: the first three and reduce;
# tcp: pump, reduce and codec)
ACCOUNTED = ("desc_wait_s", "ack_wait_s", "pull_s", "reduce_s", "pump_s", "codec_s")
# what a group's host path copies a step: landing, pack, pulls, reduce, H2D
COPIES = ("bytes_d2h", "pack_bytes", "ring_pull_bytes", "ring_reduce_bytes", "h2d_bytes")

PerStep = Callable[[program_spans.Trace], Optional[List[float]]]


def spread(values: Sequence[float]) -> float:
    return max(values) - min(values)


REDUCTIONS = {"max": max, "min": min, "spread": spread}


def per_group(run, per_step: PerStep) -> List[Optional[float]]:
    """Per group: the median over the traced steps of ``per_step(trace)``, or
    None where that group's trace has nothing to read."""
    out = []
    for r in run.results:
        trace = program_spans.load(r)
        values = per_step(trace) if trace is not None else None
        out.append(median(values) if values else None)
    return out


def over_groups(run, per_step: PerStep, how: str) -> Optional[float]:
    """:func:`per_group`, then ``how`` over the groups that gave a reading:
    ``mean``, ``max``, ``min`` or ``spread``; None where none did."""
    values = per_group(run, per_step)
    if how == "mean":
        return run.per_group_mean(values)
    values = [v for v in values if v is not None]
    return REDUCTIONS[how](values) if values else None


def counter(*keys: str) -> PerStep:
    """Per traced step: the sum of the stats ``keys`` of
    ``tft.exchange.counters``; None where one is missing."""

    def per_step(trace):
        columns = [trace.stat(COUNTERS, key) for key in keys]
        if any(c is None for c in columns):
            return None
        return [sum(vals) for vals in zip(*columns)]

    return per_step


def rate(bytes_key: str, seconds_key: str) -> PerStep:
    """Per traced step: GB/s of two stats of ``tft.exchange.counters``; None
    where one is missing or a step's seconds are 0 (nothing moved that way)."""

    def per_step(trace):
        nbytes, seconds = trace.stat(COUNTERS, bytes_key), trace.stat(COUNTERS, seconds_key)
        if nbytes is None or seconds is None or not all(seconds):
            return None
        return [b / s / 1e9 for b, s in zip(nbytes, seconds)]

    return per_step


def ring_unattributed(trace) -> Optional[List[float]]:
    """Per traced step: the ``tft.exchange.ring`` spans' seconds minus what
    their accounts name — stripe dispatch, stripe imbalance (the account is a
    mean over stripes) and the Python around the native call."""
    ring = trace.seconds(RING)
    named = [trace.stat(ACCOUNT, key) for key in ACCOUNTED]
    if ring is None or any(n is None for n in named):
        return None
    return [r - sum(parts) for r, *parts in zip(ring, *named)]


def first_ring_delay(trace) -> Optional[List[float]]:
    """Per traced step: from the start of the main thread's ``tft.exchange``
    to the start of the step's first ``tft.exchange.ring`` (the op thread's);
    None where a step lacks either."""
    out = []
    for lo, hi in trace.units:
        inside = [(s, n, line) for s, _, n, line, _ in trace.events if lo <= s < hi]
        exchanges = [s for s, n, line in inside if n == EXCHANGE and line == trace.main_line]
        rings = [s for s, n, _ in inside if n == RING]
        if not exchanges or not rings:
            return None
        out.append((min(rings) - min(exchanges)) / 1e9)
    return out
