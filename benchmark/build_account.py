"""Set-up from inside: what the seven readers of the program's build account
share (PR 56).

The program (``torchft_tpu/telemetry/builds.py``) listens on ``jax.monitoring``
and keeps process totals since start of what JAX spent building programs:
``trace_s`` (outermost traces only — Python), ``lower_s`` (to MLIR, Pallas
kernels through Mosaic), ``load_s`` (backend stages that hit the persistent
cache: retrieval, deserialisation, load), ``compile_s`` (those that did not),
``cache_misses``, ``step_program_s`` (all stages of the ``tft_*`` programs
alone) and ``first_call_s`` (the wall seconds of each ``tft_*`` program's
first call, as ``TrainStep`` felt them). ``TrainStep.step`` and ``.grads`` put
the totals on a zero-length ``tft.build.counters`` a call.

A reader takes, per group, the stats of the FIRST such event of the first
traced unit (``program_spans.load(r).events`` is in order of start): the
harness refuses a run that compiles inside the window, so the totals then hold
every build of the set-up and nothing else — not a sum over a unit's steps,
each of which repeats them. Seconds reduce to the slowest group, as
``compile_s_in_setup`` does. A program without the event (an older commit), an
untraced run and a trace without units give ``None``, never an error; an exact
0 is a reading (nothing compiled: a warm start).
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import program_spans

COUNTERS = program_spans.PREFIX + "build.counters"


def first_counters(trace: program_spans.Trace) -> Optional[Dict[str, Any]]:
    """The stats of the first ``tft.build.counters`` of the first traced unit."""
    lo, hi = trace.units[0]
    for start, _, name, _, stats in trace.events:
        if name == COUNTERS and lo <= start < hi:
            return stats
    return None


def slowest_group(run, key: str) -> Optional[float]:
    """The stat ``key`` of each group's :func:`first_counters`, the most over
    the groups that carry it; None where none does."""
    values = []
    for r in run.results:
        trace = program_spans.load(r)
        stats = first_counters(trace) if trace is not None else None
        if stats is not None and key in stats:
            values.append(float(stats[key]))
    return max(values, default=None)
