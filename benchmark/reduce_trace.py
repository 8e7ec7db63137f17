"""From a profiler trace (``*.xplane.pb``) to the numbers the per-layer
metrics read: device-busy time, per-op device time under the names XLA
printed, device time by the program's named scopes, and the idle gaps with
the host span that covered each.

The reduction is the yardstick's, so it lives with the benchmark; it is
checked against the small recorded traces in ``benchmark/tests/``. It reads
the events with ``jax.profiler.ProfileData``, and each op's ``op_name`` —
which ``ProfileData`` does not hand out — with ``xplane_meta.py``.

What a trace of a TPU process looks like (TPU v5 lite, jax 0.9.0): one
plane per chip named ``/device:TPU:<n>`` whose ``XLA Ops`` line holds one
event per executed HLO op (a ``while`` spans the ops of its body, so op time
is SELF time), and a ``/host:CPU`` plane with one line per thread; the
benchmark's ``jax.profiler.TraceAnnotation`` spans sit on the main thread's
line. The ``XLA Modules`` line of a device plane holds one event per executed
program, ``jit_<function>(<program id>)``: an op belongs to the program
whose event covers its start. The planes share one clock to within about 1.5 ms: in the recorded
trace a program starts on the device plane ~1.3 ms before the host span
that launched it. Against units of 0.6 s and more that is under 0.3 %, and
the busy and gap seconds do not correct it; which unit a program run belongs
to is decided on the host's clock alone, by when the host enqueued the run;
a run that no enqueue event in the trace launched was launched before the
trace began, and belongs to no traced unit.
"""

from __future__ import annotations

import glob
import os
import re
from bisect import bisect_right
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

import xplane_meta
from xplane_meta import UNSCOPED

__all__ = ["find_xplane", "reduce_file", "reduce_planes", "union_seconds"]

UNIT_SPAN = "bench_unit"  # worker.py wraps every unit of the loop in it
DEVICE_PREFIX = xplane_meta.DEVICE_PREFIX
OP_LINE = "XLA Ops"
MODULE_LINE = "XLA Modules"
# the runtime's host thread enqueues each program run under this name, and the
# run's ``XLA Modules`` event carries the same ``run_id`` stat
ENQUEUE_EVENT, RUN_ID = "DoEnqueueProgram", "run_id"
Interval = Tuple[float, float]  # (start_ns, end_ns)


def find_xplane(trace_dir: str) -> Optional[str]:
    hits = sorted(
        glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    )
    return hits[-1] if hits else None


def _merge(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def union_seconds(intervals: Iterable[Interval], lo: float, hi: float) -> float:
    """Seconds of [lo, hi) covered by the union of ``intervals`` (ns in)."""
    total = 0.0
    for s, e in _merge(intervals):
        s, e = max(s, lo), min(e, hi)
        if e > s:
            total += e - s
    return total / 1e9


def _self_times(events: Sequence[Tuple[float, float, Any]]) -> Dict[Any, float]:
    """Per-name SELF seconds of possibly nested (start, end, name) events:
    an event's time minus the time of the events nested inside it."""
    out: Dict[Any, float] = {}
    stack: List[List[Any]] = []  # [end, name, self_ns]

    def close(upto: float) -> None:
        while stack and stack[-1][0] <= upto:
            end, name, self_ns = stack.pop()
            out[name] = out.get(name, 0.0) + max(self_ns, 0.0) / 1e9

    for s, e, name in sorted(events, key=lambda ev: (ev[0], -ev[1])):
        close(s)
        if stack:
            stack[-1][2] -= min(e, stack[-1][0]) - s
        stack.append([e, name, e - s])
    close(float("inf"))
    return out


_OP = re.compile(r"^(%[^ ]+) = (\(?[a-z0-9]+\[[0-9,]*\])?")


def short_op_name(text: str) -> str:
    """``%fusion.8 = bf16[1024,1024]{1,0:T(8,128)} fusion(...)`` (what XLA
    printed, hundreds of characters) -> ``%fusion.8 bf16[1024,1024]``: the
    op's name in its module and the shape of its (first) result."""
    m = _OP.match(text)
    if not m:
        return text[:80]
    return m.group(1) + (" " + m.group(2).lstrip("(") if m.group(2) else "")


def _events(line) -> List[Tuple[float, float, str]]:
    return [
        (float(ev.start_ns), float(ev.start_ns) + float(ev.duration_ns), ev.name)
        for ev in line.events
    ]


def _clip(events, lo: float, hi: float):
    return [(max(s, lo), min(e, hi), n) for s, e, n, *_ in events if e > lo and s < hi]


def _with_scopes(ops, modules, names, enqueued):
    """``ops`` as (start, end, (short name, scope), when the op's program run
    was launched). ``names`` maps (program id, op name) to the op's
    ``op_name``; an op's program run is the ``modules`` event (start, end,
    ``jit_<function>(<id>)``, run id) that covers its start, launched when
    the host enqueued that run id (``enqueued``: on the host's clock, as the
    units are). Launched is None where the trace does not say: no run covers
    the op, or no enqueue event names the run. Both are what a run launched
    BEFORE the trace began leaves in it — the ``apply`` of the step before
    the first traced one is enqueued late and is still running when the
    profiler attaches (its module event is cut short, or missing) — so such
    ops are no traced unit's. A plane without a ``XLA Modules`` line (the
    CPU rehearsal) promises no ownership: its ops count where they start."""
    starts = [m[0] for m in modules]
    ids = [m[2].rsplit("(", 1)[-1].rstrip(")") for m in modules]
    cache: Dict[Tuple[int, str], Tuple[str, str]] = {}
    out = []
    for s, e, name in ops:
        i = bisect_right(starts, s) - 1
        program, launched = 0, (None if modules else s)
        if i >= 0 and s < modules[i][1]:
            program = int(ids[i]) if ids[i].isdigit() else 0
            launched = enqueued.get(modules[i][3])
        key = (program, name)
        if key not in cache:
            cache[key] = (short_op_name(name), xplane_meta.scope_of(names.get(key)))
        out.append((s, e, cache[key], launched))
    return out


def reduce_planes(
    planes: Sequence[Any],
    span_names: Sequence[str],
    cpu_rehearsal: bool = False,
    op_names: Optional[xplane_meta.OpNames] = None,
) -> Dict[str, Any]:
    """Reduce loaded planes. ``span_names`` are the host spans gaps may be
    attributed to (innermost wins); :data:`UNIT_SPAN` spans delimit the
    traced window and the units.

    ``op_names`` (``xplane_meta.op_names`` of the same file) gives each op
    its ``op_name``, and the innermost of ``xplane_meta.SCOPES`` on that path is the
    op's scope: SELF time again, so a ``while``'s body counts where its ops
    run, and what ``jax.checkpoint`` computes a second time counts to the
    scope it recomputes. A fusion has one ``op_name``, its root's. Without
    ``op_names`` every op is :data:`UNSCOPED`; with them, a device plane
    that ran ops and has an ``op_name`` for none is an error, not a step
    that is all ``unscoped``.

    ``cpu_rehearsal``: a CPU process has no device plane; its XLA:CPU op
    events (those that carry an ``hlo_op`` stat) on the host plane stand in
    so that the whole path can be rehearsed. Never used for a result."""
    per_chip = []  # (ops, program runs, op names) of each device plane that ran ops
    host_spans: List[Tuple[float, float, str]] = []
    enqueued: Dict[Any, float] = {}  # a program run's id -> when the host enqueued it
    wanted = set(span_names) | {UNIT_SPAN}
    for plane in planes:
        if plane.name.startswith(DEVICE_PREFIX):
            lines = [ln for ln in plane.lines if ln.name == OP_LINE]
            if not lines:
                raise ValueError(
                    f"device plane {plane.name!r} has no {OP_LINE!r} line "
                    f"(lines: {[ln.name for ln in plane.lines]})"
                )
            ops = [ev for ln in lines for ev in _events(ln)]
            modules = sorted(
                (float(ev.start_ns), float(ev.start_ns) + float(ev.duration_ns), ev.name, dict(ev.stats).get(RUN_ID))
                for ln in plane.lines if ln.name == MODULE_LINE for ev in ln.events
            )
            names = (op_names or {}).get(plane.name, {})
            if ops and op_names is not None and not names:
                raise ValueError(
                    f"device plane {plane.name!r} ran ops and names no op_name for any: "
                    "the runtime's trace layout is not the one xplane_meta.py reads"
                )
            if ops:
                per_chip.append((ops, modules, names))
        elif plane.name.startswith("/host:CPU"):
            cpu_ops: List[Tuple[float, float, str]] = []
            for ln in plane.lines:
                for ev in ln.events:
                    s = float(ev.start_ns)
                    e = s + float(ev.duration_ns)
                    if ev.name in wanted:
                        host_spans.append((s, e, ev.name))
                    elif ev.name == ENQUEUE_EVENT:
                        run = dict(ev.stats).get(RUN_ID)
                        enqueued[run] = min(s, enqueued.get(run, s))
                    elif cpu_rehearsal and any(k == "hlo_op" for k, _ in ev.stats):
                        cpu_ops.append((s, e, ev.name))
            if cpu_rehearsal and cpu_ops:
                per_chip.append((cpu_ops, [], {}))
    # per chip: (start, end, (short name, scope), launched), see _with_scopes
    device_ops = [_with_scopes(*chip, enqueued) for chip in per_chip]
    # per chip: its program runs as (seconds, ``jit_<function>``, launched)
    device_runs = [
        [((e - s) / 1e9, name.split("(")[0], enqueued[run]) for s, e, name, run in modules if run in enqueued]
        for _, modules, _ in per_chip
    ]
    units = sorted((s, e) for s, e, n in host_spans if n == UNIT_SPAN)
    if not units:
        raise ValueError(f"the trace holds no {UNIT_SPAN!r} span")
    if not device_ops:
        raise ValueError("the trace holds no device operation")
    lo, hi = units[0][0], units[-1][1]
    chips = len(device_ops)
    merged = [_merge((s, e) for s, e, *_ in ops) for ops in device_ops]

    busy_s = sum(union_seconds(m, lo, hi) for m in merged) / chips
    # a unit owns the program runs launched from its start to the next unit's:
    # the program a step dispatches last (``apply``) starts on the device as
    # its unit ends and runs on into the next; the last unit's is cut where
    # the trace stops. Launched, not started: the device's clock runs up to
    # 1.5 ms ahead of the host's, and a unit's first run can start "before" it.
    # A run the trace holds no launch of was launched before it began: no unit's
    starts = [s for s, _ in units]
    owned = list(zip(starts, starts[1:] + [float("inf")]))
    unit_rows = []
    for (s, e), (own_lo, own_hi) in zip(units, owned):
        by_scope: Dict[str, float] = {}
        programs: Dict[str, float] = {}
        unlaunched_s = 0.0
        for ops, runs in zip(device_ops, device_runs):
            scoped = [(a, b, key[1]) for a, b, key, launched in ops if launched is not None and own_lo <= launched < own_hi]
            for scope, sec in _self_times(scoped).items():
                by_scope[scope] = by_scope.get(scope, 0.0) + sec / chips
            stray = [(a, b, None) for a, b, _, launched in ops if launched is None and own_lo <= a < own_hi]
            unlaunched_s += sum(_self_times(stray).values()) / chips
            for sec, name, launched in runs:
                if own_lo <= launched < own_hi:
                    programs[name] = programs.get(name, 0.0) + sec / chips
        unit_rows.append({
            "dur_s": (e - s) / 1e9,
            "busy_s": sum(union_seconds(m, s, e) for m in merged) / chips,
            "by_scope": by_scope,
            # the same ownership for the program runs themselves: what the
            # scopes have to add up to (scope_metrics.py holds them to it)
            "programs": programs,
            # ops that ran in this unit's stretch of a run launched before
            # the trace began: no traced unit's, so in no unit's scopes
            "unlaunched_s": unlaunched_s,
        })

    op_s: Dict[str, float] = {}
    scope_s: Dict[str, float] = {}
    for ops in device_ops:
        for (name, scope), sec in _self_times(_clip(ops, lo, hi)).items():
            label = name if scope == UNSCOPED else f"{name} @{scope}"
            op_s[label] = op_s.get(label, 0.0) + sec / chips
            scope_s[scope] = scope_s.get(scope, 0.0) + sec / chips

    # idle gaps of the first chip, each cut at the edges of the host spans
    # under it and given to the innermost span that covers the piece
    inner = sorted(
        ((s, e, n) for s, e, n in host_spans), key=lambda sp: sp[1] - sp[0]
    )
    gap_s: Dict[str, float] = {}
    edges = sorted({t for s, e, _ in host_spans for t in (s, e)})
    cursor = lo
    for s, e in merged[0] + [(hi, hi)]:
        g0, g1 = cursor, min(s, hi)
        cursor = max(cursor, e)
        if g1 <= g0:
            continue
        cuts = [g0] + [t for t in edges if g0 < t < g1] + [g1]
        for a, b in zip(cuts, cuts[1:]):
            mid = (a + b) / 2
            label = next(
                (n for s2, e2, n in inner if s2 <= mid < e2), "outside any span"
            )
            if label == UNIT_SPAN:
                label = "step, outside the wrapped calls"
            gap_s[label] = gap_s.get(label, 0.0) + (b - a) / 1e9

    top = lambda d: [
        [k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:10]
    ]
    return {
        "chips": chips,
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy_s,
        "units": unit_rows,
        "by_scope": scope_s,
        "device_ops": top(op_s),
        "idle_gaps": top(gap_s),
    }


def reduce_file(
    path: str, span_names: Sequence[str], cpu_rehearsal: bool = False
) -> Dict[str, Any]:
    from jax.profiler import ProfileData

    return reduce_planes(
        list(ProfileData.from_file(path).planes), span_names, cpu_rehearsal,
        xplane_meta.op_names(path),
    )
