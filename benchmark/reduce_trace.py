"""From a profiler trace (``*.xplane.pb``) to the numbers the per-layer
metrics read: device-busy time, per-op device time under the names XLA
printed, and the idle gaps with the host span that covered each.

The reduction is the yardstick's, so it lives with the benchmark; it is
checked against the small recorded trace in ``benchmark/tests/``. It reads
the file with ``jax.profiler.ProfileData`` and nothing else.

What a trace of a TPU process looks like (TPU v5 lite, jax 0.9.0): one
plane per chip named ``/device:TPU:<n>`` whose ``XLA Ops`` line holds one
event per executed HLO op (a ``while`` spans the ops of its body, so op time
is SELF time), and a ``/host:CPU`` plane with one line per thread; the
benchmark's ``jax.profiler.TraceAnnotation`` spans sit on the main thread's
line. The planes share one clock to within about 1.5 ms: in the recorded
trace a program starts on the device plane ~1.3 ms before the host span
that launched it. Against units of 0.6 s and more that is under 0.3 %, and
the reduction does not correct it.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

__all__ = ["find_xplane", "reduce_file", "reduce_planes", "union_seconds"]

UNIT_SPAN = "bench_unit"  # worker.py wraps every unit of the loop in it
DEVICE_PREFIX = "/device:TPU"
OP_LINE = "XLA Ops"
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


def _self_times(events: Sequence[Tuple[float, float, str]]) -> Dict[str, float]:
    """Per-name SELF seconds of possibly nested (start, end, name) events:
    an event's time minus the time of the events nested inside it."""
    out: Dict[str, float] = {}
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
        (float(ev.start_ns), float(ev.start_ns) + float(ev.duration_ns), short_op_name(ev.name))
        for ev in line.events
    ]


def reduce_planes(
    planes: Sequence[Any],
    span_names: Sequence[str],
    cpu_rehearsal: bool = False,
) -> Dict[str, Any]:
    """Reduce loaded planes. ``span_names`` are the host spans gaps may be
    attributed to (innermost wins); :data:`UNIT_SPAN` spans delimit the
    traced window and the units.

    ``cpu_rehearsal``: a CPU process has no device plane; its XLA:CPU op
    events (those that carry an ``hlo_op`` stat) on the host plane stand in
    so that the whole path can be rehearsed. Never used for a result."""
    device_ops: List[List[Tuple[float, float, str]]] = []  # per chip
    host_spans: List[Tuple[float, float, str]] = []
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
            if ops:
                device_ops.append(ops)
        elif plane.name.startswith("/host:CPU"):
            cpu_ops: List[Tuple[float, float, str]] = []
            for ln in plane.lines:
                for ev in ln.events:
                    s = float(ev.start_ns)
                    e = s + float(ev.duration_ns)
                    if ev.name in wanted:
                        host_spans.append((s, e, ev.name))
                    elif cpu_rehearsal and any(k == "hlo_op" for k, _ in ev.stats):
                        cpu_ops.append((s, e, ev.name))
            if cpu_rehearsal and cpu_ops:
                device_ops.append(cpu_ops)
    units = sorted((s, e) for s, e, n in host_spans if n == UNIT_SPAN)
    if not units:
        raise ValueError(f"the trace holds no {UNIT_SPAN!r} span")
    if not device_ops:
        raise ValueError("the trace holds no device operation")
    lo, hi = units[0][0], units[-1][1]
    chips = len(device_ops)
    merged = [_merge((s, e) for s, e, _ in ops) for ops in device_ops]

    busy_s = sum(union_seconds(m, lo, hi) for m in merged) / chips
    unit_rows = [
        {
            "dur_s": (e - s) / 1e9,
            "busy_s": sum(union_seconds(m, s, e) for m in merged) / chips,
        }
        for s, e in units
    ]

    op_s: Dict[str, float] = {}
    for ops in device_ops:
        inside = [(max(s, lo), min(e, hi), n) for s, e, n in ops if e > lo and s < hi]
        for name, sec in _self_times(inside).items():
            op_s[name] = op_s.get(name, 0.0) + sec / chips

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
        "device_ops": top(op_s),
        "idle_gaps": top(gap_s),
    }


def reduce_file(
    path: str, span_names: Sequence[str], cpu_rehearsal: bool = False
) -> Dict[str, Any]:
    from jax.profiler import ProfileData

    return reduce_planes(
        list(ProfileData.from_file(path).planes), span_names, cpu_rehearsal
    )
