"""The program's own spans in a traced run's ``.xplane.pb``: what the
per-layer metrics that look INSIDE a layer read.

The program (``torchft_tpu/telemetry/tracing.py``) puts a
``jax.profiler.TraceAnnotation`` named ``tft.<span>`` around each piece of
the step, on the thread that runs it: the step's pieces on the main thread,
a bucket's ring and its ``device_put`` on the collectives op thread. This
file opens the trace a worker left (``result["trace"]["xplane"]``) with
``jax.profiler.ProfileData`` — in the parent, after the workers have exited,
without initialising a backend — cuts it to the traced units and offers, per
traced step:

* seconds by span name, over all threads or the main thread only;
* a stat of a span (the counters ``tft.exchange.counters`` carries);
* self time: a main-thread span minus the program spans nested in it;
* the chip's idle seconds under a span, by ``reduce_trace.reduce_planes``.

A program without such spans (an older commit), a trace without a device
plane (the CPU rehearsal) or a missing file gives ``None``, never an error.
Several host lines are named ``python3``; threads are told apart by line,
and the main thread's is the one that holds ``bench_unit``.
"""

from __future__ import annotations

import functools
import types
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import reduce_trace
from measure import median

PREFIX = "tft."

# (start_ns, end_ns, name, line index, stats)
Event = Tuple[float, float, str, int, Dict[str, Any]]


def _event(name: str, start_ns: float, duration_ns: float, stats=()) -> Any:
    """An event as ``reduce_trace.reduce_planes`` reads one."""
    return types.SimpleNamespace(
        name=name, start_ns=start_ns, duration_ns=duration_ns, stats=stats
    )


class Trace:
    """One group's traced steps."""

    def __init__(self, path: str) -> None:
        from jax.profiler import ProfileData

        self._data = ProfileData.from_file(path)  # owns the planes' memory
        self.device_planes = []
        self.events: List[Event] = []
        self._cpu_op_events: Optional[List[Any]] = None
        units: List[Tuple[float, float, int]] = []
        n_lines = 0
        for plane in self._data.planes:
            if plane.name.startswith(reduce_trace.DEVICE_PREFIX):
                self.device_planes.append(plane)
            if not plane.name.startswith("/host:CPU"):
                continue
            for line in plane.lines:
                n_lines += 1
                for ev in line.events:
                    name = ev.name
                    if name == reduce_trace.UNIT_SPAN:
                        s = float(ev.start_ns)
                        units.append((s, s + float(ev.duration_ns), n_lines))
                    elif name.startswith(PREFIX):
                        s = float(ev.start_ns)
                        self.events.append(
                            (s, s + float(ev.duration_ns), name, n_lines, dict(ev.stats))
                        )
        units.sort()
        self.units = [(s, e) for s, e, _ in units]
        self.main_line = units[0][2] if units else None
        self.events.sort(key=lambda ev: (ev[0], -ev[1]))

    def _in_unit(self, unit, name: str, main_only: bool) -> List[Event]:
        lo, hi = unit
        return [
            ev for ev in self.events
            if ev[2] == name and lo <= ev[0] < hi
            and (not main_only or ev[3] == self.main_line)
        ]

    def has(self, name: str) -> bool:
        return any(ev[2] == name for ev in self.events)

    def seconds(self, name: str, main_only: bool = False) -> Optional[List[float]]:
        """Per traced step: summed duration of the spans ``name`` that
        started inside it; None where the trace has no such span at all."""
        if not self.has(name):
            return None
        return [
            sum(e - s for s, e, *_ in self._in_unit(u, name, main_only)) / 1e9
            for u in self.units
        ]

    def stat(self, name: str, key: str) -> Optional[List[float]]:
        """Per traced step: the stat ``key`` summed over the spans ``name``;
        None where no such span carries it."""
        out = []
        for u in self.units:
            vals = [ev[4][key] for ev in self._in_unit(u, name, False) if key in ev[4]]
            if not vals:
                return None
            out.append(float(sum(vals)))
        return out

    def self_seconds(self, name: str) -> Optional[List[float]]:
        """Per traced step: the main thread's spans ``name`` minus what the
        program spans nested in them on that thread cover; None where the
        trace has no such span at all."""
        if not self.has(name):
            return None
        out = []
        for u in self.units:
            total = 0.0
            for s, e, *_ in self._in_unit(u, name, True):
                inner = [
                    (s2, e2) for s2, e2, n2, line, _ in self.events
                    if line == self.main_line and s <= s2 and e2 <= e
                    and (s2, e2, n2) != (s, e, name)
                ]
                total += (e - s) / 1e9 - reduce_trace.union_seconds(inner, s, e)
            out.append(total)
        return out

    def _cpu_ops(self) -> List[Any]:
        """The CPU rehearsal's stand-in for a device plane: the XLA:CPU op
        events (those with an ``hlo_op`` stat) of the host plane, as
        ``reduce_planes(cpu_rehearsal=True)`` takes them."""
        if self._cpu_op_events is None:
            self._cpu_op_events = [
                _event(ev.name, float(ev.start_ns), float(ev.duration_ns), (("hlo_op", ""),))
                for plane in self._data.planes if plane.name.startswith("/host:CPU")
                for line in plane.lines for ev in line.events
                if any(k == "hlo_op" for k, _ in ev.stats)
            ]
        return self._cpu_op_events

    def idle_seconds(self, names: Sequence[str], cpu_rehearsal: bool = False) -> Optional[Dict[str, List[float]]]:
        """Per traced step: the chip's idle seconds while the main thread was
        inside each of the spans ``names`` (innermost wins; at most 8 names,
        ``reduce_planes`` keeps the ten largest rows). None without a device
        plane."""
        if len(names) > 8:
            raise ValueError("at most 8 span names: reduce_planes keeps ten rows")
        out: Dict[str, List[float]] = {n: [] for n in names}
        for lo, hi in self.units:
            # reduce_planes sees this step alone: its unit span and the main
            # thread's spans inside it
            lines = [types.SimpleNamespace(name="main", events=[
                _event(reduce_trace.UNIT_SPAN, lo, hi - lo)
            ] + [
                _event(n, s, e - s) for s, e, n, line, _ in self.events
                if line == self.main_line and n in out and lo <= s < hi
            ])]
            if cpu_rehearsal:
                lines.append(types.SimpleNamespace(name="ops", events=self._cpu_ops()))
            host = types.SimpleNamespace(name="/host:CPU", lines=lines)
            try:
                reduced = reduce_trace.reduce_planes(
                    self.device_planes + [host], list(names), cpu_rehearsal
                )
            except ValueError:  # no device operation in the trace
                return None
            gaps = dict(reduced["idle_gaps"])
            for n in names:
                out[n].append(gaps.get(n, 0.0))
        return out


@functools.lru_cache(maxsize=8)
def _load(path: str) -> Trace:
    return Trace(path)


def load(result: Dict[str, Any]) -> Optional[Trace]:
    """The trace of one group's result, or None: an untraced run, a file
    that is gone or unreadable, or a trace without units."""
    path = (result.get("trace") or {}).get("xplane")
    if not path:
        return None
    try:
        trace = _load(path)
    except Exception as e:  # noqa: BLE001 — a metric left out, never a failed run
        print(f"[bench] program_spans: cannot read {path}: {type(e).__name__}: {e}", flush=True)
        return None
    return trace if trace.units else None


def is_cpu_rehearsal(run) -> bool:
    return run.results[0]["device"]["platform"] == "cpu"


def per_step_median(run, per_step: Callable[[Trace], Optional[List[float]]]) -> Optional[float]:
    """Median over the traced steps of ``per_step(trace)``, mean over groups;
    None where no group's trace has what ``per_step`` reads."""
    per_group = []
    for r in run.results:
        trace = load(r)
        values = per_step(trace) if trace is not None else None
        per_group.append(median(values) if values else None)
    return run.per_group_mean(per_group)


def exchange_counter_median(run, key: str) -> Optional[float]:
    """:func:`per_step_median` of the stat ``key`` that the program's
    ``tft.exchange.counters`` carries (``utime_s``, ``stime_s``,
    ``buckets_reused``, ``d2h_pages_kept``). An exact 0 is a reading — no
    bucket reused, or CPU seconds under the kernel's 10 ms tick — and only a
    program without the counter gives None."""
    return per_step_median(run, lambda t: t.stat(PREFIX + "exchange.counters", key))
