"""Scopes inside a scope: the one reader that splits the device seconds of a
top-level scope (``xplane_meta.SCOPES``) among the ``jax.named_scope``s the
program nests under it.

``xplane_meta.scope_of`` gives every nested name to its top-level scope — the
readers of the six (``attn_device_s``, ``ffn_device_s``, …) read the whole —
and this file splits that whole: each device op whose ``op_name`` counts to
``parent`` gives its SELF time to the innermost of ``names`` on its path below
``parent``, or to ``parent`` itself where it names none (XLA's own copies at
the scope's edge), with ``reduce_trace.reduce_planes``'s rules: a traced unit
owns the program runs the host launched in it, a run launched before the trace
began is no unit's, the last of several traced units is left out, seconds are
per step, the mean over a group's chips. The parts add up to the parent's
``by_scope`` row, nothing lost and nothing added.

It opens the trace a worker left (``result["trace"]["xplane"]``) in the
parent, after the workers have exited, as ``program_spans.py`` does. A
missing file, a trace without a device plane (the CPU rehearsal) or a program
without the scope gives ``None``, never an error.

Who reads through it: ``moe_scopes.py`` (``moe`` into ``router``,
``dispatch``, ``experts``, ``combine``). An architecture with two kinds of
sequence mixing inside ``attn``, or a shared expert inside ``moe``, brings
reader files that call :func:`seconds` with its own names.
"""

from __future__ import annotations

import functools
from bisect import bisect_right
from typing import Callable, Dict, List, Optional, Sequence

import reduce_trace
import scope_metrics
import xplane_meta
from measure import median

Rows = Optional[List[Dict[str, float]]]


def innermost(op_name: Optional[str], parent: str, names: Sequence[str]) -> Optional[str]:
    """``None`` for an op that does not count to ``parent``; else the innermost
    of ``names`` on its path below ``parent``, or ``parent`` where it names none."""
    if xplane_meta.scope_of(op_name) != parent:
        return None
    for part in reversed(op_name.split("/")):
        core = part.rsplit("(", 1)[-1].rstrip(")")
        if core in names:
            return core
        if core == parent:
            break
    return parent


@functools.lru_cache(maxsize=8)
def _trace(path: str):
    """What a split needs of a trace, whatever the parent: per chip its ops, its
    program runs and its ops' ``op_name``s; the traced units' starts; and when
    the host enqueued each program run. Read once a file: two splits of one
    trace (``attn`` and ``moe``) share it."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    op_names_by_plane = xplane_meta.op_names(path)
    chips, starts_of_units, enqueued = [], [], {}
    for plane in data.planes:
        if plane.name.startswith(reduce_trace.DEVICE_PREFIX):
            ops = [ev for ln in plane.lines if ln.name == reduce_trace.OP_LINE for ev in reduce_trace._events(ln)]
            modules = sorted(
                (float(ev.start_ns), float(ev.start_ns) + float(ev.duration_ns), ev.name, dict(ev.stats).get(reduce_trace.RUN_ID))
                for ln in plane.lines if ln.name == reduce_trace.MODULE_LINE for ev in ln.events
            )
            if ops:
                chips.append((ops, modules, op_names_by_plane.get(plane.name, {})))
        elif plane.name.startswith("/host:CPU"):
            for ln in plane.lines:
                for ev in ln.events:
                    if ev.name == reduce_trace.UNIT_SPAN:
                        starts_of_units.append(float(ev.start_ns))
                    elif ev.name == reduce_trace.ENQUEUE_EVENT:
                        run = dict(ev.stats).get(reduce_trace.RUN_ID)
                        enqueued[run] = min(float(ev.start_ns), enqueued.get(run, float("inf")))
    return chips, sorted(starts_of_units), enqueued


def units(path: str, parent: str, names: Sequence[str]) -> Rows:
    """Per traced unit, device seconds of ``parent`` by the names nested in it
    (mean over chips); None where the trace has no unit or no device op."""
    chips, starts_of_units, enqueued = _trace(path)
    if not starts_of_units or not chips:
        return None
    rows = [dict() for _ in starts_of_units]
    for ops, modules, op_names in chips:
        starts = [m[0] for m in modules]
        per_unit = [[] for _ in starts_of_units]
        for s, e, name in ops:
            # the op's program run: the module event that covers its start,
            # launched when the host enqueued it (the units' clock)
            i = bisect_right(starts, s) - 1
            program, launched = 0, s
            if i >= 0 and s < modules[i][1]:
                ident = modules[i][2].rsplit("(", 1)[-1].rstrip(")")
                program = int(ident) if ident.isdigit() else 0
                launched = enqueued.get(modules[i][3])
            elif modules:
                launched = None
            if launched is None:
                continue  # a run launched before the trace began: no unit's (reduce_trace._with_scopes)
            u = bisect_right(starts_of_units, launched) - 1
            if u >= 0:
                per_unit[u].append((s, e, innermost(op_names.get((program, name)), parent, names)))
        for row, events in zip(rows, per_unit):
            for sub, sec in reduce_trace._self_times(events).items():
                if sub is not None:
                    row[sub] = row.get(sub, 0.0) + sec / len(chips)
    return rows


def seconds(
    run, parent: str, names: Sequence[str], rows_of: Optional[Callable[[str], Rows]] = None,
) -> Optional[Dict[str, float]]:
    """Device seconds a step of ``parent`` by the names nested in it (and
    ``parent`` for what names none): median over the traced units but the last
    of several, mean over groups. None where no group's trace has an op of the
    scope, or the units' scopes do not add up to their program runs
    (``scope_metrics.adds_up``). ``rows_of`` gives a trace's rows by its path:
    :func:`units` of ``parent`` and ``names`` unless the caller hands one in
    (``moe_scopes.py``, whose test replaces it by rows written by hand)."""
    if not scope_metrics.adds_up(run):
        return None
    if rows_of is None:
        rows_of = lambda path: units(path, parent, names)  # noqa: E731
    per = int(run.traffic["steps_per_unit"])
    per_group = []
    for r in run.results:
        path = (r.get("trace") or {}).get("xplane")
        try:
            rows = rows_of(path) if path else None
        except Exception as e:  # noqa: BLE001 — a metric left out, never a failed run
            print(f"[bench] subscopes of {parent}: cannot read {path}: {type(e).__name__}: {e}", flush=True)
            rows = None
        rows = [row for row in scope_metrics.whole(rows or []) if row]  # the last of several may be cut
        if rows:
            per_group.append({
                sub: median([row.get(sub, 0.0) for row in rows]) / per
                for sub in set().union(*rows)
            })
    if not per_group:
        return None
    return {
        sub: sum(g.get(sub, 0.0) for g in per_group) / len(per_group)
        for sub in set().union(*per_group)
    }
