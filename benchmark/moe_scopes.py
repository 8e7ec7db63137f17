"""Inside the scope ``moe``: what the three readers ``moe_experts_device_s``,
``moe_route_device_s`` and ``moe_experts_roofline`` share.

The program nests four ``jax.named_scope``s under ``moe``
(``torchft_tpu/models/transformer._ffn_moe``, ``ops/layers.moe_dropless``):
``router`` (the router's matmul, softmax, top-k, the balance term),
``dispatch`` (ordering the token-expert rows by expert, counting them,
gathering the rows), ``experts`` (the three grouped matmuls and the gate) and
``combine`` (putting the rows back, weighting and summing them per token).
``xplane_meta.scope_of`` gives all of them to ``moe`` — ``ffn_device_s`` reads
the whole — and this file splits that whole: each device op whose ``op_name``
counts to ``moe`` gives its SELF time to the innermost of the four on its
path, or to ``moe`` itself where it names none (XLA's own copies at the
scope's edge), with ``reduce_trace.reduce_planes``'s rules: a traced unit
owns the program runs the host launched in it, the last of several traced
units is left out, seconds are per step, the mean over a group's chips.

It opens the trace a worker left (``result["trace"]["xplane"]``) in the
parent, after the workers have exited, as ``program_spans.py`` does. A
missing file, a trace without a device plane (the CPU rehearsal) or a program
without the scope (a dense model; an older commit) gives ``None``, never an
error.

The experts' least work is counted here beside its hand-worked test
(``tests/test_moe_scopes.py``), from ``opcount.py``'s count of the scope less
the router's part.
"""

from __future__ import annotations

import functools
from bisect import bisect_right
from typing import Any, Dict, Optional

import opcount
import reduce_trace
import scope_metrics
import xplane_meta
from measure import median

MOE = "moe"
SUBSCOPES = ("router", "dispatch", "experts", "combine")


def subscope_of(op_name: Optional[str]) -> Optional[str]:
    """``None`` for an op that does not count to ``moe``; else the innermost
    of :data:`SUBSCOPES` on its path, or ``"moe"`` where it names none."""
    if xplane_meta.scope_of(op_name) != MOE:
        return None
    for part in reversed(op_name.split("/")):
        core = part.rsplit("(", 1)[-1].rstrip(")")
        if core in SUBSCOPES:
            return core
        if core == MOE:
            break
    return MOE


@functools.lru_cache(maxsize=8)
def _units_by_subscope(path: str):
    """Per traced unit, device seconds by subscope (mean over chips); None
    where the trace has no unit or no device op."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    names = xplane_meta.op_names(path)
    chips, units, enqueued = [], [], {}
    for plane in data.planes:
        if plane.name.startswith(reduce_trace.DEVICE_PREFIX):
            ops = [ev for ln in plane.lines if ln.name == reduce_trace.OP_LINE for ev in reduce_trace._events(ln)]
            modules = sorted(
                (float(ev.start_ns), float(ev.start_ns) + float(ev.duration_ns), ev.name, dict(ev.stats).get(reduce_trace.RUN_ID))
                for ln in plane.lines if ln.name == reduce_trace.MODULE_LINE for ev in ln.events
            )
            if ops:
                chips.append((ops, modules, names.get(plane.name, {})))
        elif plane.name.startswith("/host:CPU"):
            for ln in plane.lines:
                for ev in ln.events:
                    if ev.name == reduce_trace.UNIT_SPAN:
                        units.append(float(ev.start_ns))
                    elif ev.name == reduce_trace.ENQUEUE_EVENT:
                        run = dict(ev.stats).get(reduce_trace.RUN_ID)
                        enqueued[run] = min(float(ev.start_ns), enqueued.get(run, float("inf")))
    if not units or not chips:
        return None
    units.sort()
    rows = [dict() for _ in units]
    for ops, modules, op_names in chips:
        starts = [m[0] for m in modules]
        per_unit = [[] for _ in units]
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
            u = bisect_right(units, launched) - 1
            if u >= 0:
                per_unit[u].append((s, e, subscope_of(op_names.get((program, name)))))
        for row, events in zip(rows, per_unit):
            for sub, sec in reduce_trace._self_times(events).items():
                if sub is not None:
                    row[sub] = row.get(sub, 0.0) + sec / len(chips)
    return rows


def device_seconds(run) -> Optional[Dict[str, float]]:
    """Device seconds a step by subscope of ``moe`` (``router``, ``dispatch``,
    ``experts``, ``combine``, and ``moe`` for what names none): median over the
    traced units but the last of several, mean over groups. None where no
    group's trace has an op of the scope, or the units' scopes do not add
    up to their program runs (``scope_metrics.adds_up``)."""
    if not scope_metrics.adds_up(run):
        return None
    per = int(run.traffic["steps_per_unit"])
    per_group = []
    for r in run.results:
        path = (r.get("trace") or {}).get("xplane")
        try:
            rows = _units_by_subscope(path) if path else None
        except Exception as e:  # noqa: BLE001 — a metric left out, never a failed run
            print(f"[bench] moe_scopes: cannot read {path}: {type(e).__name__}: {e}", flush=True)
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


def experts_seconds(run) -> Optional[float]:
    by = device_seconds(run)
    return by.get("experts") if by else None


def route_seconds(run) -> Optional[float]:
    """Everything of ``moe`` that is not the experts' grouped matmuls."""
    by = device_seconds(run)
    if not by:
        return None
    return sum(sec for sub, sec in by.items() if sub != "experts")


def experts_flops_per_step(tc: Dict[str, Any], batch: int, seq: int) -> float:
    """Forward + backward operations of the experts alone: what ``opcount``
    counts for ``moe`` less the router's ``d x E`` matmul (6 per parameter
    per token). Recomputation and padding to a tile are not counted."""
    router = 6.0 * tc["n_layers"] * tc["d_model"] * tc["n_experts"]
    return (opcount.flops_per_token_by_scope(tc, seq)[MOE] - router) * batch * seq


def experts_bytes_per_step(tc: Dict[str, Any], batch: int, seq: int) -> float:
    """The experts' share of ``opcount.bytes_per_step_by_scope``'s ``moe``:
    all E experts' weights read forward and backward and their gradient
    written, and the activations at the scope's edge — less the router's
    weights, three times as the scope counts them."""
    c = opcount.ITEMSIZE[tc.get("dtype", "bfloat16")]
    router = 3 * c * tc["n_layers"] * tc["d_model"] * tc["n_experts"]
    return opcount.bytes_per_step_by_scope(tc, batch, seq)[MOE] - router


def experts_roofline(run) -> Optional[float]:
    """The least time the published peaks allow the experts a step — their
    operations over the bf16 peak or their bytes over the HBM bandwidth,
    whichever is longer — as a share of ``moe_experts_device_s``. Never
    clamped."""
    tc = run.config["program"]["transformer_config"]
    seconds = experts_seconds(run)
    if run.peaks is None or not seconds or not tc.get("n_experts"):
        return None
    batch, seq = int(run.traffic["batch"]), int(run.traffic["seq"])
    least = max(
        experts_flops_per_step(tc, batch, seq) / run.peaks["bf16_flops_per_s"],
        experts_bytes_per_step(tc, batch, seq) / run.peaks["hbm_bytes_per_s"],
    )
    chips = int(run.config["layout"]["chips_per_group"])
    return 100.0 * least / (seconds * chips)
