"""What the per-layer metrics of a named scope share: the scope's device
seconds a step from the reduced trace (``reduce_trace.reduce_planes``'s
``by_scope`` rows), and its share of its roofline from the configuration's count
(``opcount.for_config``: ``opcount.py``, or the file the configuration names).

A traced unit owns the program runs the host launched before the next unit's
start, so the ``optimizer`` of a step's ``apply``, which starts as the unit ends
and runs on into the next, counts to its own step. The last traced unit's may be cut
where the trace stops, so of several traced units the last is left out (a loop
that traces one unit blocks at its end: ``loops/fused.py``). A group's seconds
are the mean over its chips, and a sharded group's chips share the step's
operations and its state: a share divides by ``chips_per_group``.

The same rule owns the program runs themselves (``program_seconds``: what
``grads_device_s`` and ``apply_device_s`` read), and the scopes of a unit have to
add up to its programs: every op a unit owns runs inside a program run it owns
(``reduce_trace`` keeps what is left of a run launched before the trace began out
of every unit: given to the first unit by its start on the device's clock, 4.3 ms
of such an ``apply`` once read ``optimizer_roofline`` 131 %; ledger, PR 28). Where
they do not, part of a run went to another unit, a scope's seconds are short and
its roofline can pass 100 %: the readers then give None for every scope and say
why (``adds_up``).
"""

from __future__ import annotations

from typing import Optional, Sequence

import opcount
from measure import median


SUM_TOLERANCE = 0.005  # the scopes of a unit against its program runs


def whole(units):
    return units[:-1] if len(units) > 1 else units


def fault(run) -> Optional[str]:
    """Why the scopes of a traced unit do not add up to its program runs, or
    None where they do (or the rows carry no programs: nothing was promised)."""
    for r in run.results:
        for i, u in enumerate(whole((r.get("trace") or {}).get("units") or [])):
            if not u.get("programs"):
                continue
            scopes, programs = sum(u.get("by_scope", {}).values()), sum(u["programs"].values())
            if abs(scopes - programs) > SUM_TOLERANCE * programs:
                return (
                    f"group {r.get('group')}, traced unit {i}: the scopes add up to {scopes:.6f} s, "
                    f"its program runs {u['programs']} to {programs:.6f} s"
                )
    return None


def adds_up(run) -> bool:
    """:func:`fault` once a run, with its reason in the run's log."""
    if not hasattr(run, "scope_fault"):
        run.scope_fault = fault(run)
        if run.scope_fault:
            print(f"[bench] scope metrics left out: {run.scope_fault}", flush=True)
    return run.scope_fault is None


def program_seconds(run, program: str) -> Optional[float]:
    """Device seconds a step spends in the jitted program ``program`` (the
    ``XLA Modules`` events ``jit_<program>(<id>)`` of the runs its unit
    launched): median over the traced units but the last of several, mean
    over groups; None where no such unit ran it."""
    per = int(run.traffic["steps_per_unit"])
    name = "jit_" + program
    return run.per_group_mean([
        median([u["programs"][name] / per for u in whole(r["trace"].get("units") or []) if name in u.get("programs", {})])
        for r in run.results if r.get("trace")
    ])


def device_seconds(run, scope: str) -> Optional[float]:
    """Device seconds a step spends in ``scope``: median over the traced
    units but the last of several, mean over groups; None where no such unit
    ran an op of it, or the units' scopes do not add up (:func:`adds_up`)."""
    if not adds_up(run):
        return None
    per = int(run.traffic["steps_per_unit"])
    return run.per_group_mean([
        median([
            u["by_scope"][scope] / per
            for u in whole(r["trace"]["units"]) if scope in u.get("by_scope", {})
        ])
        for r in run.results if r.get("trace")
    ])


def device_seconds_of(run, scopes: Sequence[str]) -> Optional[float]:
    """Device seconds a step in ``scopes`` together (``ffn`` and ``moe`` of a
    model with a dense layer ahead of its expert layers): the sum over those
    that ran an op, None where none did."""
    found = [s for s in (device_seconds(run, scope) for scope in scopes) if s is not None]
    return sum(found) if found else None


def roofline(run, *scopes: str) -> Optional[float]:
    """The least time the published peaks allow ``scopes`` a step — for each
    its counted operations over the bf16 peak or its counted bytes over the HBM
    bandwidth, whichever is longer (the configuration's count,
    ``opcount.for_config``: what the algorithm needs, not what the program
    executes), summed: scopes run one after another — as a share of their
    device seconds together. None where a counted scope ran no op: its least
    time over the others' seconds would be no share of anything."""
    if run.peaks is None:
        return None  # a rehearsal on a device without published peaks
    tc = run.config["program"]["transformer_config"]
    batch, seq = int(run.traffic["batch"]), int(run.traffic["seq"])
    counts = opcount.for_config(run.config)
    flops, moved = counts.flops_per_token_by_scope(tc, seq), counts.bytes_per_step_by_scope(tc, batch, seq)
    least = seconds = 0.0
    for scope in scopes:
        ran = device_seconds(run, scope)
        needs = max(
            flops.get(scope, 0.0) * batch * seq / run.peaks["bf16_flops_per_s"],
            moved.get(scope, 0.0) / run.peaks["hbm_bytes_per_s"],
        )
        if needs and not ran:
            return None
        least, seconds = least + needs, seconds + (ran or 0.0)
    if not least or not seconds:
        return None  # a scope this configuration does not have
    chips = int(run.config["layout"]["chips_per_group"])
    return 100.0 * least / (seconds * chips)
