"""From the workers' stamps to numbers: the arithmetic every metric of the
benchmark shares. Part of the yardstick — no program code is imported here.

A worker's result holds ``units``: one row per unit of its loop (one
``FTTrainer.step``, or one block of fused steps that ends in
``block_until_ready``) with host-clock ``t_start`` / ``t_end``
(``CLOCK_MONOTONIC``, shared by all processes of the host) and one entry per
step (``loss``, ``committed``, ``participants``). A unit's ``t_end`` is the
commit stamp of its steps.
"""

from __future__ import annotations

import math
from typing import Any, Dict, List, Optional, Sequence, Tuple

class NotEnoughCommits(Exception):
    pass


def median(values: Sequence[float]) -> Optional[float]:
    vs = sorted(values)
    if not vs:
        return None
    mid = len(vs) // 2
    return vs[mid] if len(vs) % 2 else (vs[mid - 1] + vs[mid]) / 2


def window_units(result: Dict[str, Any]) -> List[Dict[str, Any]]:
    """Units whose commit stamp lies inside the window."""
    w = result["window"]
    return [u for u in result["units"] if w["open"] <= u["t_end"] <= w["close"]]


def _rate(units: List[Dict[str, Any]], tokens_per_step: int) -> Tuple[float, int]:
    """(tokens/s, intervals) from the first to the last commit stamp of
    ``units``: the tokens of every later unit's committed steps over the
    time since the first stamp — a window that cuts a unit does not
    quantise it."""
    if len(units) < 2:
        return 0.0, 0
    tokens = sum(
        tokens_per_step * sum(1 for s in u["steps"] if s["committed"])
        for u in units[1:]
    )
    return tokens / (units[-1]["t_end"] - units[0]["t_end"]), len(units) - 1


def tokens_per_s(results: Sequence[Dict[str, Any]], skip_traced: bool = False) -> float:
    """Tokens of the steps committed inside the window over the time from
    the first to the last commit inside it, summed over groups. Fewer than
    two commit-to-commit intervals in any group is an error, not a number.

    ``skip_traced`` (a traced run): only the stretch after the profiler
    stopped counts, so that starting and stopping the trace is not read as
    step time; if that stretch holds fewer than two intervals the whole
    window is used."""
    total = 0.0
    for r in results:
        units = window_units(r)
        rate, n = _rate(units, r["tokens_per_step"])
        if n < 2:
            raise NotEnoughCommits(
                f"group {r['group']}: {n} commit-to-commit interval(s) inside "
                f"the window of {r['window']['close'] - r['window']['open']:.0f} s; "
                "two are the least a rate can rest on"
            )
        t1 = (r.get("trace_window") or {}).get("t1")
        if skip_traced and t1 is not None:
            after = [u for u in units if u["t_start"] >= t1]
            rate_after, n_after = _rate(after, r["tokens_per_step"])
            if n_after >= 2:
                rate = rate_after
        total += rate
    return total


def step_p50_s(results: Sequence[Dict[str, Any]]) -> float:
    """Median time of a step inside the window: per group the median over its
    commit-to-commit intervals (every unit whose commit stamp lies inside the
    window, committed or not) of the interval over the unit's steps, mean over
    groups. The same stamps ``tokens_per_s`` rests on, all of them; a stall
    of the host that lengthens a few steps moves it little, a slower step
    moves it in full."""
    per_group = []
    for r in results:
        units = window_units(r)
        steps = [(b["t_end"] - a["t_end"]) / len(b["steps"]) for a, b in zip(units, units[1:])]
        if len(steps) < 2:
            raise NotEnoughCommits(f"group {r['group']}: {len(steps)} commit-to-commit interval(s) inside the window")
        per_group.append(median(steps))
    return sum(per_group) / len(per_group)


# what a ``--trace 0`` run may report beside ``setup_s``: name -> (function of
# the groups' results, unit). BENCHMARK.json's ``end_to_end`` says which of
# them a cell reports (an entry's ``workloads`` key; absent: every cell).
END_TO_END = {
    "tokens_per_s": (tokens_per_s, "tokens/s"),
    "step_p50_s": (step_p50_s, "s"),
}


def attempted_failed(results: Sequence[Dict[str, Any]]) -> Tuple[int, int]:
    """Steps started inside the window, and those of them that did not
    commit, over all groups."""
    attempted = failed = 0
    for r in results:
        w = r["window"]
        for u in r["units"]:
            if w["open"] <= u["t_start"] < w["close"]:
                attempted += len(u["steps"])
                failed += sum(1 for s in u["steps"] if not s["committed"])
    return attempted, failed


def losses_finite(results: Sequence[Dict[str, Any]]) -> bool:
    return all(
        math.isfinite(s["loss"])
        for r in results for u in window_units(r) for s in u["steps"]
    )


def compiles_in_window(results: Sequence[Dict[str, Any]]) -> List[str]:
    out = []
    for r in results:
        w = r["window"]
        out += [
            f"g{r['group']}: {msg}" for t, msg in r["compile_log"]
            if msg.startswith("Compiling ") and w["open"] <= t <= w["close"]
        ]
    return out


# a worker's stamps in order, and what the stretch that ends at each is
SETUP_STAGES = (
    ("start", "launch"), ("imports", "imports"), ("devices", "backend"), ("state", "state"),
    ("reference", "reference"), ("ready", "warmup"),
)


def setup_stages(results: Sequence[Dict[str, Any]], t_exec: float, t_open: float) -> Dict[str, float]:
    """``setup_s`` split at the workers' stamps, each the slowest group's:
    exec of ``run.py`` -> the workers run (launcher, lighthouse) -> imports ->
    ``jax.devices()`` -> state placed -> reference check -> warm-up commits
    (the first program load is here) -> the parent opens the window."""
    out, prev = {}, t_exec
    for stamp, name in SETUP_STAGES:
        t = max(r["stamps"][stamp] for r in results)
        out[name + "_s"] = t - prev
        prev = t
    out["open_s"] = t_open - prev
    return out


class Run:
    """What a per-layer metric's ``compute(run)`` may read."""

    def __init__(self, cell, config, traffic, peaks, results):
        self.cell = cell            # the BENCHMARK.json workload entry
        self.config = config        # the configuration file
        self.traffic = traffic      # the traffic file
        self.peaks = peaks          # peaks.json entry of this device_kind, or None
        self.results = results      # one dict per group, see worker.py

    def per_group_mean(self, values: Sequence[Optional[float]]) -> Optional[float]:
        vs = [v for v in values if v is not None]
        return sum(vs) / len(vs) if vs else None

    def ledger_phase_median(self, phases: Sequence[str]) -> Optional[float]:
        """Median over the window's committed steps of the summed LEDGER
        phases, mean over groups. The LEDGER's rows are the program's own
        (host clock); a row's ``step`` is the manager's step before the
        commit, a unit's is the step after it."""
        per_group = []
        for r in self.results:
            steps = {
                s["step"] - 1
                for u in window_units(r) for s in u["steps"] if s["committed"]
            }
            vals = [
                sum(row["phases"].get(p, 0.0) for p in phases)
                for row in r.get("ledger_rows") or [] if row["step"] in steps
            ]
            per_group.append(median(vals))
        return self.per_group_mean(per_group)

    def span_median(self, name: str) -> Optional[float]:
        """Median duration of the wrapper span ``name`` inside the window,
        mean over groups (traced runs only: the wrappers exist only there)."""
        per_group = []
        for r in self.results:
            w = r["window"]
            vals = [t1 - t0 for n, t0, t1 in r.get("spans") or [] if n == name and w["open"] <= t0 <= w["close"]]
            per_group.append(median(vals))
        return self.per_group_mean(per_group)
