"""Seconds the set-up's bootstrap heal took on the slowest healer: the
``duration_s`` of the ``heal_end`` events the program emitted before the
window. At step 0 every group but one fetches the whole replica state from
the bootstrap source; the stages are on an earlier line of the output."""

NAME, UNIT, SOURCE = "bootstrap_heal_s", "s", "program_span"
LAYER = "checkpoint transport (heal)"
MOVES = "setup_s"


def compute(run):
    durations = [
        e["duration_s"] for r in run.results for e in r.get("heal_events") or []
        if e.get("duration_s") is not None
    ]
    return max(durations) if durations else None
