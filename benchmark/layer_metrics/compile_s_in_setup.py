"""Seconds JAX spent in XLA compilation (or in fetching a program from the
persistent cache) before the window opened, slowest group — from the
``Finished XLA compilation of ... in N sec`` lines JAX_LOG_COMPILES makes
JAX log. Misses of the persistent cache are printed on an earlier line."""

import re

NAME, UNIT, SOURCE = "compile_s_in_setup", "s", "program_span"
LAYER = "compile cache"
MOVES = "setup_s"

_FINISHED = re.compile(r"Finished XLA compilation of .* in ([0-9.eE+-]+) sec")


def compute(run):
    per_group = []
    for r in run.results:
        total = 0.0
        for t, msg in r["compile_log"]:
            m = _FINISHED.match(msg)
            if m and t <= r["window"]["open"]:
                total += float(m.group(1))
        per_group.append(total)
    return max(per_group)
