"""Tokens a second over the window, as ``tokens_per_s`` counts them
(``measure.py``: the committed steps' tokens over the time from the first to
the last commit stamp; in this traced run the stretch after the profiler
stopped). In a cell whose host path shares its cores with other machines'
work the rate spreads too widely between runs to carry a bound (PERF.md §2);
there the median step is the end-to-end metric and the rate stands here."""

from measure import tokens_per_s

NAME, UNIT, SOURCE = "window_tokens_per_s", "tokens/s", "host_clock"
LAYER = "ft loop"
MOVES = "step_p50_s"


def compute(run):
    return tokens_per_s(run.results, skip_traced=True)
