"""The host's weather as the window opens: seconds one ``np.copyto`` of a fixed
256 MB array takes in the worker (``host_probe.py``: the median of three, into
a buffer touched before), taken after the last warm-up commit. The FT step is
paced by such copies (2.43 GB a step through ``exchange.pack``); a ledger line
whose step moved shows here whether the machine's memory was slower too.
Mean over groups (at four groups the four probes run at once, as their packs
do)."""

NAME, UNIT, SOURCE = "host_probe_copy_s", "s", "host_clock"
LAYER = "gradient exchange (host path)"
MOVES = "step_p50_s"


def compute(run):
    return run.per_group_mean([
        ((r.get("host_probe") or {}).get("open") or {}).get("copy_s") for r in run.results
    ])
