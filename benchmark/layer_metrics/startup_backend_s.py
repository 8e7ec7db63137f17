"""Exec of a worker -> its backend is up (``jax.devices()`` returned): the
slowest group. Import time is in it; no cache covers backend init."""

NAME, UNIT, SOURCE = "startup_backend_s", "s", "host_clock"
LAYER = "launcher and worker start"
MOVES = "setup_s"


def compute(run):
    return max(r["stamps"]["devices"] - r["stamps"]["start"] for r in run.results)
