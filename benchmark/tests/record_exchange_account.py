#!/usr/bin/env python3
"""How ``benchmark/tests/recorded_exchange_account_v5e.xplane.pb`` was made:
ONE group's trace of the four-group cell's rehearsal (``run.py --workload
olmo1b-4g.ft-steady --rehearse --trace 1``: the cell's own launcher, workers
and ``ft`` loop at ``rehearsal.json``'s tiny size) on the four-chip v5e host —
four groups of one chip each, world size 4, plane ``cma``, 64 KB buckets so
that a step has two rings. Run on that host; copies group 0's trace to the
directory given and prints its ``tft.exchange*`` events by thread, which is
what ``exchange_account.py`` and its readers were written against, then the
rehearsal's own last line.

    python3 benchmark/tests/record_exchange_account.py chiprun_out/recorded

The parent process stays off JAX until the workers have exited: a chip belongs
to one process at a time.
"""

import os
import shutil
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
CELL = "olmo1b-4g.ft-steady"
NAME = "recorded_exchange_account_v5e.xplane.pb"


def main(out_dir: str) -> int:
    env = dict(os.environ, TORCHFT_WIRE_BUCKET_BYTES=str(1 << 16))
    run = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL, "--rehearse",
         "--trace", "1", "--seed", "2147489038", "--seconds", "4"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=1200,
    )
    if run.returncode != 0:
        print(run.stdout[-4000:], run.stderr[-4000:], sep="\n")
        return run.returncode
    sys.path[:0] = [BENCH]
    import reduce_trace

    path = reduce_trace.find_xplane(os.path.join(ROOT, "benchmark_runs", CELL, "trace.0"))
    os.makedirs(out_dir, exist_ok=True)
    dst = os.path.join(out_dir, NAME)
    shutil.copy(path, dst)
    print("wrote", dst, os.path.getsize(dst), "bytes")

    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(dst).planes:
        print("PLANE", plane.name, [(ln.name, len(list(ln.events))) for ln in plane.lines])
        for i, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith(("tft.exchange", "bench_unit")):
                    print("  ", i, line.name, ev.name, ev.start_ns, ev.duration_ns, dict(ev.stats))
    print(run.stdout.splitlines()[-1])
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
