#!/usr/bin/env python3
"""Run one cell the way the driver's check does, and keep what each run saw.

    python3 benchmark/tools/driver_order.py --workload <cell> --checkouts .bench_scratch/P .bench_scratch/C \
        --positions PCCPPCCPPCCP --seed 7000 --out chiprun_out/step0/set1

Two checkouts of one tree ("P" and "C": unpack ``git archive`` twice into a
git-ignored directory), each with a ``HOME``, ``XDG_CACHE_HOME`` and ``TMPDIR`` of
its own (and, unless the machine names one cache directory for all, its own
compile cache). Each side's first run, which may compile, is kept apart
(``cold``); then, at each position of ``--positions``, that side makes one
``--trace 1`` run and one ``--trace 0`` run, every run on a fresh seed. After
each run the workers' ``result.<group>.json`` (stamps, units, LEDGER rows, the
program's exchange spans, ``host_probe``), the result line, the processes alive
before and after it and, where ``--keep-xplane`` names its side, a traced run's
``.xplane.pb`` go to ``--out`` as ``<n>.<side>.t<trace>.json`` (``.xplane.pb.gz``).
``--summary`` prints a directory of those. It never touches JAX: a chip belongs
to the run.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import statistics
import subprocess
import sys
import time



def processes() -> list:
    """Every process alive but this one, ``pid:command``."""
    out = []
    for pid in sorted(int(p) for p in os.listdir("/proc") if p.isdigit()):
        if pid == os.getpid():
            continue
        try:
            with open(f"/proc/{pid}/cmdline", "rb") as f:
                cmd = f.read().replace(b"\0", b" ").decode(errors="replace").strip()
        except OSError:
            continue
        if cmd:
            out.append(f"{pid}:{cmd[:120]}")
    return out


def meminfo() -> dict:
    out = {}
    with open("/proc/meminfo") as f:
        for line in f:
            key, _, rest = line.partition(":")
            if key in ("Dirty", "Writeback", "AnonHugePages", "MemFree", "Cached"):
                out[key] = int(rest.split()[0])
    return out


def one_run(checkout: str, side: str, args, seed: int, trace: int, seconds: float, out_path: str, cold: bool = False) -> dict:
    home = os.path.abspath(os.path.join(checkout, ".home"))
    env = dict(os.environ, HOME=home, XDG_CACHE_HOME=os.path.join(home, ".cache"), TMPDIR=os.path.join(home, "tmp"), BENCH_RUN=f"{side}-{seed}")
    for d in (env["XDG_CACHE_HOME"], env["TMPDIR"]):
        os.makedirs(d, exist_ok=True)
    before = {"t": time.monotonic(), "meminfo": meminfo(), "processes": processes()}
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", args.workload, "--seed", str(seed),
         "--seconds", str(seconds), "--trace", str(trace)] + args.extra,
        cwd=checkout, env=env, capture_output=True, text=True, timeout=1500,
    )
    after = {"t": time.monotonic(), "meminfo": meminfo(), "processes": processes()}
    record = {"side": side, "seed": seed, "trace": trace, "seconds": seconds, "cold": cold, "rc": proc.returncode,
              "before": before, "after": after, "wall_s": after["t"] - before["t"]}
    lines = proc.stdout.strip().splitlines()
    record["log"] = [l for l in lines if l.startswith("[bench]")][-12:]
    try:
        record["line"] = json.loads(lines[-1])
    except (ValueError, IndexError):
        record["line"] = None
        record["stderr"] = proc.stderr[-3000:]
    run_dir = os.path.join(checkout, "benchmark_runs", args.workload)
    record["results"] = []
    for path in sorted(glob.glob(os.path.join(run_dir, "result.*.json"))):
        with open(path) as f:
            r = json.load(f)
        r.pop("compile_log", None)
        record["results"].append(r)
        xplane = (r.get("trace") or {}).get("xplane")
        if xplane and os.path.exists(xplane):
            record.setdefault("xplane_bytes", []).append(os.path.getsize(xplane))
            if args.keep_xplane and side + str(trace) in args.keep_xplane:
                subprocess.run(["gzip", "-k", "-1", xplane], check=False)
                os.replace(xplane + ".gz", out_path[:-5] + ".xplane.pb.gz")
    with open(out_path, "w") as f:
        json.dump(record, f)
    return record


def brief(rec: dict) -> str:
    m = (rec.get("line") or {}).get("metrics", {})
    vals = {k: round(v["value"], 5) for k, v in m.items() if k in ("step_p50_s", "setup_s", "tokens_per_s", "exchange_s", "exchange_pack_s", "optimizer_roofline")}
    hp = (rec["results"][0].get("host_probe") or {}) if rec.get("results") else {}
    probes = {at: (round(p["copy_s"], 4), round(p["spin_s"], 4)) for at, p in hp.items()}
    return f"{rec['side']} t{rec['trace']} seed {rec['seed']} rc {rec['rc']} wall {rec['wall_s']:.0f}s {vals} probe {probes}"


def summary(out_dir: str) -> None:
    recs = []
    for path in sorted(glob.glob(os.path.join(out_dir, "*.json")), key=lambda p: int(os.path.basename(p).split(".")[0])):
        with open(path) as f:
            recs.append(json.load(f))
    steps = [r["line"]["metrics"]["step_p50_s"]["value"] for r in recs if not r["trace"] and r.get("line") and not r.get("cold")]
    if len(steps) >= 4:
        q = statistics.quantiles(steps, n=4)
        print(f"untraced step_p50_s: n {len(steps)} median {statistics.median(steps):.5f} Q1 {q[0]:.5f} Q3 {q[2]:.5f} spread {(q[2] - q[0]) / statistics.median(steps):.4f}; slow (> 1.10 x Q1): {sum(s > 1.10 * q[0] for s in steps)}")
    for r in recs:
        print(("cold " if r.get("cold") else "") + brief(r))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload")
    ap.add_argument("--checkouts", nargs=2, metavar=("P", "C"))
    ap.add_argument("--positions", default="PCCPPCCPPCCP")
    ap.add_argument("--seed", type=int, default=7000, help="the first seed; each run takes the next")
    ap.add_argument("--seconds", type=float, default=51)
    ap.add_argument("--traced-seconds", type=float, default=20)
    ap.add_argument("--out", required=True)
    ap.add_argument("--keep-xplane", nargs="*", default=[], help="e.g. P1 C1: bring the raw traces of those sides' traced runs back, gzipped")
    ap.add_argument("--between", default="", help="a shell command run between two runs (e.g. sync)")
    ap.add_argument("--skip-cold", action="store_true")
    ap.add_argument("--untraced-only", action="store_true")
    ap.add_argument("--extra", nargs="*", default=[], help="further arguments for run.py (--rehearse)")
    ap.add_argument("--summary", action="store_true", help="only print the table of --out")
    args = ap.parse_args()
    if args.summary:
        summary(args.out)
        return 0
    os.makedirs(args.out, exist_ok=True)
    checkouts = dict(zip("PC", args.checkouts))
    seed, n = args.seed, 0

    def run(side, trace, seconds, cold=False):
        nonlocal seed, n
        rec = one_run(checkouts[side], side, args, seed, trace, seconds, os.path.join(args.out, f"{n}.{side}.t{trace}.json"), cold)
        print(("cold " if cold else "") + brief(rec), flush=True)
        seed += 1
        n += 1
        if args.between:
            t0 = time.monotonic()
            subprocess.run(args.between, shell=True, check=False)
            print(f"between: {args.between!r} took {time.monotonic() - t0:.2f} s", flush=True)

    if not args.skip_cold:
        for side in "PC":
            run(side, 0, 12, cold=True)
    for side in args.positions:
        if not args.untraced_only:
            run(side, 1, args.traced_seconds)
        run(side, 0, args.seconds)
    summary(args.out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
