#!/usr/bin/env python3
"""The benchmark's one command:

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout. It reads the cell from ``BENCHMARK.json``, its
configuration from ``benchmark/configs/`` and its traffic from
``benchmark/traffic/``, starts the job through the program's normal entry —
``python -m torchft_tpu.launcher --groups G -- python benchmark/worker.py``,
one process per replica group, chips handed out by the launcher — waits
until every group is warm, opens the window (one ``CLOCK_MONOTONIC``
instant written to the run directory), and ``--seconds`` later the workers
finish the step in flight and report. The last line of standard output is
the result, one JSON object; anything that would hide the device, a
compilation inside the window, or fewer than two commit intervals is a
non-zero exit that names the reason and prints no result.

This parent never imports JAX: a chip belongs to one process, and a parent
that had touched it would hold what its workers need. There is no per-cell
branch here: a configuration, a traffic mix, a loop, a per-layer metric and
an event handler are files of their own, found by name.
"""

from __future__ import annotations

import time

T_EXEC = time.monotonic()  # set-up is counted from here

import argparse
import glob
import json
import os
import shutil
import signal
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [HERE, ROOT]
from common import load_json, load_module, write_atomic  # noqa: E402

RUNS = os.path.join(ROOT, "benchmark_runs")  # git-ignored, inside the checkout
COLD_LIMIT_S = 1100.0  # a first run in a checkout compiles; the contract allows 1200 s


class Refused(Exception):
    """The run cannot give a result; the message is the reason."""


def resolve_cell(workload: str, rehearse: bool):
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise Refused(f"no workload {workload!r} in BENCHMARK.json (has: {sorted(cells)})")
    cell = cells[workload]
    cfg_entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = load_json(os.path.join(ROOT, cfg_entry["file"]))
    traffic = load_json(os.path.join(HERE, "traffic", cell["traffic"] + ".json"))
    rehearsal = None
    if rehearse:
        # a tiny size on whatever backend there is, to rehearse the control
        # flow; the result line then names that backend and is no result
        rehearsal = load_json(os.path.join(HERE, "tests", "rehearsal.json"))
        config["program"]["transformer_config"].update(rehearsal["transformer_config"])
        # sizes only this configuration has (latent ranks, a second head size,
        # experts held) are made tiny by its own file, after the shared seven
        config["program"]["transformer_config"].update((config.get("rehearsal") or {}).get("transformer_config", {}))
        traffic.update(rehearsal["traffic"])
    check_events(traffic.get("events", []))
    return bench, cell, config, traffic, rehearsal


def check_events(events) -> None:
    """A traffic event needs its handler file; none exists yet."""
    for ev in events:
        if not {"at_s", "do"} <= set(ev):
            raise Refused(f"traffic event {ev!r} needs 'at_s' and 'do'")
        if not os.path.exists(os.path.join(HERE, "events", ev["do"] + ".py")):
            raise Refused(f"traffic event {ev['do']!r}: not implemented (no benchmark/events/{ev['do']}.py)")


def tail(path: str, n: int = 40) -> str:
    try:
        with open(path, errors="replace") as f:
            lines = [l.rstrip()[:300] for l in f if "jax._src" not in l]
    except OSError:
        return "(no log)"
    return "\n".join(lines[-n:])


def stop(proc: subprocess.Popen) -> None:
    """End the launcher and everything it started, and wait for it."""
    if proc.poll() is None:
        try:
            os.killpg(proc.pid, signal.SIGTERM)
            proc.wait(timeout=10)
        except (OSError, subprocess.TimeoutExpired):
            pass
    try:
        os.killpg(proc.pid, signal.SIGKILL)
    except OSError:
        pass
    proc.wait()


def drive(args, cell, config, traffic, rehearsal, run_dir):
    """Start the job, open and close the window; returns (results, t_open)."""
    if not os.path.isdir(os.path.join(ROOT, "torchft_tpu")):
        raise Refused(f"the program (torchft_tpu/) is not in this checkout: {ROOT}")
    from torchft_tpu.utils.compile_cache import place_compile_cache  # jax-free

    cache_dir = place_compile_cache()  # the workers inherit it
    groups = int(config["layout"]["groups"])
    with open(os.path.join(run_dir, "cell.json"), "w") as f:
        json.dump(
            {
                "workload": cell["name"], "config": config, "traffic": traffic,
                "seed": args.seed, "seconds": args.seconds, "trace": args.trace,
                "rehearsal": rehearsal, "setup_deadline_s": COLD_LIMIT_S - 100,
            },
            f,
        )
    env = dict(os.environ)
    env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
    env["PYTHONUNBUFFERED"] = "1"
    env["JAX_LOG_COMPILES"] = "1"  # compilations, with their seconds, in the workers' logs
    env["JAX_EXPLAIN_CACHE_MISSES"] = "1"
    cmd = [
        sys.executable, "-m", "torchft_tpu.launcher", "--groups", str(groups),
        "--max-restarts", "0", "--",
        sys.executable, os.path.join(HERE, "worker.py"), "--run-dir", run_dir,
    ]
    log_path = os.path.join(run_dir, "launcher.log")
    print(f"[bench] {cell['name']}: {groups} group(s), compile cache {cache_dir}, logs {log_path}", flush=True)
    with open(log_path, "w") as log:
        proc = subprocess.Popen(
            cmd, env=env, cwd=ROOT, stdout=log, stderr=subprocess.STDOUT,
            start_new_session=True,
        )
    try:
        def check_alive(what: str) -> None:
            errors = sorted(glob.glob(os.path.join(run_dir, "error.*")))
            if errors:
                with open(errors[0]) as f:
                    raise Refused(f"{os.path.basename(errors[0])}: {f.read()}")
            if proc.poll() is not None and what != "results":
                raise Refused(f"the launcher exited with {proc.returncode} while {what}\n--- {log_path}\n{tail(log_path)}")

        # -- set-up: every group warm and checked against the reference
        while len(glob.glob(os.path.join(run_dir, "ready.*"))) < groups:
            check_alive("the groups warmed up")
            if time.monotonic() - T_EXEC > COLD_LIMIT_S:
                raise Refused(f"set-up did not finish within {COLD_LIMIT_S:.0f} s\n--- {log_path}\n{tail(log_path)}")
            time.sleep(0.01)
        t_open = time.monotonic() + 0.01
        write_atomic(
            os.path.join(run_dir, "window.json"),
            json.dumps({"open": t_open, "close": t_open + args.seconds}),
        )

        # -- the window: traffic events fire from here, by their handlers
        pending = sorted(traffic.get("events", []), key=lambda ev: ev["at_s"])
        while time.monotonic() < t_open + args.seconds:
            check_alive("the window was open")
            while pending and time.monotonic() >= t_open + pending[0]["at_s"]:
                ev = pending.pop(0)
                load_module(os.path.join(HERE, "events", ev["do"] + ".py"), "bench_event").fire(ev, run_dir, proc)
            time.sleep(0.05)

        # -- the workers finish the step in flight and report
        limit = time.monotonic() + 240
        while proc.poll() is None:
            check_alive("results")
            if time.monotonic() > limit:
                raise Refused(f"the workers did not finish within 240 s of the window's end\n--- {log_path}\n{tail(log_path)}")
            time.sleep(0.05)
        check_alive("results")
        if proc.returncode != 0:
            raise Refused(f"the launcher exited with {proc.returncode}\n--- {log_path}\n{tail(log_path)}")
    finally:
        stop(proc)
    results = [load_json(os.path.join(run_dir, f"result.{g}.json")) for g in range(groups)]
    return results, t_open


def report(args, bench, cell, config, traffic, results, t_open):
    import measure

    late = measure.compiles_in_window(results)
    if late:
        raise Refused(f"{len(late)} compilation(s) inside the window: {late[:3]}")
    device0 = results[0]["device"]
    peaks_all = load_json(os.path.join(HERE, "peaks.json"))
    peaks = peaks_all.get(device0["kind"]) if device0["platform"] == "tpu" else None
    run = measure.Run(cell, config, traffic, peaks, results)
    measure.tokens_per_s(results)  # refuses a window with < 2 commit intervals, whatever the cell reports
    attempted, failed = measure.attempted_failed(results)

    # earlier lines: what a reader checks across rows, and the metrics' context
    for r in results:
        first = r["units"][0]["steps"][0]
        print(
            f"[bench] g{r['group']}: step-1 loss {first['loss']:.6f}; reference check "
            f"max|diff| {r['reference']['max_abs_diff']:.3g} (atol {r['reference']['atol']}); "
            f"final step {r['final_step']} param_checksum {r['param_checksum']}; "
            f"plane {r.get('plane_info')}; exchange_bytes {r.get('exchange_bytes')}; "
            f"window units {len(measure.window_units(r))}",
            flush=True,
        )
        misses = sum(1 for _, m in r["compile_log"] if m.startswith("PERSISTENT COMPILATION CACHE MISS"))
        hits = sum(1 for _, m in r["compile_log"] if m.startswith("Persistent compilation cache hit"))
        print(f"[bench] g{r['group']}: persistent compile cache {hits} hit(s), {misses} miss(es)", flush=True)
        for e in r.get("heal_events") or []:
            print(f"[bench] g{r['group']}: heal_end {json.dumps(e)}", flush=True)
        probes = r.get("host_probe") or {}
        print(
            f"[bench] g{r['group']}: host_probe "
            + "; ".join(f"{at}: copy {p['copy_s']:.4f} s, spin {p['spin_s']:.4f} s" for at, p in probes.items()),
            flush=True,
        )
    stages = measure.setup_stages(results, T_EXEC, t_open)
    print("[bench] setup_s by stage: " + ", ".join(f"{k} {v:.2f}" for k, v in stages.items()), flush=True)

    same_state = len({(r["final_step"], r["param_checksum"]) for r in results}) == 1
    correct = bool(measure.losses_finite(results) and same_state)
    if not same_state:
        print("[bench] NOT CORRECT: the groups differ in step count or param_checksum", flush=True)

    metrics = {}
    if not args.trace:
        for m in bench["end_to_end"]:
            if m["name"] != "setup_s" and cell["name"] in m.get("workloads", [cell["name"]]):
                fn, unit = measure.END_TO_END[m["name"]]
                metrics[m["name"]] = {"value": fn(results), "unit": unit}
        metrics["setup_s"] = {"value": t_open - T_EXEC, "unit": "s"}
    else:
        listed = {m["name"]: m for m in bench["per_layer"]}
        for path in sorted(glob.glob(os.path.join(HERE, "layer_metrics", "*.py"))):
            mod = load_module(path, "bench_metric_" + os.path.basename(path)[:-3])
            entry = listed.get(mod.NAME)
            if entry is None or cell["name"] not in entry.get("workloads", [cell["name"]]):
                continue
            value = mod.compute(run)
            if value is not None:
                metrics[mod.NAME] = {"value": value, "unit": mod.UNIT}
    device = {
        "platform": device0["platform"], "kind": device0["kind"],
        "count": sum(r["device"]["count"] for r in results),
        "memory_peak_bytes": max(
            (m.get("peak_bytes_in_use", 0) for r in results for m in r["memory_stats"]), default=0
        ),
    }
    line = {"correct": correct, "attempted": attempted, "failed": failed, "metrics": metrics, "device": device}
    if args.trace:
        traces = [r["trace"] for r in results]
        device["busy_s"] = sum(t["busy_s"] for t in traces) / len(traces)
        device["window_s"] = sum(t["window_s"] for t in traces) / len(traces)

        def merged(key):
            acc = {}
            for t in traces:
                for name, sec in t[key]:
                    acc[name] = acc.get(name, 0.0) + sec / len(traces)
            return [[k, v] for k, v in sorted(acc.items(), key=lambda kv: -kv[1])[:10]]

        line["breakdown"] = {"device_ops": merged("device_ops"), "idle_gaps": merged("idle_gaps")}
    print(json.dumps(line), flush=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument(
        "--rehearse", action="store_true",
        help="tiny sizes (benchmark/tests/rehearsal.json) on whatever backend "
        "there is, to rehearse the control flow on the CPU; not a result",
    )
    args = ap.parse_args()
    try:
        bench, cell, config, traffic, rehearsal = resolve_cell(args.workload, args.rehearse)
        run_dir = os.path.join(RUNS, cell["name"])
        shutil.rmtree(run_dir, ignore_errors=True)
        os.makedirs(run_dir)
        results, t_open = drive(args, cell, config, traffic, rehearsal, run_dir)
        report(args, bench, cell, config, traffic, results, t_open)
    except Refused as e:
        print(f"[bench] REFUSED: {e}", file=sys.stderr, flush=True)
        return 1
    except Exception as e:  # noqa: BLE001 — e.g. NotEnoughCommits: a reason, not a result
        import traceback

        traceback.print_exc()
        print(f"[bench] REFUSED: {type(e).__name__}: {e}", file=sys.stderr, flush=True)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
