#!/usr/bin/env python3
"""A scope's device seconds by op, from a traced benchmark run: which ops hold
the time of ``attn/kda/kda_core`` (or of any ``jax.named_scope`` named with
``--scope``), split by pass — the layer's forward, the forward a
``jax.checkpoint`` runs again, the backward — and by the path below the scope
and the op's result shape. PERF.md §6 (PR 36) holds the table this printed
for the ``jax.numpy`` core and for the kernel.

    python3 benchmark/run.py --workload kimi-linear-1g.fused-s8192 --seed 7 --seconds 51 --trace 1
    python scripts/kda_core_by_op.py            # the newest trace under benchmark_runs/

SELF seconds (an op's time less the ops nested in it, ``reduce_trace._self_times``)
over every step the trace holds, given per step by ``--steps-per-unit`` times
the traced units; the shares do not depend on that count.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.join(ROOT, "benchmark"), ROOT]

import reduce_trace  # noqa: E402
import subscopes  # noqa: E402


def which_pass(op_name: str) -> str:
    # the forward a ``jax.checkpoint`` runs again sits inside the backward's
    # own scope: look for it first
    if "rematted_computation" in op_name:
        return "forward again"
    return "backward" if "transpose(" in op_name else "forward"


def below(op_name: str, scope: str) -> str:
    """The path under the innermost ``scope``, without JAX's wrappers."""
    parts = op_name.split("/")
    at = max(i for i, p in enumerate(parts) if p.rsplit("(", 1)[-1].rstrip(")") == scope)
    return "/".join(p.rsplit("(", 1)[-1].rstrip(")") for p in parts[at + 1 :])


def table(path: str, scope: str, per: int):
    chips, starts_of_units, _ = subscopes._trace(path)
    steps = max(len(starts_of_units), 1) * per
    rows = {}
    for ops, modules, op_names in chips:
        by_event = {}
        for (program, name), op_name in op_names.items():
            by_event.setdefault(name, op_name)
        events = [(s, e, (name, by_event.get(name))) for s, e, name in ops]
        for (name, op_name), sec in reduce_trace._self_times(events).items():
            if not op_name or scope not in [p.rsplit("(", 1)[-1].rstrip(")") for p in op_name.split("/")]:
                continue
            key = (which_pass(op_name), below(op_name, scope), reduce_trace.short_op_name(name))
            row = rows.setdefault(key, [0.0, 0, op_name])
            row[0] += sec / len(chips)
            row[1] += 1
    total = sum(r[0] for r in rows.values())
    out = [
        {"pass": k[0], "below": k[1], "op": k[2], "op_name": r[2], "s_per_step": r[0] / steps, "share_pct": 100 * r[0] / total, "kinds": r[1]}
        for k, r in sorted(rows.items(), key=lambda kv: -kv[1][0])
    ]
    return {"xplane": path, "scope": scope, "steps": steps, "s_per_step": total / steps, "rows": out}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("xplane", nargs="?")
    ap.add_argument("--scope", default="kda_core")
    ap.add_argument("--steps-per-unit", type=int, default=5)
    ap.add_argument("--rows", type=int, default=40)
    ap.add_argument("--out", default=os.path.join("chiprun_out", "kda_core_by_op.json"))
    args = ap.parse_args()
    path = args.xplane
    if path is None:
        found = sorted(glob.glob(os.path.join(ROOT, "benchmark_runs", "**", "*.xplane.pb"), recursive=True), key=os.path.getmtime)
        if not found:
            sys.exit("no *.xplane.pb under benchmark_runs/: run a cell with --trace 1 first")
        path = found[-1]
    got = table(path, args.scope, args.steps_per_unit)
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(got, f, indent=1)
    print(f"{got['scope']}: {got['s_per_step']:.4f} s a step over {got['steps']} steps ({path})")
    by_pass, by_path = {}, {}
    for r in got["rows"]:
        by_pass[r["pass"]] = by_pass.get(r["pass"], 0.0) + r["s_per_step"]
        lead = (r["pass"], r["below"])
        by_path[lead] = by_path.get(lead, 0.0) + r["s_per_step"]
    print("by pass:", {k: round(v, 4) for k, v in by_pass.items()})
    print("by pass and path:")
    for (p, b), v in sorted(by_path.items(), key=lambda kv: -kv[1])[: args.rows]:
        print(f"  {v:9.5f} s  {p:14s} {b}")
    print("by op:")
    for r in got["rows"][: args.rows]:
        print(f"  {r['s_per_step']:9.5f} s {r['share_pct']:5.1f} %  {r['pass']:14s} {r['below'][:60]:60s} {r['op']}")


if __name__ == "__main__":
    main()
