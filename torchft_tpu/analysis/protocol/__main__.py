"""CLI: ``python -m torchft_tpu.analysis.protocol``.

Two halves, one exit code (premerge gate [4]):

* **model check** (default) — exhaustively explore every gate
  configuration (:data:`~torchft_tpu.analysis.protocol.checker.GATE_CONFIGS`)
  with a crash injected at every transition point; any invariant
  violation prints its action trace and fails the gate.
* **conformance replay** (``--conformance DIR``, repeatable) — replay
  every event trail / black box under DIR against the spec's event-level
  transition rules; any illegal transition fails the gate.

Exit codes: 0 clean, 1 violations/illegal transitions, 2 crash.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from typing import List, Optional

from torchft_tpu.analysis.protocol.checker import (
    GATE_CONFIGS,
    HA_STATE_BUDGETS,
    check,
)
from torchft_tpu.analysis.protocol.conformance import check_tree


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="torchft_tpu.analysis.protocol",
        description="FT-protocol verification gate: exhaustive bounded "
        "model check + trace-conformance replay",
    )
    ap.add_argument("--conformance", action="append", default=[],
                    metavar="DIR",
                    help="also replay every trail/black box under DIR "
                    "(repeatable)")
    ap.add_argument("--config", action="append", default=None,
                    choices=sorted(GATE_CONFIGS),
                    help="model-check only these gate configs")
    ap.add_argument("--skip-model", action="store_true",
                    help="conformance replay only")
    ap.add_argument("--no-por", action="store_true",
                    help="disable partial-order reduction (exhaustive "
                    "reference mode half 1)")
    ap.add_argument("--no-symmetry", action="store_true",
                    help="disable symmetry reduction (reference half 2)")
    ap.add_argument("--bitstate", action="store_true",
                    help="64-bit bitstate hashing: cheaper visited set, "
                    "APPROXIMATE coverage — never a gate verdict")
    ap.add_argument("--max-states", type=int, default=None,
                    help="override the per-config state budget")
    ap.add_argument("--json", action="store_true", dest="as_json")
    args = ap.parse_args(argv)

    report = {"model": {}, "conformance": {}, "ok": True}
    try:
        if not args.skip_model:
            names = args.config or sorted(GATE_CONFIGS)
            for name in names:
                t0 = time.time()
                budget = args.max_states or HA_STATE_BUDGETS.get(
                    name, 2_000_000
                )
                res = check(
                    GATE_CONFIGS[name],
                    max_states=budget,
                    por=not args.no_por,
                    symmetry=not args.no_symmetry,
                    bitstate=args.bitstate,
                )
                report["model"][name] = {
                    "states": res.states,
                    "transitions": res.transitions,
                    "budget": budget,
                    "violations": [
                        {"invariant": v.invariant, "detail": v.detail,
                         "trace": v.trace}
                        for v in res.violations
                    ],
                    "truncated": res.truncated,
                    "truncated_states": res.truncated_states,
                    "truncated_transitions": res.truncated_transitions,
                    "approximate": res.approximate,
                    "seconds": round(time.time() - t0, 2),
                }
                if not args.as_json:
                    print(
                        f"model {name}: {res.states} states, "
                        f"{res.transitions} transitions, "
                        f"{len(res.violations)} violation(s) "
                        f"[{report['model'][name]['seconds']}s]"
                    )
                    if res.truncated:
                        print(
                            f"  TRUNCATED: budget {budget} hit — "
                            f"{res.truncated_states} frontier state(s) "
                            f"and {res.truncated_transitions} enabled "
                            "action(s) never explored; NOT a clean "
                            "verdict"
                        )
                    if res.approximate:
                        print(
                            "  APPROXIMATE: bitstate hashing on — a "
                            "hash collision silently prunes coverage; "
                            "exploratory only, never a gate verdict"
                        )
                    for v in res.violations:
                        print("  " + v.render())
                report["ok"] = report["ok"] and res.ok
        for root in args.conformance:
            rep = check_tree(root)
            report["conformance"][root] = {
                "sources": rep.sources,
                "records": rep.records,
                "lifecycle_records": rep.lifecycle_records,
                "findings": [f.__dict__ for f in rep.findings],
            }
            if not args.as_json:
                print(rep.render())
            report["ok"] = report["ok"] and rep.ok
    except Exception as e:  # noqa: BLE001 — checker crash is exit 2
        print(f"protocol gate failed: {type(e).__name__}: {e}",
              file=sys.stderr)
        return 2

    if args.as_json:
        print(json.dumps(report, indent=2))
    elif report["ok"]:
        print("protocol gate clean")
    return 0 if report["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
