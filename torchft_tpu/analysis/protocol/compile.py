"""Trace→schedule compiler: lower model-checker action traces into
runnable faultinject schedules.

The checker names crash interleavings abstractly (``work(1)`` then
``crash(1)`` then ``resolve(0,r0,abort)``); the faultinject plane kills
real processes at named sites (``collective.issue`` nth=6 → SIGKILL).
This module is the bridge (ISSUE 20 tentpole part 3): every checker
trace — a counterexample from a broken config, or a sampled coverage
path from a clean one — compiles into the faultinject JSON schedule
grammar (site / match / nth / action) plus a scenario descriptor the
runner ingests (``python -m torchft_tpu.faultinject.runner --compiled``),
so the interleavings the checker explored symbolically are replayed
against the real system and re-judged by the conformance gate.

Lowering maps the victim's *protocol phase at death* onto the nearest
real injection coordinate (the runner's victim is group 1; the model
victim is the first crashed replica):

=====================  ====================================================
model position         fault rule
=====================  ====================================================
crashed mid-round      ``commit.vote`` match="prepare" nth=votes+1 — died
after working,         between contributing the collective and casting the
before voting          commit vote (the barrier-drain site)
crashed after voting   ``collective.issue`` match="allreduce" nth=works+1 —
                       the vote is on the wire; the nearest runnable hook
                       is entering the NEXT step's collective
crashed before         ``quorum.reply`` nth=rounds — died on the quorum
working                reply, before contributing anything
``work_corrupt(v)``    ``collective.complete`` match="allreduce" nth=works
                       action=corrupt frac=0.05, with the divergence
                       sentinel+fence armed (the fence vetoes the commit,
                       so the run still ends bit-identical)
``heal_fail(v)``       survivor schedule ``ckpt.serve`` nth=1 drop — the
                       transfer dies on the SERVING side (the victim's
                       respawn env is scrubbed by design, so a healer-side
                       kill is not replayable; the serve drop is)
=====================  ====================================================

HA-tier actions (``lh_*``, ``delta*``, ``sub_*``) have no runnable
lowering until the Raft lighthouse lands: they are collected into the
schedule's ``unlowered`` list, the descriptor is still written (the
trace and the intended coordinates are the spec for that future wiring),
and ``runnable`` stays False unless at least one real rule lowered.

``compile_gate_schedules()`` compiles the shipped set from sampled
coverage paths of the single-lighthouse gate configs; the faultmatrix
tier replays them green today (tests/test_faultinject_compiled.py).
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys
import zlib
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

from torchft_tpu.analysis.protocol.spec import (
    SpecConfig,
    State,
    check_state,
    check_terminal,
    enabled_actions,
    init_state,
)

__all__ = [
    "CompiledSchedule",
    "compile_trace",
    "sample_paths",
    "compile_gate_schedules",
    "SHIPPED_DIR",
]

# the checked-in descriptors the runner's bare `--compiled` flag loads
SHIPPED_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)
    ))),
    "faultinject", "compiled",
)

_ACT = re.compile(r"^([a-z_]+)\(([^)]*)\)")


@dataclass
class CompiledSchedule:
    """One lowered trace: the scenario descriptor the runner ingests."""

    name: str
    description: str
    source: str                  # "counterexample" | "coverage"
    trace: List[str]
    victim: int                  # model replica index lowered to group 1
    victim_schedule: Optional[dict] = None
    survivor_schedule: Optional[dict] = None
    common_env: Dict[str, str] = field(default_factory=dict)
    expect_victim_death: bool = False
    unlowered: List[str] = field(default_factory=list)
    notes: List[str] = field(default_factory=list)

    @property
    def runnable(self) -> bool:
        """At least one real rule lowered — an all-HA trace compiles to
        coordinates only the future Raft wiring can honor."""
        return bool(
            (self.victim_schedule or {}).get("rules")
            or (self.survivor_schedule or {}).get("rules")
        )

    def to_descriptor(self) -> dict:
        return {
            "name": self.name,
            "description": self.description,
            "source": self.source,
            "trace": list(self.trace),
            "victim": self.victim,
            "victim_schedule": self.victim_schedule,
            "survivor_schedule": self.survivor_schedule,
            "common_env": dict(self.common_env),
            "expect_victim_death": self.expect_victim_death,
            "unlowered": list(self.unlowered),
            "notes": list(self.notes),
            "runnable": self.runnable,
        }

    @classmethod
    def from_descriptor(cls, doc: dict) -> "CompiledSchedule":
        return cls(
            name=doc["name"],
            description=doc.get("description", ""),
            source=doc.get("source", "coverage"),
            trace=list(doc.get("trace", [])),
            victim=int(doc.get("victim", 1)),
            victim_schedule=doc.get("victim_schedule"),
            survivor_schedule=doc.get("survivor_schedule"),
            common_env=dict(doc.get("common_env", {})),
            expect_victim_death=bool(doc.get("expect_victim_death")),
            unlowered=list(doc.get("unlowered", [])),
            notes=list(doc.get("notes", [])),
        )


def _parse(label: str) -> Tuple[str, List[str]]:
    """``"vote(1)!stale"`` → ``("vote", ["1"])`` (suffix tags dropped —
    they annotate the invariant, not the coordinate)."""
    m = _ACT.match(label)
    if not m:
        return label, []
    return m.group(1), [a.strip() for a in m.group(2).split(",") if a]


# the HA tier: model actions with no real implementation to inject into
# yet (the Raft lighthouse / delta protocol / sub-aggregator tree)
_HA_PREFIXES = (
    "lh_", "delta", "sub_",
)


def compile_trace(
    trace: List[str],
    name: str,
    description: str = "",
    source: str = "coverage",
) -> CompiledSchedule:
    """Lower one checker action trace into a scenario descriptor.

    The victim is the first replica the trace crashes (no crash and no
    corrupt → nothing to inject; the descriptor comes back with no rules
    and ``runnable`` False). The schedule seed is derived from the trace
    so identical traces compile to identical schedules.
    """
    seed = zlib.crc32("|".join(trace).encode()) % 1000 or 1
    out = CompiledSchedule(
        name=name, description=description, source=source,
        trace=list(trace), victim=1,
    )

    victim: Optional[int] = None
    for label in trace:
        act, args = _parse(label)
        if act == "crash":
            victim = int(args[0])
            break
        if act == "work_corrupt":
            victim = int(args[0])
            break
    if victim is None:
        for label in trace:
            act, args = _parse(label)
            if act == "heal_fail":
                victim = int(args[0])
                break
    out.victim = victim if victim is not None else 1

    rules: List[dict] = []
    survivor_rules: List[dict] = []
    # the victim's walked protocol position
    works = votes = rounds = 0
    in_round = worked = voted = False
    crashed = False

    for label in trace:
        act, args = _parse(label)
        if any(act.startswith(p) for p in _HA_PREFIXES):
            out.unlowered.append(label)
            continue
        tgt: Optional[int] = None
        if args:
            head = args[0].split("<-")[0].split("->")[0]
            if head.isdigit():
                tgt = int(head)
        if act == "form":
            if not crashed and victim is not None:
                in_round, worked, voted = True, False, False
                rounds += 1
            continue
        if tgt != victim:
            continue
        if act == "work":
            works += 1
            worked = True
        elif act == "work_corrupt":
            works += 1
            worked = True
            rules.append({
                "site": "collective.complete", "match": "allreduce",
                "nth": works, "action": "corrupt", "frac": 0.05,
            })
            # the fence turns the planted corruption into an abort +
            # clean retry, so the compiled run still converges
            out.common_env["TORCHFT_DIVERGENCE_SENTINEL"] = "1"
            out.common_env["TORCHFT_DIVERGENCE_FENCE"] = "1"
            out.notes.append(
                f"{label}: corrupt lowered with the divergence fence "
                "armed (commit must abort, retry must be clean)"
            )
        elif act == "vote":
            votes += 1
            voted = True
        elif act == "resolve":
            in_round = worked = voted = False
        elif act == "heal_fail":
            survivor_rules.append({
                "site": "ckpt.serve", "nth": 1, "action": "drop",
            })
            out.notes.append(
                f"{label}: healer-side failure lowered to the survivor's "
                "serve (the respawned victim's schedule is scrubbed by "
                "the runner, so the serving side carries the fault)"
            )
        elif act == "crash":
            if crashed:
                out.unlowered.append(label)
                out.notes.append(
                    f"{label}: second victim death not replayable (the "
                    "respawn env is scrubbed — one scheduled death per "
                    "incarnation)"
                )
                continue
            crashed = True
            if in_round and worked and not voted:
                rules.append({
                    "site": "commit.vote", "match": "prepare",
                    "nth": votes + 1, "action": "kill", "sig": 9,
                })
                out.notes.append(
                    f"{label}: died after contributing, before the "
                    f"commit vote → kill at the barrier drain "
                    f"(prepare #{votes + 1})"
                )
            elif in_round and voted:
                rules.append({
                    "site": "collective.issue", "match": "allreduce",
                    "nth": works + 1, "action": "kill", "sig": 9,
                })
                out.notes.append(
                    f"{label}: died with the vote on the wire → kill "
                    f"entering the next collective (allreduce "
                    f"#{works + 1})"
                )
            else:
                rules.append({
                    "site": "quorum.reply",
                    "nth": max(rounds, 1), "action": "kill", "sig": 9,
                })
                out.notes.append(
                    f"{label}: died before contributing → kill on the "
                    f"quorum reply (#{max(rounds, 1)})"
                )
            out.expect_victim_death = True

    if rules:
        out.victim_schedule = {"seed": seed, "rules": rules}
    if survivor_rules:
        out.survivor_schedule = {"seed": seed, "rules": survivor_rules}
    return out


# ---------------------------------------------------------------------------
# coverage-path sampling
# ---------------------------------------------------------------------------


def sample_paths(
    cfg: SpecConfig,
    want: int = 32,
    max_states: int = 200_000,
) -> List[List[str]]:
    """Deterministic DFS over ``cfg`` collecting up to ``want`` coverage
    paths: clean traces that reach a terminal with at least one commit
    AND contain at least one crash — the interleavings worth replaying.
    Violating paths are skipped (those are counterexamples; compile them
    from the checker's Violation directly)."""
    root = init_state(cfg)
    paths: List[List[str]] = []
    seen = {root}
    stack: List[Tuple[State, List[str]]] = [(root, [])]
    states = 0
    while stack and len(paths) < want and states < max_states:
        state, path = stack.pop()
        states += 1
        actions = enabled_actions(state, cfg)
        if not actions:
            if (
                state.commits
                and any(p.startswith("crash(") for p in path)
                and not check_terminal(state, cfg)
                and not check_state(state, cfg)
            ):
                paths.append(path)
            continue
        for label, nxt in actions:
            if nxt not in seen:
                seen.add(nxt)
                stack.append((nxt, path + [label]))
    return paths


def _classify(cs: CompiledSchedule) -> Optional[str]:
    rules = (cs.victim_schedule or {}).get("rules", [])
    return rules[0]["site"] if rules else None


def compile_gate_schedules(
    cfg: Optional[SpecConfig] = None,
) -> List[CompiledSchedule]:
    """The shipped set: from sampled coverage paths of the ``sync-2g``
    gate config, one compiled schedule per distinct victim-death
    coordinate the lowering can express — kill at the quorum reply, kill
    at the commit-vote drain, kill entering the next collective. Each
    replays green through the faultmatrix runner (that's the gate)."""
    from torchft_tpu.analysis.protocol.checker import GATE_CONFIGS

    cfg = cfg or GATE_CONFIGS["sync-2g"]
    picked: Dict[str, CompiledSchedule] = {}
    descr = {
        "quorum.reply": (
            "compiled_kill_quorum_reply",
            "checker coverage path: the victim dies on a quorum reply "
            "before contributing; the cohort re-forms and converges "
            "(compiled from the sync-2g model by analysis.protocol."
            "compile)",
        ),
        "commit.vote": (
            "compiled_kill_commit_vote",
            "checker coverage path: the victim dies at the barrier "
            "drain after contributing, before its commit vote; the "
            "survivor's step aborts and the respawn heals (compiled "
            "from the sync-2g model)",
        ),
        "collective.issue": (
            "compiled_kill_next_collective",
            "checker coverage path: the victim dies entering the "
            "collective after a cast vote; the committed step survives "
            "it (compiled from the sync-2g model)",
        ),
    }
    for path in sample_paths(cfg):
        cs = compile_trace(path, name="tmp", source="coverage")
        site = _classify(cs)
        if site in descr and site not in picked:
            name, text = descr[site]
            cs.name, cs.description = name, text
            picked[site] = cs
        if len(picked) == len(descr):
            break
    return [picked[s] for s in sorted(picked)]


# ---------------------------------------------------------------------------
# CLI: write descriptors
# ---------------------------------------------------------------------------


def write_descriptors(
    schedules: List[CompiledSchedule], outdir: str
) -> List[str]:
    os.makedirs(outdir, exist_ok=True)
    written = []
    for cs in schedules:
        path = os.path.join(outdir, f"{cs.name}.json")
        with open(path, "w", encoding="utf-8") as f:
            json.dump(cs.to_descriptor(), f, indent=2, sort_keys=True)
            f.write("\n")
        written.append(path)
    return written


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="torchft_tpu.analysis.protocol.compile",
        description="compile checker traces into faultinject schedules",
    )
    ap.add_argument("--outdir", default=SHIPPED_DIR,
                    help="where descriptors land (default: the shipped "
                    "faultinject/compiled/ set)")
    ap.add_argument("--fixture", metavar="JSON",
                    help="compile the counterexample of a broken spec "
                    "fixture (tests/fixtures/analysis/spec_*.json) "
                    "instead of the gate coverage set")
    args = ap.parse_args(argv)

    if args.fixture:
        from torchft_tpu.analysis.protocol.checker import check

        with open(args.fixture, encoding="utf-8") as f:
            doc = json.load(f)
        doc.pop("_comment", None)
        expect = doc.pop("expect_violation", None)
        res = check(SpecConfig(**doc), max_violations=1)
        if not res.violations:
            print(f"{args.fixture}: no violation found — nothing to "
                  "compile", file=sys.stderr)
            return 1
        v = res.violations[0]
        base = os.path.splitext(os.path.basename(args.fixture))[0]
        cs = compile_trace(
            v.trace,
            name=f"counterexample_{base}",
            description=f"counterexample of {base} "
            f"({v.invariant}; expected {expect}): {v.detail}",
            source="counterexample",
        )
        written = write_descriptors([cs], args.outdir)
    else:
        written = write_descriptors(
            compile_gate_schedules(), args.outdir
        )
    for path in written:
        with open(path, encoding="utf-8") as f:
            doc = json.load(f)
        tag = "runnable" if doc["runnable"] else (
            f"NOT runnable ({len(doc['unlowered'])} unlowered HA "
            "action(s) — pending the Raft wiring)"
        )
        print(f"{path}: {tag}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
