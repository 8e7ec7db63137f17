"""Trace-conformance checker: replay real FT evidence against the spec.

The event trail (``telemetry/events.py``) and the crash-durable black
boxes (PR 10) record every protocol lifecycle event a replica took. This
module replays those records against the spec's *event-level* transition
rules and flags any sequence the protocol cannot legally produce — which
turns every faultmatrix scenario (and every postmortem) into a
conformance proof: the scenarios already exercise the interleavings; now
an illegal transition in any of them fails the run.

One trail file / black box = one replica's history (workers write
per-process sinks), so the rules are per-replica; the cross-replica
invariants (unique commit lineage, quorum agreement) are the model
checker's jurisdiction — a trail can't see what it never observed.

Rules (rule ids appear in findings and docs/static_analysis.md):

``epoch-regression``
    ``quorum_ready.quorum_id`` decreased. The lighthouse's epoch counter
    only ever increments (``coord.cc``), and a replica observing a lower
    epoch after a higher one re-entered a dead epoch's plane.

``step-regression``
    A ``commit`` at a step at or below an already-committed step: a
    committed step is final — recommitting it forks the lineage.

``healing-commit``
    A ``commit`` while a heal is in flight (``heal_begin`` seen, no
    ``heal_end``/``heal_failed`` yet): the staged state must land (the
    commit barrier applies it) before the vote — a commit mid-transfer
    means the barrier voted on a half-healed replica.

``heal-failed-commit``
    A ``commit`` after ``heal_failed`` with no intervening
    ``quorum_ready``: a failed heal latches the error, and the step MUST
    abort at the barrier; only the next quorum may commit again.

``diverged-commit``
    With the fence armed (``divergence_detected`` carries ``fence``),
    a ``commit`` at the step the sentinel latched on: the fence's
    whole contract is vetoing that commit (PR 10).

Sources may be *truncated* (black-box rings evict old records; trails
rotate), so the checker seeds its state leniently from the first record
it sees and never flags what truncation hides.
"""

from __future__ import annotations

import glob
import json
import os
from dataclasses import dataclass, field
from typing import Any, Dict, Iterable, List, Optional

from torchft_tpu.telemetry.events import LIFECYCLE_EVENTS

__all__ = [
    "ConformanceFinding",
    "ConformanceReport",
    "check_records",
    "check_trail_file",
    "check_tree",
]


@dataclass
class ConformanceFinding:
    rule: str
    source: str       # trail path / box id
    index: int        # record index within the source
    event: str
    step: int
    epoch: int
    detail: str

    def render(self) -> str:
        return (
            f"{self.source}#{self.index}: [{self.rule}] {self.event} "
            f"(step={self.step}, epoch={self.epoch}): {self.detail}"
        )


@dataclass
class ConformanceReport:
    sources: int = 0
    records: int = 0
    lifecycle_records: int = 0
    findings: List[ConformanceFinding] = field(default_factory=list)

    @property
    def ok(self) -> bool:
        return not self.findings

    def render(self) -> str:
        lines = [
            f"conformance: {self.sources} source(s), "
            f"{self.lifecycle_records}/{self.records} lifecycle "
            f"record(s), {len(self.findings)} illegal transition(s)"
        ]
        lines += [f"  {f.render()}" for f in self.findings]
        return "\n".join(lines)


def _normalize(rec: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """Map a trail record ({"event": ...}) or a black-box mirror record
    ({"k": ...}) onto one shape; None for non-lifecycle records."""
    kind = rec.get("event", rec.get("k"))
    if kind not in LIFECYCLE_EVENTS:
        return None
    step = rec.get("step", rec.get("st", -1))
    try:
        step = int(step)
    except (TypeError, ValueError):
        step = -1
    epoch = rec.get("quorum_id", rec.get("ep", -1))
    try:
        epoch = int(epoch)
    except (TypeError, ValueError):
        epoch = -1
    return {"kind": kind, "step": step, "epoch": epoch, "rec": rec}


def check_records(
    records: Iterable[Dict[str, Any]], source: str = "<records>"
) -> ConformanceReport:
    """Replay one replica's records (trail or box order = emit order)
    against the event-level spec. Returns the report for this source."""
    rep = ConformanceReport(sources=1)
    max_epoch = -1          # highest quorum_ready epoch seen
    committed_steps: set = set()
    max_committed = -1
    heal_inflight: Optional[int] = None   # heal_begin's step
    heal_failed_latched = False
    fence_steps: set = set()  # steps where divergence latched w/ fence

    for idx, raw in enumerate(records):
        rep.records += 1
        norm = _normalize(raw)
        if norm is None:
            continue
        rep.lifecycle_records += 1
        kind, step, epoch = norm["kind"], norm["step"], norm["epoch"]

        def flag(rule: str, detail: str) -> None:
            rep.findings.append(ConformanceFinding(
                rule=rule, source=source, index=idx, event=kind,
                step=step, epoch=epoch, detail=detail,
            ))

        if kind == "quorum_start":
            # a quorum_start back at step 0 after real progress means a
            # NEW process appended to this sink (SIGKILL + respawn — the
            # faultmatrix's bread and butter): per-process trackers
            # reset, because the respawned replica legitimately re-heals
            # and re-commits steps its predecessor's discarded state
            # already saw. The epoch tracker survives: the lighthouse
            # epoch is global and must stay monotone across respawns.
            if step == 0 and (committed_steps or heal_inflight is not None
                              or heal_failed_latched):
                committed_steps = set()
                max_committed = -1
                heal_inflight = None
                heal_failed_latched = False
                fence_steps = set()
        elif kind == "quorum_ready":
            if epoch >= 0:
                if max_epoch >= 0 and epoch < max_epoch:
                    flag(
                        "epoch-regression",
                        f"quorum_id {epoch} after having observed "
                        f"{max_epoch} — the lighthouse epoch only "
                        "increments; this replica re-entered a dead "
                        "epoch's plane",
                    )
                max_epoch = max(max_epoch, epoch)
            heal_failed_latched = False
            # a new round re-averages the vetoed step from the committed
            # state and re-compares digests: the fence latch belonged to
            # the ABORTED attempt, and the retry's commit (identical
            # digests this time) is the legal outcome — observed live in
            # the corrupt_divergence fence leg (veto -> re-quorum ->
            # clean retry of the same step)
            fence_steps = set()
        elif kind == "heal_begin":
            heal_inflight = step
        elif kind in ("heal_end", "heal_failed"):
            heal_inflight = None
            if kind == "heal_failed":
                heal_failed_latched = True
        elif kind == "divergence_detected":
            if bool(raw.get("fence")):
                fence_steps.add(step)
        elif kind == "commit":
            if heal_inflight is not None:
                flag(
                    "healing-commit",
                    f"commit at step {step} while a heal begun at step "
                    f"{heal_inflight} is still in flight (no heal_end/"
                    "heal_failed) — the barrier voted on a half-healed "
                    "replica",
                )
            if heal_failed_latched:
                flag(
                    "heal-failed-commit",
                    f"commit at step {step} after heal_failed with no "
                    "intervening quorum_ready — a failed heal latches "
                    "the error and the step must abort",
                )
            if step >= 0:
                if step in committed_steps:
                    flag(
                        "step-regression",
                        f"step {step} committed twice — a committed "
                        "step is final; recommitting forks the lineage",
                    )
                elif max_committed >= 0 and step < max_committed:
                    flag(
                        "step-regression",
                        f"commit at step {step} after step "
                        f"{max_committed} already committed — committed "
                        "steps are monotone",
                    )
                if step in fence_steps:
                    flag(
                        "diverged-commit",
                        f"commit at step {step} where the divergence "
                        "sentinel latched with the fence armed — the "
                        "fence must veto this commit",
                    )
                committed_steps.add(step)
                max_committed = max(max_committed, step)
    return rep


def _merge(into: ConformanceReport, one: ConformanceReport) -> None:
    into.sources += one.sources
    into.records += one.records
    into.lifecycle_records += one.lifecycle_records
    into.findings.extend(one.findings)


def check_trail_file(path: str) -> ConformanceReport:
    """Replay one JSONL trail file (torn tails skipped, like every other
    trail reader)."""
    records: List[Dict[str, Any]] = []
    try:
        with open(path, encoding="utf-8") as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    rec = json.loads(line)
                except ValueError:
                    continue
                if isinstance(rec, dict):
                    records.append(rec)
    except OSError:
        return ConformanceReport()
    return check_records(records, source=os.path.relpath(path))


def check_tree(root: str) -> ConformanceReport:
    """Replay every trail file and black box under ``root``: the
    ``postmortem --conformance`` / faultmatrix-runner entry point.

    Trails and boxes duplicate each other (the box mirrors every trail
    emit), but conformance is per-source order-sensitive, so both are
    replayed independently — a finding in either is real."""
    rep = ConformanceReport()
    for path in sorted(
        glob.glob(os.path.join(root, "**", "*.jsonl"), recursive=True)
    ):
        _merge(rep, check_trail_file(path))
    # black boxes: python rings carry the mirrored trail records
    try:
        from torchft_tpu.telemetry.blackbox import (
            read_blackbox,
            read_native_blackbox,
        )

        for path in sorted(
            glob.glob(os.path.join(root, "**", "*.bb"), recursive=True)
        ):
            try:
                if path.endswith("_native.bb"):
                    records, _meta = read_native_blackbox(path)
                else:
                    records, _meta = read_blackbox(path)
            except OSError:
                continue
            _merge(
                rep,
                check_records(records, source=os.path.relpath(path)),
            )
    except Exception:  # noqa: BLE001 — boxes are optional evidence
        pass
    return rep
