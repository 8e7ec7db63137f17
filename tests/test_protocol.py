"""Tests for the FT-protocol verification plane (ISSUE 15 + ISSUE 20).

Five layers, mirroring the package:

* **model checker** — the shipped gate configurations must verify clean
  under exhaustive bounded exploration (crash injected at every
  transition point), and every deliberately-broken spec variant (the
  seeded fixtures) must produce exactly its planted violation class —
  the checker is itself code under test, so both directions matter;
* **reductions** (ISSUE 20) — POR + symmetry must reproduce the PR 15
  verdicts at ≥5× fewer explored states, bitstate must mark itself
  approximate, and budget truncation must be loud, never a silent pass;
* **the HA tier** (ISSUE 20) — the four Raft-lighthouse gate configs
  verify clean within their stated state budgets, and each broken HA
  variant fixture is caught with its planted invariant + a trace, in
  both reduced and reference modes;
* **trace conformance** — each illegal-transition rule catches its
  seeded trail (the ``trail_healing_commit.jsonl`` fixture et al.) and
  passes legal lifecycles, including the SIGKILL+respawn append pattern
  real faultmatrix trails produce;
* **the trace→schedule compiler + CLI** — checker traces lower into the
  faultinject grammar deterministically (the shipped
  ``faultinject/compiled/`` descriptors are pinned regenerable), and
  ``python -m torchft_tpu.analysis.protocol`` is premerge gate [4] with
  its exit-code contract pinned here.
"""

import json
import os
import signal
import subprocess
import sys

import pytest

from torchft_tpu.analysis.protocol import SpecConfig, check
from torchft_tpu.analysis.protocol.checker import (
    GATE_CONFIGS,
    HA_STATE_BUDGETS,
)
from torchft_tpu.analysis.protocol.compile import (
    compile_gate_schedules,
    compile_trace,
    sample_paths,
)
from torchft_tpu.analysis.protocol.conformance import (
    check_records,
    check_trail_file,
)

REPO = os.path.normpath(os.path.join(os.path.dirname(__file__), ".."))
FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures", "analysis")

# PR 15's plain-DFS explored-state counts for the legacy gate configs —
# measured by running the PR 15 checker (commit 7020015) against the
# unchanged single-lighthouse spec. The ISSUE 20 acceptance bar: the
# POR+symmetry checker reproduces these verdicts at >=5x fewer states.
PR15_STATES = {
    "sync-2g": 3082,
    "divergence-fenced-2g": 14416,
    "sync-3g": 118466,
}

# Explored-state counts of every gate config under the default
# reductions, as the checker printed them at PR 49's commit — the commit
# before the pipelined-commit fork (a fifth replica state, its heal
# fence, residual rollback) left the spec. Equal counts are the proof that only
# the fork left: every remaining config explores the state space it
# explored with the fork's transitions present and disabled.
GATE_STATES = {
    "sync-2g": 343,
    "divergence-fenced-2g": 1267,
    "sync-3g": 584,
    "ha-leader-crash": 16707,
    "ha-partition-reelect": 10578,
    "ha-delta-resync": 626,
    "ha-subagg-crash": 2246,
}

# fixture -> the SpecConfig knob whose healthy setting makes it clean
HA_FIXTURES = {
    "spec_split_brain_leaders.json": ("raft_single_vote", True),
    "spec_stale_leader_commit.json": ("stale_leader_fence", True),
    "spec_out_of_order_delta.json": ("ordered_deltas", True),
}


def _kinds(result):
    return sorted({v.invariant for v in result.violations})


def _load_fixture(name):
    with open(os.path.join(FIXTURES, name), encoding="utf-8") as f:
        doc = json.load(f)
    doc.pop("_comment", None)
    expect = doc.pop("expect_violation")
    return doc, expect


# ---------------------------------------------------------------------------
# model checker: the shipped protocol verifies clean
# ---------------------------------------------------------------------------


class TestModelChecker:
    def test_sync_2g_clean(self):
        r = check(GATE_CONFIGS["sync-2g"])
        assert r.ok, [v.render() for v in r.violations]
        # exhaustive means EXPLORED: a broken scheduler that visits 3
        # states would also report "no violations" (reduced counts —
        # the PR 15 plain-DFS bound lives in TestReductions)
        assert r.states > 100
        assert r.terminals > 0
        assert not r.truncated and not r.approximate

    def test_divergence_fenced_2g_clean(self):
        r = check(GATE_CONFIGS["divergence-fenced-2g"])
        assert r.ok, [v.render() for v in r.violations]
        assert r.states > 100

    def test_sync_3g_clean(self):
        # ~118k states under PR 15's plain DFS; symmetry over 3
        # interchangeable groups makes it tier-1-sized now
        r = check(GATE_CONFIGS["sync-3g"])
        assert r.ok and not r.truncated
        assert r.states > 100

    @pytest.mark.parametrize("name", sorted(GATE_STATES))
    def test_explored_state_count_pinned(self, name):
        assert set(GATE_STATES) == set(GATE_CONFIGS)
        r = check(GATE_CONFIGS[name])
        assert r.ok and not r.truncated and not r.approximate, name
        assert r.states == GATE_STATES[name], (name, r.states)

    def test_crash_interleaved_at_every_point(self):
        """The SIGKILL-anywhere contract: with a crash budget, the
        explored transition multiset contains a crash from many distinct
        predecessor depths — spot-check by counting crash transitions."""
        from torchft_tpu.analysis.protocol.spec import (
            enabled_actions,
            init_state,
        )

        cfg = GATE_CONFIGS["sync-2g"]
        state = init_state(cfg)
        labels = [a for a, _s in enabled_actions(state, cfg)]
        assert "crash(0)" in labels and "crash(1)" in labels
        # take a non-crash step; the crash action must still be offered
        _label, nxt = next(
            (a, s) for a, s in enabled_actions(state, cfg)
            if a.startswith("join")
        )
        labels2 = [a for a, _s in enabled_actions(nxt, cfg)]
        assert "crash(0)" in labels2 and "crash(1)" in labels2


# ---------------------------------------------------------------------------
# model checker: every broken variant is caught (seeded spec fixtures)
# ---------------------------------------------------------------------------


class TestBrokenVariantsCaught:
    def test_double_commit_fixture(self):
        """The seeded split-brain spec (join barrier off) must produce
        the double-commit interleaving — and the same bounds with the
        barrier ON must not."""
        with open(os.path.join(FIXTURES, "spec_double_commit.json")) as f:
            doc = json.load(f)
        expect = doc.pop("expect_violation")
        doc.pop("_comment")
        broken = SpecConfig(**doc)
        r = check(broken)
        assert expect in _kinds(r), _kinds(r)
        # the violation comes with an executable reproduction trace
        bad = next(v for v in r.violations if v.invariant == expect)
        assert any(t.startswith("form(") for t in bad.trace)
        fixed = SpecConfig(**{**doc, "join_barrier": True})
        assert check(fixed).ok

    def test_divergence_fence_load_bearing(self):
        """PR 10: sentinel/fence off -> a silently-corrupt compute
        commits a second lineage."""
        broken = SpecConfig(
            n_replicas=2, min_replicas=1, max_rounds=2, crash_budget=0,
            respawn_budget=0, corrupt_budget=1, fence_divergence=False,
        )
        assert "I1-unique-commit" in _kinds(check(broken))


# ---------------------------------------------------------------------------
# checker scale-up: POR + symmetry + bitstate + budgets (ISSUE 20)
# ---------------------------------------------------------------------------


class TestReductions:
    def test_legacy_verdicts_identical_at_5x_fewer_states(self):
        """The acceptance bar: the three PR 15 gate configs, identical
        (clean) verdicts, >=5x fewer explored states under the default
        POR+symmetry reductions."""
        for name, pr15 in PR15_STATES.items():
            r = check(GATE_CONFIGS[name])
            assert r.ok and not r.truncated, name
            assert r.states * 5 <= pr15, (name, r.states, pr15)

    def test_reductions_agree_with_reference_mode(self):
        """Soundness spot-check: reductions on vs off, same verdict —
        on a clean config AND on a broken one (the violation must
        survive the pruning)."""
        for name in ("sync-2g", "divergence-fenced-2g"):
            red = check(GATE_CONFIGS[name])
            ref = check(GATE_CONFIGS[name], por=False, symmetry=False)
            assert red.ok and ref.ok, name
        doc, expect = _load_fixture("spec_double_commit.json")
        broken = SpecConfig(**doc)
        red = check(broken, max_violations=1)
        ref = check(broken, max_violations=1, por=False, symmetry=False)
        assert expect in _kinds(red) and expect in _kinds(ref)

    def test_bitstate_is_loudly_approximate(self):
        r = check(GATE_CONFIGS["sync-2g"], bitstate=True)
        assert r.approximate is True
        # and the exact default never claims to be approximate
        assert check(GATE_CONFIGS["sync-2g"]).approximate is False

    def test_budget_truncation_is_not_a_clean_verdict(self):
        r = check(GATE_CONFIGS["sync-2g"], max_states=50)
        assert r.truncated
        assert not r.ok  # a truncated run must never read as verified
        assert r.truncated_states > 0  # the unexplored frontier is counted

    def test_early_stop_on_max_violations(self):
        """``max_violations=1`` turns a broken fixture into a fast
        fail-on-first run — marked truncated, never ok."""
        doc, expect = _load_fixture("spec_stale_leader_commit.json")
        fast = check(SpecConfig(**doc), max_violations=1)
        assert len(fast.violations) == 1
        assert fast.violations[0].invariant == expect
        assert fast.truncated and not fast.ok
        full = check(SpecConfig(**doc))
        assert fast.states < full.states


# ---------------------------------------------------------------------------
# the HA tier: Raft lighthouse + membership deltas + quorum tree
# ---------------------------------------------------------------------------


class TestHaGates:
    def test_ha_gate_configs_clean_within_stated_budget(self):
        ha = {n: c for n, c in GATE_CONFIGS.items() if n.startswith("ha-")}
        assert len(ha) >= 4, sorted(ha)
        for name, cfg in ha.items():
            budget = HA_STATE_BUDGETS[name]
            r = check(cfg, max_states=budget)
            assert r.ok and not r.truncated, (
                name, r.states, [v.render() for v in r.violations],
            )
            assert r.states <= budget


class TestBrokenHaVariantsCaught:
    def test_each_fixture_caught_with_planted_class_and_trace(self):
        """Every broken HA fixture fires EXACTLY its planted invariant —
        in the reduced mode and in the reference (no-POR, no-symmetry)
        mode, with a rendered action trace either way."""
        for name in HA_FIXTURES:
            doc, expect = _load_fixture(name)
            broken = SpecConfig(**doc)
            for kwargs in ({}, {"por": False, "symmetry": False}):
                r = check(broken, max_violations=1, **kwargs)
                assert _kinds(r) == [expect], (name, kwargs, _kinds(r))
                v = r.violations[0]
                assert v.trace, (name, kwargs)
                assert expect in v.render()

    def test_fixed_twins_are_clean(self):
        """The same bounds with the protection ON must verify clean —
        each HA protection is proven load-bearing."""
        for name, (knob, healthy) in HA_FIXTURES.items():
            doc, _expect = _load_fixture(name)
            doc[knob] = healthy
            r = check(SpecConfig(**doc))
            assert r.ok, (name, [v.render() for v in r.violations])


# ---------------------------------------------------------------------------
# trace conformance
# ---------------------------------------------------------------------------


class TestConformance:
    def test_healing_commit_fixture_caught(self):
        rep = check_trail_file(
            os.path.join(FIXTURES, "trail_healing_commit.jsonl")
        )
        assert [f.rule for f in rep.findings] == ["healing-commit"]
        assert rep.findings[0].step == 4

    def test_legal_lifecycle_passes(self):
        legal = [
            {"event": "quorum_start", "step": 0},
            {"event": "quorum_ready", "quorum_id": 1, "step": 0},
            {"event": "commit", "step": 0},
            {"event": "quorum_start", "step": 1},
            {"event": "quorum_ready", "quorum_id": 2, "step": 1},
            {"event": "heal_begin", "step": 5},
            {"event": "heal_end", "step": 5},
            {"event": "commit", "step": 5},
            {"event": "abort", "step": 6},
            {"event": "quorum_start", "step": 6},
            {"event": "quorum_ready", "quorum_id": 3, "step": 6},
            {"event": "commit", "step": 6},
        ]
        rep = check_records(legal, "legal")
        assert rep.ok, [f.render() for f in rep.findings]

    def test_respawn_append_pattern_legal(self):
        """A respawned worker appends to the same trail: its step-0
        quorum_start resets per-process trackers, so re-healing and
        re-committing an already-seen step is legal — but the epoch
        must stay monotone across the respawn."""
        records = [
            {"event": "quorum_ready", "quorum_id": 3, "step": 0},
            {"event": "commit", "step": 0},
            {"event": "commit", "step": 1},
            # process died; respawn starts over
            {"event": "quorum_start", "step": 0},
            {"event": "quorum_ready", "quorum_id": 7, "step": 0},
            {"event": "heal_begin", "step": 1},
            {"event": "heal_end", "step": 1},
            {"event": "commit", "step": 1},
        ]
        assert check_records(records).ok
        # same pattern with a REGRESSING epoch after respawn: illegal
        bad = list(records)
        bad[4] = {"event": "quorum_ready", "quorum_id": 2, "step": 0}
        rep = check_records(bad)
        assert [f.rule for f in rep.findings] == ["epoch-regression"]

    def test_epoch_regression_caught(self):
        rep = check_records([
            {"event": "quorum_ready", "quorum_id": 5, "step": 0},
            {"event": "quorum_ready", "quorum_id": 4, "step": 0},
        ])
        assert [f.rule for f in rep.findings] == ["epoch-regression"]

    def test_double_commit_caught(self):
        rep = check_records([
            {"event": "quorum_ready", "quorum_id": 1, "step": 0},
            {"event": "commit", "step": 2},
            {"event": "commit", "step": 2},
        ])
        assert [f.rule for f in rep.findings] == ["step-regression"]

    def test_heal_failed_then_commit_caught(self):
        rep = check_records([
            {"event": "quorum_ready", "quorum_id": 1, "step": 0},
            {"event": "heal_begin", "step": 2},
            {"event": "heal_failed", "step": 2},
            {"event": "commit", "step": 2},
        ])
        assert [f.rule for f in rep.findings] == ["heal-failed-commit"]
        # ... but a commit after the NEXT quorum is the legal retry
        rep2 = check_records([
            {"event": "quorum_ready", "quorum_id": 1, "step": 0},
            {"event": "heal_begin", "step": 2},
            {"event": "heal_failed", "step": 2},
            {"event": "quorum_ready", "quorum_id": 2, "step": 0},
            {"event": "heal_begin", "step": 2},
            {"event": "heal_end", "step": 2},
            {"event": "commit", "step": 2},
        ])
        assert rep2.ok, [f.render() for f in rep2.findings]

    def test_fence_veto_bypass_caught(self):
        rep = check_records([
            {"event": "quorum_ready", "quorum_id": 1, "step": 0},
            {"event": "divergence_detected", "step": 3, "fence": True},
            {"event": "commit", "step": 3},
        ])
        assert [f.rule for f in rep.findings] == ["diverged-commit"]
        # sentinel-only (fence unarmed): the commit is the documented
        # detect-don't-veto mode — legal
        rep2 = check_records([
            {"event": "quorum_ready", "quorum_id": 1, "step": 0},
            {"event": "divergence_detected", "step": 3, "fence": False},
            {"event": "commit", "step": 3},
        ])
        assert rep2.ok
        # the real fence flow (corrupt_divergence fence leg): veto ->
        # abort -> RE-QUORUM -> clean retry of the same step commits
        rep3 = check_records([
            {"event": "quorum_ready", "quorum_id": 1, "step": 0},
            {"event": "divergence_detected", "step": 4, "fence": True},
            {"event": "abort", "step": 4},
            {"event": "quorum_ready", "quorum_id": 1, "step": 4},
            {"event": "commit", "step": 4},
        ])
        assert rep3.ok, [f.render() for f in rep3.findings]

    def test_blackbox_record_shape_accepted(self):
        """Black-box mirror records use the compact {k, st, ep} shape;
        the normalizer maps them onto the same rules."""
        rep = check_records([
            {"k": "quorum_ready", "quorum_id": 5, "st": 0},
            {"k": "quorum_ready", "quorum_id": 4, "st": 0},
        ])
        assert [f.rule for f in rep.findings] == ["epoch-regression"]


# ---------------------------------------------------------------------------
# trace -> schedule compiler (ISSUE 20 tentpole part 3)
# ---------------------------------------------------------------------------


class TestCompileTrace:
    PREFIX = ["join(0)", "join(1)", "form(r0,step=0)"]

    def test_crash_after_work_before_vote(self):
        cs = compile_trace(self.PREFIX + ["work(1)", "crash(1)"], name="t")
        assert cs.victim == 1 and cs.expect_victim_death and cs.runnable
        (rule,) = cs.victim_schedule["rules"]
        assert rule == {"site": "commit.vote", "match": "prepare",
                        "nth": 1, "action": "kill", "sig": 9}

    def test_crash_after_vote(self):
        cs = compile_trace(
            self.PREFIX + ["work(1)", "vote(1)", "crash(1)"], name="t",
        )
        (rule,) = cs.victim_schedule["rules"]
        # the vote is on the wire; the nearest hook is the NEXT collective
        assert rule["site"] == "collective.issue"
        assert rule["match"] == "allreduce" and rule["nth"] == 2

    def test_crash_before_contributing(self):
        cs = compile_trace(self.PREFIX + ["crash(0)"], name="t")
        assert cs.victim == 0
        (rule,) = cs.victim_schedule["rules"]
        assert rule["site"] == "quorum.reply" and rule["nth"] == 1

    def test_work_corrupt_arms_the_fence(self):
        cs = compile_trace(self.PREFIX + ["work_corrupt(0)"], name="t")
        (rule,) = cs.victim_schedule["rules"]
        assert rule["site"] == "collective.complete"
        assert rule["action"] == "corrupt"
        assert cs.common_env["TORCHFT_DIVERGENCE_FENCE"] == "1"
        assert not cs.expect_victim_death

    def test_heal_fail_lowers_to_survivor_serve_drop(self):
        cs = compile_trace(self.PREFIX + ["heal_fail(1)"], name="t")
        assert cs.victim_schedule is None
        (rule,) = cs.survivor_schedule["rules"]
        assert rule == {"site": "ckpt.serve", "nth": 1, "action": "drop"}
        assert cs.runnable

    def test_ha_actions_collect_as_unlowered(self):
        trace = ["lh_campaign(0,t1)", "lh_elect(0,t1)", "delta(1,v1)"]
        cs = compile_trace(trace, name="t")
        assert cs.unlowered == trace
        assert not cs.runnable  # coordinates await the Raft wiring

    def test_second_crash_of_victim_is_unlowerable(self):
        cs = compile_trace(
            self.PREFIX + ["crash(1)", "respawn(1)", "crash(1)"], name="t",
        )
        assert len(cs.victim_schedule["rules"]) == 1
        assert cs.unlowered == ["crash(1)"]

    def test_compilation_is_deterministic(self):
        trace = self.PREFIX + ["work(1)", "crash(1)"]
        a = compile_trace(trace, name="t").to_descriptor()
        b = compile_trace(trace, name="t").to_descriptor()
        assert a == b

    def test_descriptor_round_trip(self):
        from torchft_tpu.analysis.protocol.compile import CompiledSchedule

        cs = compile_trace(self.PREFIX + ["work(1)", "crash(1)"], name="t")
        doc = cs.to_descriptor()
        assert CompiledSchedule.from_descriptor(doc).to_descriptor() == doc


class TestCompiledGateSet:
    def test_sample_paths_are_crash_bearing(self):
        paths = sample_paths(GATE_CONFIGS["sync-2g"], want=8)
        assert paths
        for p in paths:
            assert any(lbl.startswith("crash(") for lbl in p)

    def test_three_distinct_death_coordinates(self):
        schedules = compile_gate_schedules()
        sites = {s.victim_schedule["rules"][0]["site"] for s in schedules}
        assert sites == {"quorum.reply", "commit.vote", "collective.issue"}
        for s in schedules:
            assert s.runnable and s.expect_victim_death and s.trace

    def test_shipped_descriptors_are_regenerable(self):
        """The checked-in faultinject/compiled/*.json set is exactly what
        the compiler produces today — descriptor drift fails here."""
        from torchft_tpu.analysis.protocol.compile import SHIPPED_DIR

        for cs in compile_gate_schedules():
            path = os.path.join(SHIPPED_DIR, f"{cs.name}.json")
            with open(path, encoding="utf-8") as f:
                assert json.load(f) == cs.to_descriptor(), path

    def test_runner_loads_shipped_set(self):
        from torchft_tpu.faultinject.runner import (
            COMPILED_DIR,
            load_compiled_scenarios,
        )

        scenarios = load_compiled_scenarios(COMPILED_DIR)
        assert len(scenarios) >= 3
        for s in scenarios:
            assert s.victim_schedule["rules"]
            assert s.expect_victim_death and not s.quick


# ---------------------------------------------------------------------------
# round trip: checker violation -> schedule -> real fire -> conformance
# ---------------------------------------------------------------------------


_ROUNDTRIP_WORKER = """\
import json, sys

# the illegal transition the model trace encodes, as a real trail --
# written BEFORE the fault loop so it survives the scheduled SIGKILL
with open(sys.argv[2], "w") as f:
    for rec in [
        {"event": "quorum_ready", "quorum_id": 1, "step": 0},
        {"event": "heal_begin", "step": 2},
        {"event": "commit", "step": 2},
    ]:
        f.write(json.dumps(rec) + "\\n")

from torchft_tpu.faultinject.core import fault_point
for _ in range(50):
    fault_point(sys.argv[1], sys.argv[3])
sys.exit(7)  # the schedule failed to kill us
"""


class TestTraceRoundTrip:
    def test_counterexample_fires_and_conformance_classifies(self, tmp_path):
        """Satellite: checker violation trace -> compiled schedule -> the
        planted site actually fires (evidence record, SIGKILL death) ->
        conformance classifies the illegal transition."""
        from torchft_tpu.analysis.protocol.compile import main as cmain
        from torchft_tpu.faultinject.core import read_evidence

        # 1. broken HA fixture -> counterexample descriptor via the CLI
        fixture = os.path.join(FIXTURES, "spec_out_of_order_delta.json")
        assert cmain(["--fixture", fixture, "--outdir", str(tmp_path)]) == 0
        desc = tmp_path / "counterexample_spec_out_of_order_delta.json"
        doc = json.loads(desc.read_text())
        assert doc["source"] == "counterexample"
        assert doc["runnable"], doc  # the crash lowered to a real site
        assert doc["unlowered"]  # the delta ops await the Raft wiring
        rule = doc["victim_schedule"]["rules"][0]
        assert rule["action"] == "kill" and rule["sig"] == 9

        # 2. replay: a worker hits the planted site until the schedule
        # kills it; the evidence record proves the site fired
        worker = tmp_path / "worker.py"
        worker.write_text(_ROUNDTRIP_WORKER)
        trail = tmp_path / "trail0.jsonl"
        evdir = tmp_path / "evidence"
        env = dict(os.environ)
        env.pop("TORCHFT_FAULT_SCHEDULE", None)
        env["TORCHFT_FAULT_SCHEDULE"] = json.dumps(doc["victim_schedule"])
        env["TORCHFT_FAULT_EVIDENCE_DIR"] = str(evdir)
        env["JAX_PLATFORMS"] = "cpu"
        env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
        proc = subprocess.run(
            [sys.executable, str(worker), rule["site"], str(trail),
             rule.get("match", "")],
            env=env, cwd=REPO, capture_output=True, text=True, timeout=120,
        )
        assert proc.returncode == -signal.SIGKILL, (
            proc.returncode, proc.stdout, proc.stderr,
        )
        fired = read_evidence(str(evdir))
        assert any(
            r.get("site") == rule["site"] and r.get("action") == "kill"
            for r in fired
        ), fired

        # 3. the trail the worker left behind carries the model-level
        # illegal transition; conformance names it
        rep = check_trail_file(str(trail))
        assert [f.rule for f in rep.findings] == ["healing-commit"]


# ---------------------------------------------------------------------------
# CLI (premerge gate [4])
# ---------------------------------------------------------------------------


class TestProtocolCli:
    def test_conformance_only_exit_codes(self, tmp_path):
        clean = tmp_path / "clean"
        clean.mkdir()
        (clean / "trail0.jsonl").write_text(
            '{"event": "quorum_ready", "quorum_id": 1, "step": 0}\n'
            '{"event": "commit", "step": 0}\n'
        )
        proc = subprocess.run(
            [sys.executable, "-m", "torchft_tpu.analysis.protocol",
             "--skip-model", "--conformance", str(clean)],
            capture_output=True, text=True, cwd=REPO,
        )
        assert proc.returncode == 0, proc.stdout + proc.stderr

        bad = tmp_path / "bad"
        bad.mkdir()
        (bad / "trail0.jsonl").write_text(
            '{"event": "quorum_ready", "quorum_id": 1, "step": 0}\n'
            '{"event": "heal_begin", "step": 2}\n'
            '{"event": "commit", "step": 2}\n'
        )
        proc = subprocess.run(
            [sys.executable, "-m", "torchft_tpu.analysis.protocol",
             "--skip-model", "--conformance", str(bad)],
            capture_output=True, text=True, cwd=REPO,
        )
        assert proc.returncode == 1, proc.stdout + proc.stderr
        assert "healing-commit" in proc.stdout

    def test_model_check_cli_single_config(self):
        proc = subprocess.run(
            [sys.executable, "-m", "torchft_tpu.analysis.protocol",
             "--config", "sync-2g", "--json"],
            capture_output=True, text=True, cwd=REPO,
        )
        assert proc.returncode == 0, proc.stdout + proc.stderr
        doc = json.loads(proc.stdout)
        assert doc["ok"] is True
        assert doc["model"]["sync-2g"]["violations"] == []
        assert doc["model"]["sync-2g"]["states"] > 100
        assert doc["model"]["sync-2g"]["truncated"] is False
        assert doc["model"]["sync-2g"]["approximate"] is False
