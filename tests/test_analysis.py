"""Tests for the static-analysis suite (``python -m torchft_tpu.analysis``).

Two halves:

* **fixture tests** — each seeded-bug file under ``tests/fixtures/analysis``
  must be caught by exactly the rule it seeds, and the ``clean.py`` twin
  must pass every rule (the analyzers are themselves code under test);
* **the repo gate** — the real tree must come out clean (0 active
  findings, 0 stale suppressions) through the same entry point CI runs.
  This is the thin tier-1 wrapper the doc-drift checks moved into when
  they left ``test_tracing.py``.
"""

import glob
import json
import os
import re
import subprocess
import sys

import pytest

from torchft_tpu.analysis import Baseline, run_all
from torchft_tpu.analysis import concurrency, docdrift, nativelint, wiredrift
from torchft_tpu.analysis.__main__ import main as analysis_main
from torchft_tpu.analysis.base import Finding

REPO = os.path.normpath(os.path.join(os.path.dirname(__file__), ".."))
FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures", "analysis")


def _fixture_findings(name):
    with open(os.path.join(FIXTURES, name), encoding="utf-8") as f:
        return concurrency.analyze_source(name, f.read())


def _rules(findings):
    return {f.rule for f in findings}


# ---------------------------------------------------------------------------
# concurrency lint fixtures
# ---------------------------------------------------------------------------


class TestConcurrencyFixtures:
    def test_lock_inversion_caught(self):
        finds = _fixture_findings("lock_inversion.py")
        assert "lock-order-cycle" in _rules(finds)
        (f,) = [f for f in finds if f.rule == "lock-order-cycle"]
        assert "self._a" in f.symbol and "self._b" in f.symbol

    def test_blocking_under_lock_caught(self):
        finds = _fixture_findings("blocking_under_lock.py")
        hits = [f for f in finds if f.rule == "blocking-under-lock"]
        assert hits and "sleep" in hits[0].symbol

    def test_callback_under_lock_caught(self):
        finds = _fixture_findings("callback_under_lock.py")
        hits = [f for f in finds if f.rule == "callback-under-lock"]
        assert hits and "set_exception" in hits[0].symbol

    def test_missing_guarded_by_caught(self):
        finds = _fixture_findings("missing_guarded_by.py")
        hits = [f for f in finds if f.rule == "unguarded-shared-write"]
        assert [f.symbol for f in hits] == ["Unguarded._n"]

    def test_guard_not_held_caught(self):
        finds = _fixture_findings("guard_not_held.py")
        hits = [f for f in finds if f.rule == "guard-not-held"]
        assert len(hits) == 1
        assert hits[0].symbol == "BadGuard._n@bump"
        # the annotated, locked write is NOT flagged
        assert not [f for f in finds if f.rule == "unguarded-shared-write"]

    def test_cond_wait_no_loop_caught(self):
        finds = _fixture_findings("cond_wait_no_loop.py")
        assert "cond-wait-no-loop" in _rules(finds)

    def test_unnamed_thread_caught(self):
        finds = _fixture_findings("unnamed_thread.py")
        assert "thread-unnamed" in _rules(finds)

    def test_clean_fixture_passes_every_rule(self):
        finds = _fixture_findings("clean.py")
        assert finds == [], [f.render() for f in finds]

    def test_runtime_modules_all_parse(self):
        """The gate actually covers the whole ISSUE module list."""
        for rel in concurrency.RUNTIME_MODULES:
            assert os.path.exists(os.path.join(REPO, rel)), rel


# ---------------------------------------------------------------------------
# wire-drift fixtures
# ---------------------------------------------------------------------------


class TestWireDriftFixtures:
    def _texts(self):
        with open(os.path.join(FIXTURES, "wire_mismatch.h")) as f:
            hdr = f.read()
        with open(os.path.join(FIXTURES, "wire_mismatch_py.txt")) as f:
            py = f.read()
        return hdr, py

    def test_cpp_python_mismatch_caught(self):
        hdr, py = self._texts()
        finds = wiredrift.check_wire_tags(hdr, py)
        by_symbol = {f.symbol: f for f in finds}
        # STR exists only in the header
        assert "STR" in by_symbol
        assert "missing" in by_symbol["STR"].message
        # F64 value disagrees (2 vs 7)
        assert "F64" in by_symbol
        assert "mismatch" in by_symbol["F64"].message
        # NIL/I64 agree
        assert "NIL" not in by_symbol and "I64" not in by_symbol

    def test_matching_sides_pass(self):
        hdr, _ = self._texts()
        py = "_NIL = 0\n_I64 = 1\n_F64 = 2\n_STR = 3\n"
        assert wiredrift.check_wire_tags(hdr, py) == []

    def test_enum_scrape_implicit_values(self):
        got = wiredrift.scrape_cpp_enum(
            "enum class E { A = 3, B, C = 9, D };", "E"
        )
        assert got == {"A": 3, "B": 4, "C": 9, "D": 10}

    def test_wire_env_drift_both_directions(self):
        # code reads CODEC (documented) and GHOST (undocumented); the doc
        # additionally promises STALE, which nothing reads
        py = {
            "a.py": 'os.environ.get("TORCHFT_WIRE_CODEC")\n'
                    'os.environ.get("TORCHFT_WIRE_GHOST")\n',
        }
        doc = (
            "| knob | default |\n"
            "| `TORCHFT_WIRE_CODEC` | f32 |\n"
            "| `TORCHFT_WIRE_STALE` | 1 |\n"
        )
        finds = wiredrift.check_wire_env(py, doc)
        msgs = {f.symbol: f.message for f in finds}
        assert "TORCHFT_WIRE_GHOST" in msgs
        assert "missing from" in msgs["TORCHFT_WIRE_GHOST"]
        assert "TORCHFT_WIRE_STALE" in msgs
        assert "no code reads" in msgs["TORCHFT_WIRE_STALE"]
        assert "TORCHFT_WIRE_CODEC" not in msgs

    def test_wire_env_clean_tree(self):
        # the live repo's TORCHFT_WIRE_* knob family must match the
        # docs/wire_plane.md registry exactly (the PR 6 satellite)
        finds = [f for f in wiredrift.run() if f.rule == "wire-env-drift"]
        assert finds == []

    def test_heal_env_drift_both_directions(self):
        # code reads SOURCES (documented) and GHOST (undocumented); the
        # doc additionally promises STALE, which nothing reads
        py = {
            "a.py": 'os.environ.get("TORCHFT_HEAL_SOURCES")\n'
                    'os.environ.get("TORCHFT_HEAL_GHOST")\n',
        }
        doc = (
            "| knob | default |\n"
            "| `TORCHFT_HEAL_SOURCES` | 4 |\n"
            "| `TORCHFT_HEAL_STALE` | 1 |\n"
        )
        finds = wiredrift.check_heal_env(py, doc)
        msgs = {f.symbol: f.message for f in finds}
        assert "TORCHFT_HEAL_GHOST" in msgs
        assert "missing from" in msgs["TORCHFT_HEAL_GHOST"]
        assert "TORCHFT_HEAL_STALE" in msgs
        assert "no code reads" in msgs["TORCHFT_HEAL_STALE"]
        assert "TORCHFT_HEAL_SOURCES" not in msgs

    def test_heal_env_clean_tree(self):
        # the live repo's TORCHFT_HEAL_* knob family must match the
        # docs/heal_plane.md registry exactly (the ISSUE 9 satellite)
        finds = [f for f in wiredrift.run() if f.rule == "heal-env-drift"]
        assert finds == []

    def test_obs_env_covers_tsdb_family(self):
        # the obs-env-drift rule must enforce the TORCHFT_TSDB_* family
        # in BOTH directions, like the SLO/straggler families before it
        py = {
            "a.py": 'os.environ.get("TORCHFT_TSDB_RETAIN")\n'
                    'os.environ.get("TORCHFT_TSDB_GHOST")\n',
        }
        doc = (
            "| knob | default |\n"
            "| `TORCHFT_TSDB_RETAIN` | 512 |\n"
            "| `TORCHFT_TSDB_STALE` | 1 |\n"
        )
        finds = wiredrift.check_obs_env(py, doc)
        msgs = {f.symbol: f.message for f in finds}
        assert "missing from" in msgs["TORCHFT_TSDB_GHOST"]
        assert "no code reads" in msgs["TORCHFT_TSDB_STALE"]
        assert "TORCHFT_TSDB_RETAIN" not in msgs

    def test_obs_env_covers_prof_and_diag_families(self):
        # the ISSUE 12 satellite: the obs-env-drift rule must enforce
        # the TORCHFT_PROF_* / TORCHFT_DIAG_* families in BOTH
        # directions, like the five families before them
        py = {
            "a.py": 'os.environ.get("TORCHFT_PROF_HZ")\n'
                    'os.environ.get("TORCHFT_PROF_GHOST")\n'
                    'os.environ.get("TORCHFT_DIAG_DIR")\n'
                    'os.environ.get("TORCHFT_DIAG_GHOST")\n',
        }
        doc = (
            "| knob | default |\n"
            "| `TORCHFT_PROF_HZ` | 11 |\n"
            "| `TORCHFT_PROF_STALE` | 1 |\n"
            "| `TORCHFT_DIAG_DIR` | unset |\n"
            "| `TORCHFT_DIAG_STALE` | 1 |\n"
        )
        finds = wiredrift.check_obs_env(py, doc)
        msgs = {f.symbol: f.message for f in finds}
        for ghost in ("TORCHFT_PROF_GHOST", "TORCHFT_DIAG_GHOST"):
            assert ghost in msgs and "missing from" in msgs[ghost]
        for stale in ("TORCHFT_PROF_STALE", "TORCHFT_DIAG_STALE"):
            assert stale in msgs and "no code reads" in msgs[stale]
        assert "TORCHFT_PROF_HZ" not in msgs
        assert "TORCHFT_DIAG_DIR" not in msgs

    def test_obs_env_clean_tree(self):
        # the live repo's observability knob families (SLO / straggler /
        # blackbox / divergence / tsdb / regression / prof / diag) must
        # match the docs/observability.md registries exactly
        finds = [f for f in wiredrift.run() if f.rule == "obs-env-drift"]
        assert finds == []


# ---------------------------------------------------------------------------
# doc-drift fixtures
# ---------------------------------------------------------------------------


class TestDocDriftFixtures:
    DOC = (
        "## Metrics\n"
        "| `tft_ok_total` | counter |\n"
        "| `tft_ghost_total` | counter |\n"
    )

    def test_doc_only_and_code_only_both_flagged(self):
        finds = docdrift.check_metric_catalog(
            self.DOC, {"tft_ok_total", "tft_unseen_total"}
        )
        msgs = {f.symbol: f.message for f in finds}
        assert "tft_ghost_total" in msgs  # documented, not registered
        assert "tft_unseen_total" in msgs  # registered, not documented
        assert "tft_ok_total" not in msgs

    def test_fault_site_doc_table(self):
        doc = "## Site catalog\n| `rpc.send` | x |\n| `ghost.site` | x |\n"
        finds = docdrift.check_fault_sites_doc(doc, ("rpc.send", "cma.pull"))
        symbols = {f.symbol for f in finds}
        assert symbols == {"ghost.site", "cma.pull"}


# ---------------------------------------------------------------------------
# baseline semantics
# ---------------------------------------------------------------------------


class TestBaseline:
    def _finding(self):
        return Finding("blocking-under-lock", "x.py", 3, "C.m:sleep", "msg")

    def test_suppression_matches_by_key_not_line(self):
        f = self._finding()
        bl = Baseline(suppressions=[{"key": f.key, "reason": "intentional"}])
        active, suppressed, stale = bl.apply([f])
        assert active == [] and suppressed == [f] and stale == []
        # line number changes do not churn the baseline
        f2 = Finding(f.rule, f.path, 99, f.symbol, f.message)
        active, suppressed, stale = bl.apply([f2])
        assert active == [] and stale == []

    def test_stale_suppression_is_an_error(self, tmp_path):
        """A baseline entry that no longer fires must fail the gate."""
        f = self._finding()
        bl = Baseline(suppressions=[
            {"key": f.key, "reason": "live"},
            {"key": "blocking-under-lock:gone.py:C.x:sleep",
             "reason": "the code this matched was deleted"},
        ])
        active, suppressed, stale = bl.apply([f])
        assert active == []
        assert [e["key"] for e in stale] == [
            "blocking-under-lock:gone.py:C.x:sleep"
        ]
        # end to end: the CLI exits 1 on the stale entry even though the
        # tree itself is clean
        path = tmp_path / "baseline.json"
        real = Baseline.load(
            os.path.join(REPO, "torchft_tpu", "analysis", "baseline.json")
        )
        doc = {"suppressions": real.suppressions + [
            {"key": "blocking-under-lock:gone.py:C.x:sleep",
             "reason": "stale on purpose"},
        ]}
        path.write_text(json.dumps(doc))
        assert analysis_main(["--baseline", str(path)]) == 1

    def test_baseline_entries_require_reason(self, tmp_path):
        path = tmp_path / "b.json"
        path.write_text(json.dumps({"suppressions": [{"key": "x"}]}))
        with pytest.raises(ValueError):
            Baseline.load(str(path))


# ---------------------------------------------------------------------------
# the repo gate (tier-1 wrapper)
# ---------------------------------------------------------------------------


class TestRepoGate:
    def test_gate_clean_in_process(self):
        """0 active findings, 0 stale suppressions on the real tree, via
        the same code path as the CLI."""
        per_analyzer = run_all()
        baseline = Baseline.load(
            os.path.join(REPO, "torchft_tpu", "analysis", "baseline.json")
        )
        allf = [f for finds in per_analyzer.values() for f in finds]
        active, _suppressed, stale = baseline.apply(allf)
        assert active == [], [f.render() for f in active]
        assert stale == [], [e["key"] for e in stale]
        # every suppression carries a real justification
        for e in baseline.suppressions:
            assert e["reason"] and "TODO" not in e["reason"]

    def test_cli_exit_code_and_json(self):
        """`python -m torchft_tpu.analysis --json` — the exact CI
        invocation — exits 0 and reports ok=true."""
        proc = subprocess.run(
            [sys.executable, "-m", "torchft_tpu.analysis", "--json"],
            capture_output=True, text=True, cwd=REPO,
        )
        assert proc.returncode == 0, proc.stdout + proc.stderr
        doc = json.loads(proc.stdout)
        assert doc["ok"] is True
        assert set(doc["analyzers"]) == {"concurrency", "wiredrift",
                                         "docdrift", "nativelint"}


# ---------------------------------------------------------------------------
# native lint fixtures (ISSUE 15)
# ---------------------------------------------------------------------------


def _native_fixture_findings(*names):
    sources = []
    for name in names:
        with open(os.path.join(FIXTURES, name), encoding="utf-8") as f:
            sources.append((name, f.read()))
    return nativelint.analyze_sources(sources)


class TestNativeLintFixtures:
    def test_lock_order_cycle_caught(self):
        finds = _native_fixture_findings("lock_cycle.cc")
        hits = [f for f in finds if f.rule == "cpp-lock-order-cycle"]
        assert hits, [f.render() for f in finds]
        # the cycle names both mutexes, and the cross-function edge
        # (push -> refill propagation) is what closes it
        assert "mu_a_" in hits[0].symbol and "mu_b_" in hits[0].symbol

    def test_blocking_under_lock_caught(self):
        finds = _native_fixture_findings("blocking_lock.cc")
        hits = [f for f in finds if f.rule == "cpp-blocking-under-lock"]
        assert [f.symbol for f in hits] == ["Server::reply_locked:send"]

    def test_cv_wait_no_loop_caught(self):
        finds = _native_fixture_findings("blocking_lock.cc")
        hits = [f for f in finds if f.rule == "cpp-cv-wait-no-loop"]
        assert len(hits) == 1 and "wait_bad" in hits[0].symbol
        # the predicate-overload twin is NOT flagged
        assert not [f for f in finds if "wait_ok" in f.symbol]

    def test_unannotated_relaxed_atomic_caught(self):
        finds = _native_fixture_findings("relaxed_atomic.h")
        hits = [f for f in finds
                if f.rule == "cpp-atomic-no-order-reason"]
        assert [f.symbol for f in hits] == ["bump_bad:relaxed"]

    def test_clean_native_fixture_passes_every_rule(self):
        finds = _native_fixture_findings("clean_native.cc")
        assert finds == [], [f.render() for f in finds]

    def test_makefile_hdrs_drift_fixture(self):
        with open(os.path.join(FIXTURES, "makefile_hdrs_drift.mk")) as f:
            mk = f.read()
        finds = wiredrift.check_makefile_hdrs(
            mk, ["wire.h", "rpc.h", "newthing.h"]
        )
        by_symbol = {f.symbol: f.message for f in finds}
        assert set(by_symbol) == {"newthing.h", "gone.h"}
        assert "stale" in by_symbol["newthing.h"]
        assert "does not exist" in by_symbol["gone.h"]

    def test_makefile_hdrs_clean_tree(self):
        """Every real native/*.h is in the real Makefile's HDRS."""
        finds = [
            f for f in wiredrift.run()
            if f.rule == "makefile-hdrs-drift"
        ]
        assert finds == [], [f.render() for f in finds]

    def test_native_tree_lints_clean_through_baseline(self):
        """The real native tree: every finding baselined, none active
        (the repo-gate test covers this too; this one names the
        analyzer so a nativelint regression reads as itself)."""
        finds = nativelint.run()
        baseline = Baseline.load(
            os.path.join(REPO, "torchft_tpu", "analysis", "baseline.json")
        )
        active, _suppressed, _stale = baseline.apply(finds)
        assert active == [], [f.render() for f in active]


# ---------------------------------------------------------------------------
# premerge gate-id drift (ISSUE 20 satellite)
# ---------------------------------------------------------------------------


class TestPremergeGateDrift:
    DOC = (
        "### Pre-merge gates\n\nprose\n\n"
        "| gate | what runs |\n"
        "|---|---|\n"
        "| `analysis` | x |\n"
        "| `ghost-gate` | x |\n"
    )
    SCRIPT = (
        'record_gate "analysis" passed 1\n'
        '  record_gate "native-warn" skipped 0\n'
    )

    def test_both_directions_flagged(self):
        finds = docdrift.check_premerge_gates(self.DOC, self.SCRIPT)
        msgs = {f.symbol: f.message for f in finds}
        # documented but never recorded; recorded but undocumented
        assert "ghost-gate" in msgs and "no record_gate" in msgs["ghost-gate"]
        assert "native-warn" in msgs and "missing from" in msgs["native-warn"]
        assert "analysis" not in msgs

    def test_missing_table_is_a_finding(self):
        finds = docdrift.check_premerge_gates("# no section\n", self.SCRIPT)
        assert [f.symbol for f in finds] == ["<table>"]

    def test_missing_record_sites_is_a_finding(self):
        finds = docdrift.check_premerge_gates(self.DOC, "echo hi\n")
        assert [f.symbol for f in finds] == ["<script>"]

    def test_real_script_records_every_gate(self):
        """Every gate in premerge.sh emits a --json record — including
        the clang-tidy skip, which must be VISIBLE, not silent."""
        with open(os.path.join(REPO, "scripts", "premerge.sh")) as f:
            ids = set(re.findall(r'record_gate "([a-z0-9-]+)"', f.read()))
        assert ids == {"analysis", "native-warn", "native-tidy",
                       "faultmatrix-quick", "protocol"}

    def test_clean_tree(self):
        finds = [
            f for f in docdrift.run() if f.rule == "premerge-gate-drift"
        ]
        assert finds == [], [f.render() for f in finds]


# ---------------------------------------------------------------------------
# incremental analysis cache (ISSUE 20 satellite)
# ---------------------------------------------------------------------------


class TestAnalysisCache:
    def test_fingerprint_tracks_edits_and_adds(self, tmp_path):
        from torchft_tpu.analysis.cache import fingerprint

        (tmp_path / "native").mkdir()
        hdr = tmp_path / "native" / "a.h"
        hdr.write_text("int x;\n")
        pats = ("native/*.h",)
        base = fingerprint(str(tmp_path), pats)
        assert fingerprint(str(tmp_path), pats) == base  # deterministic
        hdr.write_text("int y;\n")
        edited = fingerprint(str(tmp_path), pats)
        assert edited != base  # edit -> new digest
        (tmp_path / "native" / "b.h").write_text("")
        assert fingerprint(str(tmp_path), pats) != edited  # add -> new digest

    def test_edit_refires_hit_replays(self, tmp_path):
        """The correctness contract: unchanged inputs -> the stored
        findings replay verbatim; ANY scanned-file edit -> miss."""
        from torchft_tpu.analysis.cache import AnalysisCache

        (tmp_path / "native").mkdir()
        hdr = tmp_path / "native" / "a.h"
        hdr.write_text("// v1\n")
        cache = AnalysisCache(str(tmp_path))
        assert cache.get("nativelint") is None  # cold
        finds = [Finding("cpp-atomic-no-order-reason", "native/a.h", 3,
                         "bump:relaxed", "msg")]
        cache.put("nativelint", finds)
        warm = AnalysisCache(str(tmp_path))
        assert warm.get("nativelint") == finds
        assert warm.hits == ["nativelint"]
        hdr.write_text("// v2\n")
        stale = AnalysisCache(str(tmp_path))
        assert stale.get("nativelint") is None  # edit -> re-fire

    def test_unknown_analyzer_never_caches(self, tmp_path):
        from torchft_tpu.analysis.cache import AnalysisCache

        cache = AnalysisCache(str(tmp_path))
        cache.put("mystery", [])
        assert cache.get("mystery") is None
        assert not (tmp_path / ".analysis_cache" / "mystery.json").exists()

    def test_corrupt_cache_file_is_a_miss(self, tmp_path):
        from torchft_tpu.analysis.cache import AnalysisCache

        (tmp_path / "native").mkdir()
        (tmp_path / "native" / "a.h").write_text("int x;\n")
        cache = AnalysisCache(str(tmp_path))
        cache.put("nativelint", [])
        (tmp_path / ".analysis_cache" / "nativelint.json").write_text("{oops")
        assert AnalysisCache(str(tmp_path)).get("nativelint") is None

    def test_cached_gate_verdict_identical_to_fresh(self):
        """End to end on the real tree: a warm cache replays byte-equal
        finding keys for every analyzer."""
        from torchft_tpu.analysis.cache import AnalysisCache

        cold_cache = AnalysisCache()
        cold = run_all(cache=cold_cache)
        warm_cache = AnalysisCache()
        warm = run_all(cache=warm_cache)
        assert set(warm_cache.hits) == {"concurrency", "wiredrift",
                                        "docdrift", "nativelint"}
        assert warm_cache.misses == []
        for name in cold:
            assert [f.key for f in cold[name]] == \
                [f.key for f in warm[name]], name


# ---------------------------------------------------------------------------
# telemetry_delta.h nativelint pin (ISSUE 20 satellite)
# ---------------------------------------------------------------------------


class TestTelemetryDeltaPin:
    REL = os.path.join("native", "telemetry_delta.h")

    def test_file_is_in_the_scanned_set(self):
        scanned = set()
        for pat in nativelint.NATIVE_GLOBS:
            scanned.update(glob.glob(os.path.join(REPO, pat)))
        assert os.path.join(REPO, self.REL) in scanned

    def test_clean_tree_zero_findings(self):
        """PR 16's delta ledger is mutex-guarded by design — zero atomic
        sites, so zero annotation findings; this pins that a future
        atomic added without a reason lands as an ACTIVE finding."""
        finds = [f for f in nativelint.run() if "telemetry_delta" in f.path]
        assert finds == [], [f.render() for f in finds]

    def test_seeded_unannotated_atomic_fires(self):
        """The pin is only meaningful if the lint would actually catch a
        regression in THIS file: seed one unannotated relaxed op into
        the real source and watch the rule fire."""
        with open(os.path.join(REPO, self.REL), encoding="utf-8") as f:
            src = f.read()
        seeded = src + (
            "\ninline void tdx_bump(std::atomic<unsigned long>& c) {\n"
            "  c.fetch_add(1, std::memory_order_relaxed);\n"
            "}\n"
        )
        finds = nativelint.analyze_sources([("telemetry_delta.h", seeded)])
        hits = [f for f in finds
                if f.rule == "cpp-atomic-no-order-reason"
                and "tdx_bump" in f.symbol]
        assert hits, [f.render() for f in finds]
