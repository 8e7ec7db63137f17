"""Diagnosis plane (ISSUE 12): always-on profilers, collapsed-stack
exactness, the latch→capture trigger engine, bundle schema round-trips,
and the empty-surface status hints.

The end-to-end proof (injected straggler → exactly one bundle with the
delay frame dominant in the victim's native hot stack) lives in the
``diagnose_straggler`` faultmatrix scenario; these are the fast units.
"""

from __future__ import annotations

import json
import os
import threading
import time
from typing import Dict

import pytest

from torchft_tpu import telemetry
from torchft_tpu.telemetry import profiler as prof
from torchft_tpu.telemetry.diagnosis import (
    TRIGGER_EVENTS,
    DiagnosisEngine,
    read_bundles,
)


@pytest.fixture(autouse=True)
def _clean_telemetry():
    telemetry.reset()
    yield
    telemetry.reset()


# ---------------------------------------------------------------------------
# collapsed-stack (folded) utilities
# ---------------------------------------------------------------------------


class TestFolded:
    def test_parse_render_roundtrip(self):
        text = "a;b;c 3\nx;y 1\n"
        assert prof.render_folded(prof.parse_folded(text)) == text

    def test_merge_exact_across_processes(self):
        # the cross-process merge contract: counts are integers on
        # identical keys, so merge = elementwise addition — EXACT, the
        # same property the lathist grid gives histograms
        a = "dp.pump;run;hop 10\ndp.pump;run;idle 4\nrpc.serve;loop 2\n"
        b = "dp.pump;run;hop 7\nblob.serve;conn 1\n"
        merged = prof.parse_folded(prof.merge_folded(a, b))
        pa, pb = prof.parse_folded(a), prof.parse_folded(b)
        for key in set(pa) | set(pb):
            assert merged[key] == pa.get(key, 0) + pb.get(key, 0)
        assert merged["dp.pump;run;hop"] == 17

    def test_subtract_is_window(self):
        before = "a;b 5\nc;d 2\n"
        after = "a;b 9\nc;d 2\ne;f 3\n"
        window = prof.parse_folded(prof.subtract_folded(after, before))
        assert window == {"a;b": 4, "e;f": 3}  # zero-count keys dropped

    def test_subtract_tolerates_reset(self):
        # a reset between snapshots must clamp at 0, not go negative
        assert prof.parse_folded(
            prof.subtract_folded("a;b 1\n", "a;b 5\n")
        ) == {}

    def test_malformed_lines_skipped(self):
        assert prof.parse_folded("garbage\na;b notanum\nx;y 2\n") == {
            "x;y": 2
        }


# ---------------------------------------------------------------------------
# Python sampler
# ---------------------------------------------------------------------------


class TestPySampler:
    def test_sample_once_names_thread_and_function(self):
        stop = threading.Event()

        def parked_in_named_function():
            stop.wait(5.0)

        t = threading.Thread(
            target=parked_in_named_function, name="tft_test_parked",
            daemon=True,
        )
        t.start()
        try:
            s = prof.PyStackSampler(hz=0)  # manual ticks only
            n = s.sample_once()
            assert n >= 1
            folded = s.folded()
            mine = [
                line for line in folded.splitlines()
                if line.startswith("tft_test_parked;")
            ]
            assert mine, folded
            assert "parked_in_named_function" in mine[0]
            assert s.samples_total() == n
            s.reset()
            assert s.folded() == "" and s.samples_total() == 0
        finally:
            stop.set()
            t.join()

    def test_metric_counts_py_plane(self):
        before = telemetry.PROF_SAMPLES.labels(plane="py").value
        s = prof.PyStackSampler(hz=0)
        n = s.sample_once()
        assert (
            telemetry.PROF_SAMPLES.labels(plane="py").value - before == n
        )

    def test_disarmed_starts_no_thread(self):
        s = prof.PyStackSampler(hz=0)
        assert s.ensure_started()._thread is None
        s.set_hz(50)
        try:
            assert s._thread is not None
            deadline = time.monotonic() + 2.0
            while s.samples_total() == 0 and time.monotonic() < deadline:
                time.sleep(0.02)
            assert s.samples_total() > 0
        finally:
            s.stop()


# ---------------------------------------------------------------------------
# native sampler (through the C ABI)
# ---------------------------------------------------------------------------


def _dp_pair():
    from torchft_tpu import _native

    a = _native.NativeDataPlane(0, 2, nstripes=2)
    b = _native.NativeDataPlane(1, 2, nstripes=2)
    b.connect(0, "127.0.0.1", a.port, 5000)
    a.wait_ready(5000)
    b.wait_ready(5000)
    return a, b


def _dp_traffic(a, b, rounds: int = 30, tag0: int = 1):
    import numpy as np

    bufs = [np.ones(1 << 16, dtype=np.float32) for _ in range(2)]

    def run(dp, buf):
        for t in range(rounds):
            dp.allreduce(
                buf.ctypes.data, buf.size, "avg", tag=tag0 + t,
                timeout_ms=20000,
            )

    threads = [
        threading.Thread(target=run, args=(a, bufs[0]), daemon=True),
        threading.Thread(target=run, args=(b, bufs[1]), daemon=True),
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert bufs[0][0] == 1.0


class TestNativeProfiler:
    def test_armed_samples_dp_pump(self):
        from torchft_tpu import _native

        _native.prof_reset()
        _native.prof_set_hz(199.0)
        try:
            a, b = _dp_pair()
            try:
                _dp_traffic(a, b, rounds=60)
                time.sleep(0.2)
            finally:
                a.close()
                b.close()
            folded = _native.prof_snapshot()
            assert any(
                line.startswith("dp.pump;")
                for line in folded.splitlines()
            ), folded[:500]
            assert _native.prof_samples_total() > 0
            # counts fold into the py-side metric on poll
            before = telemetry.PROF_SAMPLES.labels(plane="native").value
            prof.poll_native_samples()
            assert (
                telemetry.PROF_SAMPLES.labels(plane="native").value
                > before
            )
            _native.prof_reset()
            assert _native.prof_snapshot() == ""
            assert _native.prof_samples_total() == 0
        finally:
            _native.prof_set_hz(prof.env_hz())

    def test_disarmed_profiler_zero_cost_on_dp_hop(self):
        # the ISSUE 12 satellite: a disarmed profiler adds ZERO to the
        # dp.hop hot path — the snapshot is identical (empty) before and
        # after real hop traffic, and no sample is ever recorded
        from torchft_tpu import _native

        _native.prof_set_hz(0.0)
        _native.prof_reset()
        try:
            before = _native.prof_snapshot()
            samples_before = _native.prof_samples_total()
            a, b = _dp_pair()
            try:
                _dp_traffic(a, b, rounds=40)
            finally:
                a.close()
                b.close()
            assert _native.prof_snapshot() == before == ""
            assert _native.prof_samples_total() == samples_before == 0
        finally:
            _native.prof_set_hz(prof.env_hz())


# ---------------------------------------------------------------------------
# trigger engine
# ---------------------------------------------------------------------------


def _mk_engine(tmp_path, **kw) -> DiagnosisEngine:
    kw.setdefault("directory", str(tmp_path / "diag"))
    kw.setdefault("replica_id", "g1")
    kw.setdefault("window_s", 0.01)
    kw.setdefault("burst_hz", 0.0)  # units don't need real burst samples
    kw.setdefault("synchronous", True)
    os.makedirs(kw["directory"], exist_ok=True)
    return DiagnosisEngine(**kw)


_TRIGGER_FIXTURE: Dict[str, Dict] = {
    "straggler_detected": {"group": "g1", "p50_s": 0.4},
    "slo_breach": {"slo": "step_time", "threshold_s": 0.5},
    "watchdog_stall": {"step": 9, "elapsed_s": 120.0},
    "divergence_detected": {"step": 11, "fence": False},
}


class TestTriggerEngine:
    def test_debounce_once_per_episode_all_four(self, tmp_path):
        # every trigger captures exactly once per episode, across ALL
        # four latch events; the matching *_cleared re-arms; latches
        # with no cleared event re-arm only after rearm_s
        now = [0.0]
        eng = _mk_engine(tmp_path, rearm_s=600.0, clock=lambda: now[0])
        eng.install()
        try:
            for kind, fields in _TRIGGER_FIXTURE.items():
                telemetry.emit(kind, **fields)
                telemetry.emit(kind, **fields)  # same episode: debounced
            assert eng.bundle_count == len(TRIGGER_EVENTS)

            # the two clearable triggers re-arm on their *_cleared
            telemetry.emit("straggler_cleared", group="g1")
            telemetry.emit("slo_recovered", slo="step_time")
            for kind in ("straggler_detected", "slo_breach"):
                telemetry.emit(kind, **_TRIGGER_FIXTURE[kind])
            assert eng.bundle_count == len(TRIGGER_EVENTS) + 2

            # watchdog/divergence have no cleared event: still latched...
            telemetry.emit("watchdog_stall", **_TRIGGER_FIXTURE["watchdog_stall"])
            telemetry.emit(
                "divergence_detected",
                **_TRIGGER_FIXTURE["divergence_detected"],
            )
            assert eng.bundle_count == len(TRIGGER_EVENTS) + 2
            # ...until the re-arm window passes
            now[0] += 601.0
            telemetry.emit("watchdog_stall", **_TRIGGER_FIXTURE["watchdog_stall"])
            telemetry.emit(
                "divergence_detected",
                **_TRIGGER_FIXTURE["divergence_detected"],
            )
            assert eng.bundle_count == len(TRIGGER_EVENTS) + 4
        finally:
            eng.remove()

    def test_one_capture_per_process_across_engines(self, tmp_path):
        # review fix: the burst boost mutates the SHARED samplers, so a
        # subject-less latch that fans out to every installed engine
        # must produce ONE capture, not one per engine — a losing engine
        # would save the winner's burst rate as its own "pre-burst"
        # restore value (leaving the process at burst Hz forever) and
        # write a duplicate bundle for the same incident. The guard is
        # acquired on the EMITTING thread before the capture thread
        # spawns, so the second engine's fan-out deterministically
        # loses the try-acquire.
        pre_hz = prof.PROFILER.hz
        a = _mk_engine(tmp_path, synchronous=False, window_s=0.05)
        b = _mk_engine(tmp_path, synchronous=False, window_s=0.05)
        a.install()
        b.install()
        try:
            telemetry.emit("divergence_detected", step=3, fence=False)
            deadline = time.monotonic() + 5.0
            while (
                time.monotonic() < deadline
                and a.bundle_count + b.bundle_count < 1
            ):
                time.sleep(0.01)
            time.sleep(0.2)  # slack for a (buggy) second capture to land
            assert a.bundle_count + b.bundle_count == 1
            assert prof.PROFILER.hz == pre_hz
        finally:
            a.remove()
            b.remove()

    def test_distinct_slos_are_distinct_episodes(self, tmp_path):
        # review fix: the two SLOs share one event kind but are
        # independent streams — a live step_time episode must not
        # swallow a rejoin breach, and rejoin's recovery must not
        # re-arm step_time
        eng = _mk_engine(tmp_path).install()
        try:
            telemetry.emit("slo_breach", slo="step_time")
            telemetry.emit("slo_breach", slo="rejoin_commit")
            assert eng.bundle_count == 2
            telemetry.emit("slo_recovered", slo="rejoin_commit")
            telemetry.emit("slo_breach", slo="step_time")  # still latched
            assert eng.bundle_count == 2
            telemetry.emit("slo_breach", slo="rejoin_commit")  # re-armed
            assert eng.bundle_count == 3
        finally:
            eng.remove()

    def test_bundle_names_carry_pid(self, tmp_path):
        # review fix: process-local events can capture on every replica
        # sharing one fleet dir in the same second — the pid keeps the
        # bundle dirs from silently merging
        eng = _mk_engine(tmp_path).install()
        try:
            telemetry.emit("watchdog_stall", step=1)
        finally:
            eng.remove()
        assert f"_{os.getpid()}_" in eng.bundles[0]

    def test_remote_subject_filtered(self, tmp_path):
        eng = _mk_engine(tmp_path).install()
        try:
            telemetry.emit("straggler_detected", group="SOME_OTHER_GROUP")
            assert eng.bundle_count == 0
            # prefix matching both ways (manager ids carry uuid suffixes)
            telemetry.emit("straggler_detected", group="g1-uuid-suffix")
            assert eng.bundle_count == 1
        finally:
            eng.remove()

    def test_disabled_without_dir(self, tmp_path, monkeypatch):
        monkeypatch.delenv("TORCHFT_DIAG_DIR", raising=False)
        eng = DiagnosisEngine(
            directory=None, replica_id="g1", synchronous=True
        )
        eng.install()  # no-op: disabled
        telemetry.emit("watchdog_stall", step=1)
        assert eng.bundle_count == 0

    def test_bundle_schema_and_capture_contents(self, tmp_path):
        eng = _mk_engine(tmp_path, window_s=0.05)
        eng.install()
        try:
            telemetry.emit("slo_breach", slo="step_time", step=3)
        finally:
            eng.remove()
        bundles = read_bundles(eng.directory)
        assert len(bundles) == 1
        b = bundles[0]
        assert b["schema"] == 1
        assert b["trigger"]["event"] == "slo_breach"
        assert b["replica_id"] == "g1"
        assert set(b["files"]) >= {
            "native_folded", "python_folded", "flight", "jax_trace"
        }
        d = b["_dir"]
        for fname in ("bundle.json", "native.folded", "python.folded",
                      "flight.json"):
            assert os.path.isfile(os.path.join(d, fname)), fname
        # lathist deltas keyed by the native op set when the plane loads
        assert isinstance(b["lathist"], dict)
        with open(os.path.join(d, "flight.json"), encoding="utf-8") as f:
            flight = json.load(f)
        assert "entries" in flight and "first_stuck" in flight
        # the capture itself is announced
        kinds = [e["event"] for e in telemetry.EVENTS.recent()]
        assert "diagnosis_captured" in kinds
        assert (
            telemetry.DIAGNOSIS_BUNDLES.labels(trigger="slo_breach").value
            == 1
        )

    def test_bundle_roundtrips_through_postmortem_bundles(self, tmp_path):
        # the ISSUE 12 satellite: bundle schema round-trips through
        # `postmortem --bundles` — latch → capture → evidence on ONE
        # causal timeline, from disk alone
        eng = _mk_engine(tmp_path)
        eng.install()
        try:
            telemetry.emit("watchdog_stall", step=41, elapsed_s=99.0)
        finally:
            eng.remove()
        from torchft_tpu.telemetry import postmortem

        report = postmortem.analyze(
            str(tmp_path), bundles_dir=eng.directory
        )
        assert len(report["bundles"]) == 1
        assert report["bundles"][0]["trigger"]["event"] == "watchdog_stall"
        caps = [
            r for r in report["timeline"]
            if r.get("k") == "diagnosis_captured"
        ]
        assert len(caps) == 1
        assert caps[0]["st"] == 41  # the trigger's step coordinate
        assert caps[0]["path"] == report["bundles"][0]["_dir"]
        # without the flag the timeline stays bundle-free
        assert postmortem.analyze(str(tmp_path))["bundles"] == []
        # and the CLI path agrees
        rc = postmortem.main(
            [str(tmp_path), "--bundles", eng.directory]
        )
        assert rc == 0

    def test_burst_boost_restores_rate(self, tmp_path):
        sampler = prof.PROFILER
        before = sampler.hz
        eng = _mk_engine(tmp_path, burst_hz=123.0, window_s=0.05)
        eng.install()
        try:
            telemetry.emit("slo_breach", slo="step_time")
        finally:
            eng.remove()
        assert sampler.hz == before  # boosted for the window, restored
        assert eng.bundle_count == 1


# ---------------------------------------------------------------------------
# satellites: unified crash-time evidence + empty-surface hints
# ---------------------------------------------------------------------------


class TestFlightDumpStacks:
    def test_dump_carries_live_python_thread_stacks(self, tmp_path):
        stop = threading.Event()

        def wedged_in_named_place():
            stop.wait(5.0)

        t = threading.Thread(
            target=wedged_in_named_place, name="tft_test_wedged",
            daemon=True,
        )
        t.start()
        try:
            rec = telemetry.FlightRecorder(size=16)
            rec.record_issue("allreduce", "test", 128)
            os.environ["TORCHFT_FLIGHT_DIR"] = str(tmp_path)
            try:
                path = rec.dump("manual", force=True)
            finally:
                os.environ.pop("TORCHFT_FLIGHT_DIR", None)
            assert path
            with open(path, encoding="utf-8") as f:
                payload = json.load(f)
            stacks = payload["py_stacks"]
            mine = [
                s for s in stacks if s["thread"] == "tft_test_wedged"
            ]
            assert mine, [s["thread"] for s in stacks]
            assert any(
                "wedged_in_named_place" in fr for fr in mine[0]["frames"]
            )
        finally:
            stop.set()
            t.join()


class TestStatusHints:
    def test_lighthouse_diagnosis_json_empty_then_ok(self):
        import urllib.request

        from datetime import timedelta

        from torchft_tpu.coordination import (
            LighthouseClient,
            LighthouseServer,
        )

        lh = LighthouseServer(bind="[::]:0", min_replicas=1)
        try:
            base = lh.address()
            with urllib.request.urlopen(
                base + "/diagnosis.json", timeout=5
            ) as resp:
                doc = json.loads(resp.read().decode())
            # a scraper can tell "fleet wired, nothing captured" from a
            # bare empty shape (the ambiguity that bit PR 11's bring-up)
            assert doc["status"] == "empty"
            assert doc["bundles_total"] == 0

            client = LighthouseClient(
                base.split("//", 1)[-1],
                connect_timeout=timedelta(seconds=5),
            )
            try:
                client.heartbeat(
                    "repl_a",
                    timeout=timedelta(seconds=5),
                    telemetry_payload={
                        "step": 12,
                        "diag_bundles": 2,
                        "diag_last": "diag_17_straggler_detected_2",
                        "diag_dir": "/tmp/diag",
                    },
                )
            finally:
                client.close()
            with urllib.request.urlopen(
                base + "/diagnosis.json", timeout=5
            ) as resp:
                doc = json.loads(resp.read().decode())
            assert doc["status"] == "ok"
            assert doc["bundles_total"] == 2
            assert doc["replicas"]["repl_a"]["bundles"] == 2
            assert (
                doc["replicas"]["repl_a"]["last"]
                == "diag_17_straggler_detected_2"
            )

            # review fix: a cap overflow replaces the stored value with
            # a LOUD marker instead of silently serving the stale
            # predecessor's evidence path as if it were current
            client = LighthouseClient(
                base.split("//", 1)[-1],
                connect_timeout=timedelta(seconds=5),
            )
            try:
                client.heartbeat(
                    "repl_a",
                    timeout=timedelta(seconds=5),
                    telemetry_payload={
                        "step": 13,
                        "diag_bundles": 3,
                        "diag_last": "x" * 300,
                        "diag_dir": "/d/" + "y" * 600,
                    },
                )
            finally:
                client.close()
            with urllib.request.urlopen(
                base + "/diagnosis.json", timeout=5
            ) as resp:
                doc = json.loads(resp.read().decode())
            assert doc["replicas"]["repl_a"]["last"] == "(oversized)"
            assert doc["replicas"]["repl_a"]["dir"] == "(oversized)"
        finally:
            lh.shutdown()
