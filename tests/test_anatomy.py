"""Step-anatomy plane tests (ISSUE 8): ledger phase accounting, the
wire-stage shim, native latency histograms (+ exact cross-process merge),
the lighthouse piggyback round-trip, burn-rate SLO math and straggler
latch/unlatch hysteresis."""

import json
import os
import subprocess
import sys
import threading
import time
import urllib.request
from datetime import timedelta

import pytest

from torchft_tpu import telemetry
from torchft_tpu.telemetry.anatomy import (
    BARRIER_PHASES,
    LOG2_BUCKETS,
    PHASES,
    StepLedger,
    lathist_quantile,
    merge_lathist,
)
from torchft_tpu.telemetry.slo import BurnRateSlo, StragglerDetector


@pytest.fixture(autouse=True)
def _clean_telemetry():
    telemetry.reset()
    yield
    telemetry.reset()


# ---------------------------------------------------------------------------
# ledger accounting
# ---------------------------------------------------------------------------


class TestStepLedger:
    def test_phases_sum_to_measured_wall_clock(self):
        led = StepLedger()
        led.tick(0)
        t0 = time.perf_counter()
        led.record("compute", 0.02)
        led.record("quorum_wait", 0.01)
        led.record("commit_barrier", 0.005)
        time.sleep(0.06)
        row = led.tick(1)
        wall_measured = time.perf_counter() - t0
        assert row is not None
        # the row's phases sum to the ledger's wall EXACTLY (idle is the
        # residual) ...
        assert sum(row["phases"].values()) == pytest.approx(
            row["wall_s"], rel=1e-9
        )
        # ... and the ledger's wall agrees with an external stopwatch to
        # within the acceptance tolerance (5%)
        assert row["wall_s"] == pytest.approx(wall_measured, rel=0.05)
        assert row["phases"]["idle"] > 0
        assert row["phases"]["compute"] == pytest.approx(0.02)

    def test_local_excludes_barrier_phases(self):
        led = StepLedger()
        led.tick(0)
        led.record("compute", 0.01)
        for p in BARRIER_PHASES:
            led.record(p, 0.02)
        time.sleep(0.12)
        row = led.tick(1)
        expected = row["wall_s"] - 0.02 * len(BARRIER_PHASES)
        assert row["local_s"] == pytest.approx(expected, rel=1e-6)

    def test_idle_clamped_when_phases_overlap_wall(self):
        led = StepLedger()
        led.tick(0)
        # an off-main-thread heal can record more than the interval wall
        led.record("heal", 60.0)
        row = led.tick(1)
        assert row["phases"].get("idle", 0.0) == 0.0  # zero phases elided
        assert row["local_s"] == 0.0  # clamped, never negative

    def test_first_tick_returns_none(self):
        led = StepLedger()
        assert led.tick(0) is None

    def test_summary_percentiles_are_exact(self):
        led = StepLedger()
        led.tick(0)
        walls = []
        for i in range(5):
            led.record("compute", 0.001 * (i + 1))
            time.sleep(0.01)
            walls.append(led.tick(i + 1)["wall_s"])
        s = led.summary()
        walls.sort()
        assert s["steps"] == 5
        # exact interpolated median of the retained rows, not a
        # log2-bucket estimate (one bucket per octave would be +-50%)
        assert s["wall_p50_s"] == pytest.approx(walls[2], abs=1e-5)
        assert s["phases"]["compute"]["p50_s"] == pytest.approx(0.003)

    def test_every_phase_observed_every_step(self):
        led = StepLedger()
        led.tick(0)
        led.record("compute", 0.01)
        led.tick(1)
        for phase in PHASES:
            child = telemetry.STEP_PHASE_SECONDS.labels(phase=phase)
            assert child.count == 1, phase  # zeros observed for inactive

    def test_local_p50_rolls_with_window(self):
        led = StepLedger(window=4)
        led.tick(0)
        for i in range(8):
            time.sleep(0.005)
            led.tick(i + 1)
        assert led.local_p50() is not None
        assert len(led.dump()["rows"]) == 4


class TestWireStageShim:
    def test_shim_feeds_ledger_and_metric(self):
        from torchft_tpu.collectives import (
            record_wire_stage,
            wire_stage_snapshot,
        )

        wire_stage_snapshot(reset=True)
        before = telemetry.WIRE_STAGE_SECONDS.labels(stage="wire").value
        record_wire_stage("wire", 0.25)
        snap = wire_stage_snapshot()
        assert snap["wire"] == pytest.approx(0.25)
        after = telemetry.WIRE_STAGE_SECONDS.labels(stage="wire").value
        assert after - before == pytest.approx(0.25)
        # reset moves the mark; the ledger totals stay monotonic
        wire_stage_snapshot(reset=True)
        assert wire_stage_snapshot() == {}
        record_wire_stage("wire", 0.1)
        assert wire_stage_snapshot()["wire"] == pytest.approx(0.1)

    def test_op_thread_wire_stays_out_of_the_step_row(self):
        """An op-thread record_wire_stage feeds the wire totals but NOT
        the step row (it overlaps the main thread's wall clock); a
        main-thread record feeds both."""
        from torchft_tpu.collectives import (
            record_wire_stage,
            wire_stage_snapshot,
        )

        wire_stage_snapshot(reset=True)
        led = telemetry.LEDGER
        led.tick(0)
        t = threading.Thread(
            target=record_wire_stage, args=("wire", 0.5), name="tft_test_op"
        )
        t.start()
        t.join()
        record_wire_stage("wire", 0.125)
        row = led.tick(1)
        assert wire_stage_snapshot()["wire"] == pytest.approx(0.625)
        assert row["phases"].get("wire", 0.0) == pytest.approx(0.125)

    def test_wire_stage_snapshot_reset_then_read(self):
        # a reader's protocol: reset the mark, run, read per-stage totals
        from torchft_tpu.collectives import (
            WIRE_STAGES,
            record_wire_stage,
            wire_stage_snapshot,
        )

        wire_stage_snapshot(reset=True)
        for s in WIRE_STAGES:
            record_wire_stage(s, 0.01)
        snap = wire_stage_snapshot()
        assert set(snap) == set(WIRE_STAGES)


class TestOutlierSurfacing:
    def test_outlier_digest_in_summary_and_flight_dump(self, tmp_path,
                                                       monkeypatch):
        from torchft_tpu.profiling import StepTimer

        monkeypatch.setenv("TORCHFT_FLIGHT_DIR", str(tmp_path))
        t = StepTimer(record_metrics=False)
        t.tick()
        t.mark_heal()
        time.sleep(0.01)
        t.tick()
        assert t.outlier_digest() and t.outlier_digest()[0]["tags"] == ["heal"]
        led = telemetry.LEDGER
        led.attach_timer(t)
        led.tick(0)
        time.sleep(0.005)
        led.tick(1)
        assert led.summary()["outliers"][0]["tags"] == ["heal"]
        # ONE handler, one evidence dir: the flight dump embeds the ledger
        path = telemetry.FLIGHT.dump("manual", force=True)
        assert path is not None and path.startswith(str(tmp_path))
        with open(path) as f:
            payload = json.load(f)
        assert "anatomy" in payload
        assert payload["anatomy"]["rows"], payload["anatomy"]
        assert payload["anatomy"]["summary"]["outliers"][0]["tags"] == ["heal"]


# ---------------------------------------------------------------------------
# native latency histograms
# ---------------------------------------------------------------------------

_CHILD_SNIPPET = """
import json, sys
from torchft_tpu import _native
h, addr = _native.store_create("[::]:0")
c = _native.NativeClient("tcp://" + addr, 5000)
for i in range(int(sys.argv[1])):
    c.call("store.set", {"k": "k%d" % i, "v": b"x"}, 5000)
c.close()
print(json.dumps(_native.lathist_snapshot()))
_native.store_shutdown(h)
"""


class TestNativeLathist:
    def test_bounds_match_python_grid(self):
        from torchft_tpu import _native

        assert tuple(_native.LATHIST_BOUNDS_S) == LOG2_BUCKETS

    def test_snapshot_shape(self):
        from torchft_tpu import _native

        snap = _native.lathist_snapshot()
        assert set(snap) == {
            "dp.hop", "dp.stripe", "rpc.serve", "quorum.fanout"
        }
        for h in snap.values():
            assert len(h["counts"]) == len(LOG2_BUCKETS) + 1  # + overflow
            assert h["count"] == sum(h["counts"])

    def test_merge_exactness_across_two_processes(self):
        """Two processes record independently on the identical fixed
        grid; merging is elementwise integer addition — counts, count and
        sum_ns all add exactly, and the merged quantile is well-defined."""
        from torchft_tpu import _native

        _native.lathist_reset()
        h, addr = _native.store_create("[::]:0")
        try:
            c = _native.NativeClient("tcp://" + addr, 5000)
            for i in range(7):
                c.call("store.set", {"k": f"p{i}", "v": b"x"}, 5000)
            c.close()
        finally:
            _native.store_shutdown(h)
        mine = _native.lathist_snapshot()
        out = subprocess.run(
            [sys.executable, "-c", _CHILD_SNIPPET, "5"],
            capture_output=True, text=True, check=True,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        )
        theirs = json.loads(out.stdout.strip().splitlines()[-1])
        merged = merge_lathist(mine, theirs)
        for op in merged:
            assert merged[op]["count"] == (
                mine[op]["count"] + theirs[op]["count"]
            )
            assert merged[op]["sum_ns"] == (
                mine[op]["sum_ns"] + theirs[op]["sum_ns"]
            )
            assert merged[op]["counts"] == [
                a + b
                for a, b in zip(mine[op]["counts"], theirs[op]["counts"])
            ]
        serve = merged["rpc.serve"]
        # at least the 7+5 sets plus each client's handshake-adjacent ops
        assert serve["count"] >= 12
        q = lathist_quantile(serve, 0.5)
        assert 0 < q < 1.0  # RPC serves are far under a second

    def test_lighthouse_scrapes_latency(self):
        """The acceptance surface: native latency histograms are
        scrapeable on the lighthouse /metrics, and /status.json carries
        the raw mergeable counts."""
        from torchft_tpu.coordination import LighthouseClient, LighthouseServer

        lh = LighthouseServer(bind="[::]:0", min_replicas=1)
        try:
            cli = LighthouseClient(
                lh.address(), connect_timeout=timedelta(seconds=5)
            )
            cli.heartbeat("repX")
            cli.close()
            with urllib.request.urlopen(
                lh.address() + "/metrics", timeout=5
            ) as r:
                text = r.read().decode()
            assert 'torchft_latency_seconds_bucket{op="rpc.serve",le="+Inf"}' \
                in text
            assert "torchft_latency_seconds_count" in text
            with urllib.request.urlopen(
                lh.address() + "/status.json", timeout=5
            ) as r:
                status = json.loads(r.read().decode())
            lat = status["latency"]
            assert lat["rpc.serve"]["count"] >= 1
            assert len(lat["rpc.serve"]["counts"]) == len(LOG2_BUCKETS) + 1
            assert lat["rpc.serve"]["p50_s"] > 0
        finally:
            lh.shutdown()


# ---------------------------------------------------------------------------
# piggyback round-trip
# ---------------------------------------------------------------------------


class TestPiggybackRoundTrip:
    def test_anatomy_scalars_reach_cluster_json(self):
        from torchft_tpu.coordination import LighthouseClient, LighthouseServer
        from torchft_tpu.telemetry.native import poll_cluster

        payload = {
            "summary": json.dumps({"quorums": 1}),
            "anatomy": json.dumps(
                {"steps": 3, "phases": {"compute": {"p50_s": 0.01}}}
            ),
            "local_step_p50_s": 0.125,
            "slo_breach": True,
            "step": 3,
            "stuck": False,
            "last_heal_ts": 0.0,
        }
        lh = LighthouseServer(bind="[::]:0", min_replicas=1)
        try:
            cli = LighthouseClient(
                lh.address(), connect_timeout=timedelta(seconds=5)
            )
            cli.heartbeat("repA", telemetry_payload=payload)
            cli.heartbeat("repB", telemetry_payload={"step": 2})
            cli.close()
            cluster = poll_cluster(lh.address())
            assert cluster is not None
            a = cluster["replicas"]["repA"]
            assert a["local_step_p50_s"] == pytest.approx(0.125)
            assert a["slo_breach"] is True
            assert a["anatomy"]["steps"] == 3
            assert a["anatomy"]["phases"]["compute"]["p50_s"] == 0.01
            b = cluster["replicas"]["repB"]
            assert b["slo_breach"] is False
            assert b["anatomy"] == {}
            # the /metrics scalars next to it
            with urllib.request.urlopen(
                lh.address() + "/metrics", timeout=5
            ) as r:
                text = r.read().decode()
            assert 'torchft_replica_local_step_p50_seconds{replica_id="repA"} 0.125' in text
            assert 'torchft_slo_breach{replica_id="repA"} 1' in text
        finally:
            lh.shutdown()

    def test_manager_payload_carries_anatomy(self):
        """The Manager's piggyback builder includes the new fields (unit:
        the payload shape, not a live quorum — the round trip above and
        the integration soaks cover the wire)."""
        led = telemetry.LEDGER
        led.tick(0)
        time.sleep(0.005)
        led.tick(1)
        import json as _json

        payload = {
            "anatomy": _json.dumps(led.summary(), separators=(",", ":")),
            "local_step_p50_s": float(led.local_p50() or 0.0),
        }
        assert payload["local_step_p50_s"] > 0
        assert _json.loads(payload["anatomy"])["steps"] == 1


# ---------------------------------------------------------------------------
# burn-rate SLO math
# ---------------------------------------------------------------------------


class TestBurnRateSlo:
    def mk(self, **kw):
        kw.setdefault("target", 0.9)       # budget 0.1
        kw.setdefault("fast_s", 10.0)
        kw.setdefault("slow_s", 100.0)
        kw.setdefault("burn", 2.0)
        kw.setdefault("min_events", 2)
        return BurnRateSlo("step_time", 1.0, **kw)

    def test_no_breach_under_budget(self):
        s = self.mk()
        now = 0.0
        for v in [0.5] * 20:
            now += 1
            assert s.observe(v, now=now) is False

    def test_breach_requires_both_windows(self):
        # bad events ONLY in the fast window: slow window burn stays under
        # threshold -> no breach (the blip-suppression property)
        s = self.mk(target=0.5, burn=1.5)  # budget 0.5
        now = 0.0
        for _ in range(80):                # old good events fill slow window
            now += 1
            s.observe(0.5, now=now)
        # now a burst of bad events: fast window (last 10) goes 100% bad
        # (burn 2.0 > 1.5) but the slow window is 10/90 bad (~0.22 burn)
        for _ in range(10):
            now += 1
            s.observe(5.0, now=now)
        assert s.breached is False

    def test_breach_and_single_latch(self):
        s = self.mk()
        telemetry.reset()
        now = 0.0
        for _ in range(8):
            now += 1
            s.observe(5.0, now=now)        # 100% bad: burn 10 > 2 everywhere
        assert s.breached is True
        assert s.breaches == 1             # latched once, not per event
        events = telemetry.EVENTS.recent("slo_breach")
        assert len(events) == 1
        assert events[0]["slo"] == "step_time"
        assert telemetry.SLO_BREACH_TOTAL.labels(slo="step_time").value == 1

    def test_recovery_unlatches_and_emits(self):
        s = self.mk()
        now = 0.0
        for _ in range(8):
            now += 1
            s.observe(5.0, now=now)
        assert s.breached
        now += 50.0                        # bad events age out of fast window
        for _ in range(5):
            now += 1
            s.observe(0.5, now=now)
        assert s.breached is False
        assert len(telemetry.EVENTS.recent("slo_recovered")) == 1

    def test_min_events_guard(self):
        s = self.mk(min_events=5)
        assert s.observe(99.0, now=1.0) is False  # one bad sample: no alarm


# ---------------------------------------------------------------------------
# straggler latch/unlatch hysteresis
# ---------------------------------------------------------------------------


class TestStragglerDetector:
    def test_latch_after_k_and_exactly_one_event(self):
        d = StragglerDetector(factor=1.5, k=3)
        evs = []
        for _ in range(6):
            evs += d.update({"g0": 0.1, "g1": 0.1, "g2": 0.5})
        assert d.stragglers() == ["g2"]
        latched = [e for e in evs if e["event"] == "straggler_detected"]
        assert len(latched) == 1
        assert latched[0]["group"] == "g2"
        assert len(telemetry.EVENTS.recent("straggler_detected")) == 1
        assert (
            telemetry.STRAGGLER_DETECTED.labels(group="g2").value == 1
        )
        assert telemetry.STRAGGLERS.value == 1

    def test_consecutive_required(self):
        d = StragglerDetector(factor=1.5, k=3)
        d.update({"g0": 0.1, "g1": 0.5})
        d.update({"g0": 0.1, "g1": 0.5})
        d.update({"g0": 0.1, "g1": 0.1})   # breaks the streak
        d.update({"g0": 0.1, "g1": 0.5})
        d.update({"g0": 0.1, "g1": 0.5})
        assert d.stragglers() == []

    def test_unlatch_hysteresis(self):
        d = StragglerDetector(factor=1.5, k=2)
        for _ in range(2):
            d.update({"g0": 0.1, "g1": 0.5})
        assert d.stragglers() == ["g1"]
        # in the dead band (over 0.8*factor=1.2x, under 1.5x): stays latched
        for _ in range(4):
            d.update({"g0": 0.1, "g1": 0.13})
        assert d.stragglers() == ["g1"]
        # clearly back to fleet speed for K consecutive: unlatches
        evs = []
        for _ in range(2):
            evs += d.update({"g0": 0.1, "g1": 0.1})
        assert d.stragglers() == []
        assert [e["event"] for e in evs] == ["straggler_cleared"]
        assert telemetry.STRAGGLERS.value == 0

    def test_gap_breaks_the_consecutive_streak(self):
        """A group absent from a round (manager restart → p50 reports 0)
        must reset its over/under streaks: K means K CONSECUTIVE live
        observations, never K jittery samples separated by gaps."""
        d = StragglerDetector(factor=1.5, k=3)
        d.update({"g0": 0.1, "g1": 0.5})
        d.update({"g0": 0.1, "g1": 0.5})
        d.update({"g0": 0.1, "g1": 0.0})   # g1 absent (restarting)
        d.update({"g0": 0.1, "g1": 0.5})   # streak restarted, not 3rd hit
        assert d.stragglers() == []
        # an under-min-groups round breaks every streak the same way
        d.update({"g0": 0.1, "g1": 0.5})
        d.update({"g1": 0.5})              # fleet too small: no round
        d.update({"g0": 0.1, "g1": 0.5})
        assert d.stragglers() == []

    def test_merge_accepts_status_json_shape(self):
        # the lighthouse /status.json "latency" entries carry sum_s, the
        # ctypes snapshot sum_ns — merge_lathist must take either
        a = {"rpc.serve": {"counts": [1, 2], "count": 3, "sum_ns": 1500}}
        b = {"rpc.serve": {"counts": [2, 0], "count": 2, "sum_s": 2e-6,
                           "p50_s": 1e-6}}
        m = merge_lathist(a, b)
        assert m["rpc.serve"]["counts"] == [3, 2]
        assert m["rpc.serve"]["count"] == 5
        assert m["rpc.serve"]["sum_ns"] == 1500 + 2000

    def test_min_groups_guard(self):
        d = StragglerDetector(factor=1.5, k=1, min_groups=2)
        assert d.update({"only": 9.0}) == []
        assert d.stragglers() == []

    def test_leave_one_out_baseline(self):
        # with 2 groups each is compared against the OTHER: the fast
        # group must never latch just because the straggler drags a
        # plain fleet median up
        d = StragglerDetector(factor=1.5, k=2)
        for _ in range(4):
            d.update({"fast": 0.1, "slow": 0.9})
        assert d.stragglers() == ["slow"]
