"""PR 2 observability tests: distributed spans (nesting, carrier
propagation, Chrome export schema), the collective flight recorder (ring
wraparound, SIGUSR2 dump validity, deadline trigger), the step watchdog,
the lighthouse cluster aggregation endpoints (/cluster.json, /trace),
checkpoint-transport trace propagation, and the parameter server's
/metrics route. The docs<->code catalog drift checks that used to live
here moved into ``python -m torchft_tpu.analysis`` (docdrift rules);
``tests/test_analysis.py`` keeps them in tier-1 through the one gate.
"""

import json
import os
import signal
import threading
import time
import urllib.request
from datetime import timedelta

import numpy as np
import pytest

from torchft_tpu import telemetry
from torchft_tpu.telemetry import read_trail
from torchft_tpu.telemetry.events import EventTrail
from torchft_tpu.telemetry.flight import FlightRecorder, StepWatchdog
from torchft_tpu.telemetry.tracing import Tracer, read_spans


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------


class TestSpans:
    def test_nesting_and_trace_identity(self):
        t = Tracer()
        t.set_context(replica_id="gA", step=7, quorum_epoch=3)
        with t.span("outer", rank=0) as outer:
            with t.span("inner") as inner:
                pass
        spans = t.recent()
        assert [s["name"] for s in spans] == ["inner", "outer"]
        by_name = {s["name"]: s for s in spans}
        assert by_name["outer"]["trace_id"] == "gA:7:3"
        assert by_name["inner"]["trace_id"] == "gA:7:3"
        assert by_name["inner"]["parent_id"] == outer.span_id
        assert "parent_id" not in by_name["outer"]
        assert by_name["outer"]["attrs"]["rank"] == 0
        assert inner.dur_s <= outer.dur_s

    def test_carrier_propagation_across_tracers(self):
        # two Tracer instances stand in for two replicas
        a, b = Tracer(), Tracer()
        a.set_context(replica_id="gA", step=1, quorum_epoch=1)
        with a.span("heal_recv") as client_span:
            carrier = a.inject()
            wire = Tracer.format_carrier(carrier)
        parsed = Tracer.parse_carrier(wire)
        with b.span("checkpoint_serve", parent=parsed):
            pass
        serve = b.recent("checkpoint_serve")[-1]
        assert serve["parent_id"] == client_span.span_id
        assert serve["trace_id"] == "gA:1:1"  # adopted from the carrier

    def test_explicit_trace_id_and_error_attr(self):
        t = Tracer()
        with pytest.raises(ValueError):
            with t.span("boom", trace_id="g:1:2"):
                raise ValueError("nope")
        s = t.recent("boom")[-1]
        assert s["trace_id"] == "g:1:2"
        assert "nope" in s["attrs"]["error"]

    def test_jsonl_sink_roundtrip(self, tmp_path):
        t = Tracer()
        t.configure(str(tmp_path / "spans.jsonl"))
        with t.span("op_a"):
            pass
        t.close()
        spans = read_spans(str(tmp_path / "spans.jsonl"))
        assert [s["name"] for s in spans] == ["op_a"]
        assert spans[0]["dur_s"] >= 0

    def test_chrome_export_schema(self, tmp_path):
        t = Tracer()
        t.set_context(replica_id="gB", step=2, quorum_epoch=5)
        with t.span("quorum"):
            pass
        events = t.chrome_events()
        # metadata event naming the replica lane + the span itself
        assert any(e.get("ph") == "M" for e in events)
        xs = [e for e in events if e.get("ph") == "X"]
        assert xs, events
        for e in xs:
            for key in ("name", "ph", "ts", "dur", "pid", "tid", "args"):
                assert key in e, (key, e)
            assert isinstance(e["pid"], int) and isinstance(e["tid"], int)
        # whole document round-trips through JSON (Perfetto-loadable shape)
        doc = json.loads(
            json.dumps({"displayTimeUnit": "ms", "traceEvents": events})
        )
        assert doc["traceEvents"]

    def test_drain_chrome_fragment_is_joinable(self):
        t = Tracer()
        t.set_context(replica_id="gC", step=0, quorum_epoch=0)
        for _ in range(3):
            with t.span("s"):
                pass
        frag = t.drain_chrome_fragment(max_events=8)
        events = json.loads(f"[{frag}]")
        assert len(events) >= 3
        # drained: a second call returns only new spans
        assert t.drain_chrome_fragment() == ""

    def test_drain_byte_cap_keeps_tail_pending(self):
        # spans past the byte budget must stay queued for the next batch,
        # not be silently dropped (incident windows are span-heavy)
        t = Tracer()
        t.set_context(replica_id="gD", step=0, quorum_epoch=0)
        for i in range(6):
            with t.span(f"op{i}", pad="x" * 200):
                pass
        first = t.drain_chrome_fragment(max_events=64, max_bytes=900)
        second = t.drain_chrome_fragment(max_events=64, max_bytes=1 << 20)
        names = [
            e["name"]
            for e in json.loads(f"[{first},{second}]")
            if e.get("ph") == "X"
        ]
        assert names == [f"op{i}" for i in range(6)], names

    def test_requeue_last_batch_restores_spans(self):
        # a failed piggyback RPC requeues its drained batch (manager's
        # quorum-error path), so the outage keeps its spans
        t = Tracer()
        t.set_context(replica_id="gE", step=1, quorum_epoch=1)
        with t.span("will_fail_to_ship"):
            pass
        frag = t.drain_chrome_fragment()
        assert "will_fail_to_ship" in frag
        t.requeue_last_batch()
        again = t.drain_chrome_fragment()
        assert "will_fail_to_ship" in again
        t.requeue_last_batch()  # idempotence: batch was consumed above...
        t.requeue_last_batch()  # ...and double-requeue must not raise


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------


class TestFlightRecorder:
    def test_ring_wraparound_and_analyze(self):
        fr = FlightRecorder(size=8)
        seqs = [fr.record_issue("allreduce", "tcp", 100, rank=0) for _ in range(20)]
        snap = fr.snapshot()
        assert len(snap) == 8
        assert [r["seq"] for r in snap] == list(range(13, 21))
        # completing an overwritten record is a safe no-op
        fr.record_complete(seqs[0])
        # complete all but the oldest surviving two
        for s in range(15, 21):
            fr.record_complete(s)
        fr.record_complete(14, error=RuntimeError("peer gone"))
        digest = fr.analyze(fr.snapshot())
        assert digest["last_completed"]["seq"] == 20
        assert digest["first_stuck"]["seq"] == 13  # still "issued"
        failed = [r for r in fr.snapshot() if r["status"] == "failed"]
        assert [r["seq"] for r in failed] == [14]

    def test_dump_file_validity_and_rate_limit(self, tmp_path, monkeypatch):
        monkeypatch.setenv("TORCHFT_FLIGHT_DIR", str(tmp_path))
        fr = FlightRecorder(size=4)
        s = fr.record_issue("broadcast", "device", 64, rank=1)
        fr.record_complete(s)
        fr.record_issue("allreduce", "device", 128, rank=1)
        path = fr.dump("manual")
        assert path and os.path.exists(path)
        doc = json.loads(open(path).read())
        assert doc["reason"] == "manual"
        assert doc["last_completed"]["op"] == "broadcast"
        assert doc["first_stuck"]["op"] == "allreduce"
        assert len(doc["entries"]) == 2
        # rate-limited second dump; force overrides
        assert fr.dump("manual") is None
        assert fr.dump("manual", force=True) is not None

    def test_sigusr2_dump(self, tmp_path, monkeypatch):
        monkeypatch.setenv("TORCHFT_FLIGHT_DIR", str(tmp_path))
        assert telemetry.install_sigusr2()
        sq = telemetry.FLIGHT.record_issue("allgather", "tcp", 32, rank=0)
        telemetry.FLIGHT.record_complete(sq)
        os.kill(os.getpid(), signal.SIGUSR2)
        deadline = time.monotonic() + 10
        dumps = []
        while time.monotonic() < deadline and not dumps:
            dumps = [
                f for f in os.listdir(tmp_path) if f.startswith("tft_flight_")
            ]
            time.sleep(0.05)
        assert dumps, "SIGUSR2 produced no flight dump"
        doc = json.loads(open(tmp_path / dumps[0]).read())
        assert doc["reason"] == "signal"
        assert any(e["op"] == "allgather" for e in doc["entries"])

    def test_collectives_record_into_ring(self):
        from torchft_tpu.collectives import CollectivesDummy  # noqa: F401

        # the TCP backend records issue+completion through _count_op /
        # _track_flight; exercise via a world-1 CollectivesTcp (no sockets)
        from torchft_tpu.collectives import CollectivesTcp

        telemetry.FLIGHT.clear()
        c = CollectivesTcp(timeout=timedelta(seconds=5))
        c.configure("unused", 0, 1)
        try:
            c.allreduce([np.ones(4, np.float32)]).wait(timedelta(seconds=5))
            c.barrier().wait(timedelta(seconds=5))
        finally:
            c.shutdown()
        snap = telemetry.FLIGHT.snapshot()
        ops = [r["op"] for r in snap]
        assert "allreduce" in ops and "barrier" in ops
        assert all(r["status"] == "completed" for r in snap), snap


class TestDeadlineDump:
    def test_hung_collective_dump_identifies_stuck_op(
        self, tmp_path, monkeypatch
    ):
        """Forced collective hang: one group issues a barrier its peer
        never joins. The futures deadline manager fails the op AND writes
        a flight dump whose first_stuck names the wedged barrier."""
        from torchft_tpu.collectives_device import CollectivesDevice

        monkeypatch.setenv("TORCHFT_FLIGHT_DIR", str(tmp_path))
        monkeypatch.setattr(telemetry.FLIGHT, "min_dump_interval_s", 0.0)
        telemetry.FLIGHT.clear()
        key = "store/torchft/7701/0"
        a = CollectivesDevice(timeout=timedelta(seconds=1))
        b = CollectivesDevice(timeout=timedelta(seconds=1))
        th = threading.Thread(target=lambda: b.configure(key, 1, 2))
        th.start()
        a.configure(key, 0, 2)
        th.join()
        try:
            work = a.barrier()  # b never issues: the op can never complete
            with pytest.raises(TimeoutError):
                work.wait(timedelta(seconds=10))
        finally:
            a.shutdown()
            b.shutdown()
        deadline = time.monotonic() + 10
        dumps = []
        while time.monotonic() < deadline and not dumps:
            dumps = [
                f for f in os.listdir(tmp_path) if f.startswith("tft_flight_")
            ]
            time.sleep(0.05)
        assert dumps, "deadline expiry produced no flight dump"
        docs = [json.loads(open(tmp_path / f).read()) for f in dumps]
        assert any(
            d["reason"] == "deadline"
            and d["first_stuck"]
            and d["first_stuck"]["op"] == "barrier"
            for d in docs
        ), docs


class TestStepWatchdog:
    def test_fires_dumps_and_latches(self, tmp_path, monkeypatch):
        monkeypatch.setenv("TORCHFT_FLIGHT_DIR", str(tmp_path))
        fired = []
        fr = FlightRecorder(size=4)
        wd = StepWatchdog(
            mult=0.0001,
            min_s=0.15,
            on_stall=lambda step, el, thr: fired.append((step, el, thr)),
            recorder=fr,
        )
        try:
            ev0 = len(telemetry.EVENTS.recent("watchdog_stall"))
            wd.arm(step=42)
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline and not fired:
                time.sleep(0.02)
            assert fired and fired[0][0] == 42
            assert wd.stalled and wd.stalls == 1
            assert fired[0][2] >= 0.15  # threshold floor respected
            assert len(telemetry.EVENTS.recent("watchdog_stall")) == ev0 + 1
            assert any(
                f.startswith("tft_flight_") for f in os.listdir(tmp_path)
            )
            # fires once per armed step
            time.sleep(0.3)
            assert wd.stalls == 1
            wd.disarm()
            assert not wd.stalled
        finally:
            wd.stop()

    def test_disabled_by_mult_zero(self):
        wd = StepWatchdog(mult=0, min_s=0.01)
        wd.arm(step=1)  # no thread started
        assert wd._thread is None
        wd.stop()


# ---------------------------------------------------------------------------
# lighthouse cluster aggregation
# ---------------------------------------------------------------------------


class TestClusterAggregation:
    def test_cluster_json_and_merged_trace(self, tmp_path):
        from torchft_tpu.coordination import LighthouseClient, LighthouseServer
        from torchft_tpu.telemetry.native import fetch_merged_trace, poll_cluster

        t = Tracer()
        t.set_context(replica_id="repA", step=7, quorum_epoch=2)
        with t.span("quorum"):
            pass
        frag = t.drain_chrome_fragment()
        payload = {
            "summary": json.dumps({"quorums": 3, "heals_recv": 1}),
            "step": 7,
            "stuck": True,
            "last_heal_ts": 123.5,
            "spans": frag,
        }
        lh = LighthouseServer(bind="[::]:0", min_replicas=1)
        try:
            cli = LighthouseClient(
                lh.address(), connect_timeout=timedelta(seconds=5)
            )
            cli.heartbeat("repA", telemetry_payload=payload)
            cli.heartbeat("repB", telemetry_payload={"step": 5, "stuck": False})
            cli.close()

            cluster = poll_cluster(lh.address())
            assert cluster is not None
            reps = cluster["replicas"]
            assert reps["repA"]["step"] == 7
            assert reps["repA"]["stuck"] is True
            assert reps["repA"]["last_heal_ts"] == 123.5
            assert reps["repA"]["summary"]["quorums"] == 3
            assert reps["repB"]["step"] == 5
            assert reps["repA"]["last_seen_ms_ago"] >= 0

            out = str(tmp_path / "trace.json")
            trace = fetch_merged_trace(lh.address(), path=out)
            assert trace is not None and os.path.exists(out)
            xs = [e for e in trace["traceEvents"] if e.get("ph") == "X"]
            assert xs, trace
            for e in xs:
                for key in ("name", "ph", "ts", "pid", "tid"):
                    assert key in e
            assert any(
                e.get("args", {}).get("trace_id") == "repA:7:2" for e in xs
            )
            # the dashboard grew the health table + stuck highlight
            with urllib.request.urlopen(
                f"{lh.address()}/status", timeout=5
            ) as resp:
                html = resp.read().decode()
            assert "Replica health" in html
            assert "STUCK" in html
        finally:
            lh.shutdown()


# ---------------------------------------------------------------------------
# checkpoint transport trace propagation (cross-replica parent/child)
# ---------------------------------------------------------------------------


class TestCheckpointTracePropagation:
    def test_serve_span_is_child_of_recv_span(self):
        from torchft_tpu.checkpointing.http_transport import HTTPTransport

        src = HTTPTransport(timeout=timedelta(seconds=5))
        dst = HTTPTransport(timeout=timedelta(seconds=5))
        try:
            state = {"w": np.arange(8, dtype=np.float32)}
            src.send_checkpoint(
                dst_ranks=[1], step=3, state_dict=state,
                timeout=timedelta(seconds=5),
            )
            telemetry.TRACER.set_context(
                replica_id="healer", step=3, quorum_epoch=9
            )
            with telemetry.TRACER.span("heal_recv") as parent:
                got = dst.recv_checkpoint(
                    src_rank=0,
                    metadata=src.metadata(),
                    step=3,
                    timeout=timedelta(seconds=5),
                )
            np.testing.assert_array_equal(got["w"], state["w"])
            # the serve span is recorded on the HTTP server thread,
            # which finishes AFTER the client's recv returns — poll
            # briefly, and filter to THIS heal's trace so a straggler
            # serve span from a previous in-process test can't be
            # mistaken for ours
            deadline = time.time() + 5
            serves = []
            while not serves and time.time() < deadline:
                serves = [
                    s
                    for s in telemetry.TRACER.recent("checkpoint_serve")
                    if s["trace_id"] == "healer:3:9"
                ]
                if not serves:
                    time.sleep(0.01)
            assert serves, "serving side recorded no span"
            serve = serves[-1]
            assert serve["parent_id"] == parent.span_id
            assert serve["trace_id"] == "healer:3:9"
            assert serve["attrs"]["bytes"] > 0
        finally:
            src.shutdown()
            dst.shutdown()


# ---------------------------------------------------------------------------
# parameter server /metrics
# ---------------------------------------------------------------------------


class TestParameterServerMetrics:
    def test_scrape(self):
        from torchft_tpu.collectives import CollectivesDummy
        from torchft_tpu.parameter_server import ParameterServer

        class PS(ParameterServer):
            @classmethod
            def new_collectives(cls):
                return CollectivesDummy()

            def forward(self, session_id, collectives):
                pass

        ps = PS(port=0)
        try:
            port = ps._server.socket.getsockname()[1]
            with urllib.request.urlopen(
                f"http://localhost:{port}/metrics", timeout=5
            ) as resp:
                assert resp.status == 200
                assert "text/plain" in resp.headers["Content-Type"]
                text = resp.read().decode()
            assert "tft_quorum_latency_seconds" in text
            assert "tft_flight_dumps_total" in text
        finally:
            ps.shutdown()


# ---------------------------------------------------------------------------
# event-trail rotation
# ---------------------------------------------------------------------------


class TestTrailRotation:
    def test_rolls_to_dot1_past_cap(self, tmp_path):
        path = str(tmp_path / "trail.jsonl")
        trail = EventTrail(path=path, max_bytes=512)
        for i in range(64):
            trail.emit("commit", step=i, pad="x" * 32)
        trail.close()
        rolled = path + ".1"
        assert os.path.exists(rolled), "no rotation happened"
        assert os.path.getsize(path) <= 1024
        # both generations parse; records are contiguous across the roll
        steps = [r["step"] for r in read_trail(rolled)] + [
            r["step"] for r in read_trail(path)
        ]
        assert steps[-1] == 63
        assert steps == sorted(steps)

    def test_env_knob_and_disable(self, tmp_path, monkeypatch):
        monkeypatch.setenv("TORCHFT_EVENT_TRAIL_MAX_BYTES", "0")
        trail = EventTrail(path=str(tmp_path / "t.jsonl"))
        assert trail.max_bytes == 0
        for i in range(16):
            trail.emit("commit", step=i)
        trail.close()
        assert not os.path.exists(str(tmp_path / "t.jsonl.1"))



# ---------------------------------------------------------------------------
# the ring's account in the profiler's trace
# ---------------------------------------------------------------------------


class TestRingAccountEvent:
    @pytest.mark.parametrize("cma", ["1", "0"])
    def test_one_zero_length_account_follows_each_ring_on_the_op_thread(
        self, tmp_path, monkeypatch, cma
    ):
        """Two ranks, three allreduces each, under a ``jax.profiler`` session:
        every ``tft.exchange.ring`` has one ``tft.exchange.ring.account``
        after it on its own thread's line, a microsecond long at most, with
        the bytes the plane moved for it."""
        import glob
        from concurrent.futures import ThreadPoolExecutor

        import jax

        from torchft_tpu.collectives import RING_ACCOUNT, CollectivesTcp, ReduceOp
        from torchft_tpu.store import StoreServer

        monkeypatch.setenv("TORCHFT_DP_CMA", cma)
        store = StoreServer()
        colls = [
            CollectivesTcp(hostname="localhost", timeout=timedelta(seconds=10))
            for _ in range(2)
        ]
        n = 4 * 16 * 2 * 100

        def run(rank):
            colls[rank].configure(f"{store.address()}/rae{cma}", rank, 2)
            try:
                for _ in range(3):
                    colls[rank].allreduce(
                        [np.ones(n, np.float32)], ReduceOp.SUM, 2
                    ).wait(timedelta(seconds=20))
            finally:
                colls[rank].shutdown()

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            with ThreadPoolExecutor(max_workers=2) as ex:
                list(ex.map(run, range(2)))
        finally:
            jax.profiler.stop_trace()
            store.shutdown()

        (path,) = glob.glob(
            os.path.join(str(tmp_path), "plugins", "profile", "*", "*.xplane.pb")
        )
        op_lines = []
        for plane in jax.profiler.ProfileData.from_file(path).planes:
            if not plane.name.startswith("/host:CPU"):
                continue
            for line in plane.lines:
                evs = sorted(
                    (ev.start_ns, ev.start_ns + ev.duration_ns, ev.name, dict(ev.stats))
                    for ev in line.events
                    if ev.name in ("tft.exchange.ring", "tft.exchange.ring.account")
                )
                if evs:
                    op_lines.append(evs)
        assert len(op_lines) == 2  # one op thread a rank, nothing elsewhere
        for evs in op_lines:
            assert [name for _, _, name, _ in evs] == [
                "tft.exchange.ring", "tft.exchange.ring.account"
            ] * 3
            for (_, ring_end, _, ring), (s, e, _, acct) in zip(evs[::2], evs[1::2]):
                assert ring_end <= s and e - s < 1e6
                assert set(acct) == set(RING_ACCOUNT) | {"bytes", "plane"}
                assert acct["bytes"] == ring["bytes"] == 4 * n
                moved = "pull_bytes" if cma == "1" else "pump_bytes"
                assert acct[moved] == 4 * n and acct["reduce_bytes"] == 2 * n
                assert acct["plane"] == ("cma" if cma == "1" else "tcp-striped")
                busy = sum(acct[k] for k in RING_ACCOUNT if k.endswith("_s")) - acct["slowest_stripe_s"]
                assert 0 < busy <= acct["slowest_stripe_s"] + 1e-9
