"""Telemetry subsystem tests: registry semantics, Prometheus exposition,
FT event-trail round-trip, StepTimer outlier marking, the /metrics route
on the checkpoint HTTP server, and a 2-replica Manager integration run
asserting quorum/commit events fire.
"""

import json
import os
import re
import threading
import time
import urllib.request
from datetime import timedelta

import numpy as np
import pytest

from torchft_tpu import telemetry
from torchft_tpu.profiling import StepTimer
from torchft_tpu.telemetry import EventTrail, read_trail
from torchft_tpu.telemetry.registry import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
)


# ---------------------------------------------------------------------------
# registry semantics
# ---------------------------------------------------------------------------


class TestRegistry:
    def test_counter_basic(self):
        r = MetricsRegistry()
        c = r.counter("c_total", "help text")
        c.inc()
        c.inc(2.5)
        assert c.value == 3.5
        with pytest.raises(ValueError):
            c.inc(-1)

    def test_get_or_create_idempotent(self):
        r = MetricsRegistry()
        assert r.counter("x_total") is r.counter("x_total")
        with pytest.raises(ValueError):
            r.gauge("x_total")  # type clash must be loud

    def test_label_children(self):
        r = MetricsRegistry()
        c = r.counter("ops_total", "ops", labelnames=("op", "plane"))
        c.labels(op="allreduce", plane="tcp").inc(3)
        c.labels("allreduce", "cma").inc()
        # same labels -> same child
        assert c.labels(op="allreduce", plane="tcp").value == 3
        # a labeled family cannot be observed directly
        with pytest.raises(ValueError):
            c.inc()
        # wrong arity is loud
        with pytest.raises(ValueError):
            c.labels("only-one")
        text = "\n".join(c.render())
        assert 'ops_total{op="allreduce",plane="tcp"} 3' in text
        assert 'ops_total{op="allreduce",plane="cma"} 1' in text

    def test_gauge(self):
        r = MetricsRegistry()
        g = r.gauge("g")
        g.set(5)
        g.inc(2)
        g.dec()
        assert g.value == 6

    def test_histogram_buckets(self):
        r = MetricsRegistry()
        h = r.histogram("h_seconds", buckets=(0.1, 1.0, 10.0))
        for v in (0.05, 0.5, 0.5, 5.0, 50.0):
            h.observe(v)
        snap = h.snapshot()
        assert snap["count"] == 5
        assert snap["sum"] == pytest.approx(56.05)
        # cumulative semantics
        assert snap["buckets"]["0.1"] == 1
        assert snap["buckets"]["1"] == 3
        assert snap["buckets"]["10"] == 4
        # quantile interpolates within bounds, clamps past the last one
        assert 0.1 <= h.quantile(0.5) <= 1.0
        assert h.quantile(0.999) == 10.0
        assert r.histogram("empty_seconds").quantile(0.5) is None

    def test_histogram_time_context(self):
        r = MetricsRegistry()
        h = r.histogram("t_seconds")
        with h.time():
            time.sleep(0.01)
        assert h.count == 1
        assert h.sum >= 0.01

    def test_thread_safety_smoke(self):
        r = MetricsRegistry()
        c = r.counter("race_total", labelnames=("t",))
        h = r.histogram("race_seconds")
        n_threads, n_iter = 8, 2000

        def work(i):
            child = c.labels(t=str(i % 2))
            for _ in range(n_iter):
                child.inc()
                h.observe(0.001)

        threads = [
            threading.Thread(target=work, args=(i,)) for i in range(n_threads)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        total = sum(child.value for _v, child in c._snapshot_children())
        assert total == n_threads * n_iter
        assert h.count == n_threads * n_iter

    def test_render_is_valid_prometheus(self):
        r = MetricsRegistry()
        r.counter("a_total", 'has "quotes" and \\ slashes').inc()
        r.gauge("b", "gauge", labelnames=("x",)).labels(x='v"al').set(2)
        r.histogram("c_seconds", buckets=(1.0,)).observe(0.5)
        _assert_prometheus_text(r.render())

    def test_dump_roundtrips_through_json(self):
        r = MetricsRegistry()
        r.counter("a_total").inc()
        r.histogram("c_seconds", buckets=(1.0,)).observe(0.5)
        d = json.loads(json.dumps(r.dump()))
        assert d["a_total"]["samples"][0]["value"] == 1
        assert d["c_seconds"]["samples"][0]["count"] == 1

    def test_reset_values_keeps_references_live(self):
        r = MetricsRegistry()
        c = r.counter("r_total", labelnames=("k",))
        child = c.labels(k="a")
        child.inc(5)
        r.reset_values()
        assert child.value == 0
        child.inc()  # the held reference must still be the rendered child
        assert 'r_total{k="a"} 1' in "\n".join(c.render())


_SAMPLE_RE = re.compile(
    r'^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[a-zA-Z_][a-zA-Z0-9_]*="(\\.|[^"\\])*"'
    r'(,[a-zA-Z_][a-zA-Z0-9_]*="(\\.|[^"\\])*")*\})? '
    r"(-?\d+(\.\d+)?([eE][+-]?\d+)?|[+-]Inf|NaN)$"
)


def _assert_prometheus_text(text: str) -> None:
    """Minimal exposition-format validator: every line is a comment or a
    well-formed sample; every sample's family has a preceding # TYPE."""
    typed = set()
    for line in text.splitlines():
        if not line:
            continue
        if line.startswith("# TYPE "):
            typed.add(line.split()[2])
            continue
        if line.startswith("#"):
            continue
        assert _SAMPLE_RE.match(line), f"bad exposition line: {line!r}"
        family = re.split(r"[{ ]", line, 1)[0]
        base = re.sub(r"_(bucket|sum|count)$", "", family)
        assert family in typed or base in typed, f"untyped sample: {line!r}"


# ---------------------------------------------------------------------------
# event trail
# ---------------------------------------------------------------------------


class TestEventTrail:
    def test_ring_buffer_and_filter(self):
        trail = EventTrail()
        trail.emit("commit", step=1)
        trail.emit("abort", step=2)
        trail.emit("commit", step=3)
        assert [e["step"] for e in trail.recent("commit")] == [1, 3]
        assert len(trail.recent()) == 3
        assert all("ts" in e for e in trail.recent())

    def test_jsonl_roundtrip(self, tmp_path):
        path = str(tmp_path / "trail.jsonl")
        trail = EventTrail(path=path)
        trail.emit("quorum_ready", quorum_id=7, participants=["a", "b"])
        trail.emit("peer_death", ring_rank=1)
        trail.close()
        records = read_trail(path)
        assert [r["event"] for r in records] == ["quorum_ready", "peer_death"]
        assert records[0]["participants"] == ["a", "b"]
        assert records[0]["ts"] <= records[1]["ts"]

    def test_read_trail_skips_torn_tail(self, tmp_path):
        path = tmp_path / "trail.jsonl"
        path.write_text('{"ts": 1, "event": "commit"}\n{"ts": 2, "eve')
        assert [r["event"] for r in read_trail(str(path))] == ["commit"]

    def test_env_var_sink(self, tmp_path, monkeypatch):
        path = str(tmp_path / "env_trail.jsonl")
        monkeypatch.setenv(telemetry.ENV_TRAIL_PATH, path)
        trail = EventTrail()  # picks the env path up lazily on first emit
        trail.emit("eviction", victim="g1")
        trail.close()
        assert read_trail(path)[0]["victim"] == "g1"

    def test_emit_mirrors_into_metric(self):
        before = telemetry.FT_EVENTS_TOTAL.labels(event="test_kind").value
        telemetry.EVENTS.emit("test_kind")
        after = telemetry.FT_EVENTS_TOTAL.labels(event="test_kind").value
        assert after == before + 1


# ---------------------------------------------------------------------------
# StepTimer outlier marking
# ---------------------------------------------------------------------------


class TestStepTimer:
    def test_outliers_excluded_from_steady_rate(self):
        t = StepTimer(window=8, record_metrics=False)
        assert t.tick() is None
        for _ in range(3):
            time.sleep(0.002)
            t.tick()
        time.sleep(0.05)
        t.mark_quorum()
        d = t.tick()
        assert d >= 0.05
        assert t.outlier_steps == 1
        assert t.outliers()[0][2] == ("quorum",)
        # the slow quorum step must not drag the steady rate down
        assert t.steps_per_sec() > t.steps_per_sec_all()

    def test_tick_kwargs_and_pending_marks_combine(self):
        t = StepTimer(record_metrics=False)
        t.tick()
        t.mark_heal()
        t.tick(quorum=True)
        assert t.outliers()[0][2] == ("heal", "quorum")
        assert t.last_tags == ("heal", "quorum")
        t.tick()
        assert t.last_tags == ()  # marks don't leak into the next step

    def test_records_into_registry_by_kind(self):
        hist = telemetry.STEP_DURATION
        steady0 = hist.labels(kind="steady").count
        heal0 = hist.labels(kind="heal").count
        t = StepTimer()
        t.tick()
        t.tick()  # steady
        t.tick(heal=True, quorum=True)  # heal wins the kind
        assert hist.labels(kind="steady").count == steady0 + 1
        assert hist.labels(kind="heal").count == heal0 + 1


# ---------------------------------------------------------------------------
# /metrics on the checkpoint HTTP server
# ---------------------------------------------------------------------------


class TestMetricsEndpoint:
    def test_scrape_includes_catalog(self):
        from torchft_tpu.checkpointing.http_transport import HTTPTransport

        transport = HTTPTransport(timeout=timedelta(seconds=5))
        try:
            url = f"http://localhost:{transport._port}/metrics"
            with urllib.request.urlopen(url, timeout=5) as resp:
                assert resp.status == 200
                assert "text/plain" in resp.headers["Content-Type"]
                text = resp.read().decode()
        finally:
            transport.shutdown()
        _assert_prometheus_text(text)
        # acceptance names must be present even before any observation
        for name in (
            "tft_quorum_latency_seconds",
            "tft_allreduce_bytes_total",
            "tft_step_duration_seconds",
            "tft_commits_total",
            "tft_heal_duration_seconds",
        ):
            assert name in text, name

    def test_scrape_works_while_no_checkpoint_staged(self):
        # readers of /checkpoint/* block until staging; /metrics must not
        from torchft_tpu.checkpointing.http_transport import HTTPTransport

        transport = HTTPTransport(timeout=timedelta(seconds=5))
        try:
            t0 = time.perf_counter()
            with urllib.request.urlopen(
                f"http://localhost:{transport._port}/metrics", timeout=5
            ) as resp:
                resp.read()
            assert time.perf_counter() - t0 < 2.0
        finally:
            transport.shutdown()


# ---------------------------------------------------------------------------
# Manager integration: 2 replica groups, real quorum + commit votes
# ---------------------------------------------------------------------------


def _train_group(gid, lighthouse_addr, steps, barrier):
    from torchft_tpu.collectives import CollectivesTcp
    from torchft_tpu.manager import Manager
    from torchft_tpu.store import StoreServer

    store = StoreServer()
    manager = Manager(
        collectives=CollectivesTcp(timeout=timedelta(seconds=15)),
        load_state_dict=lambda s: None,
        state_dict=lambda: {"w": np.zeros(4, np.float32)},
        min_replica_size=2,
        replica_id=f"telemetry_g{gid}_",
        store_addr=store.address(),
        rank=0,
        world_size=1,
        lighthouse_addr=lighthouse_addr,
        timeout=timedelta(seconds=15),
        quorum_timeout=timedelta(seconds=30),
    )
    committed = 0
    try:
        barrier.wait(timeout=30)
        while committed < steps:
            manager.start_quorum()
            grad = np.full(8, float(gid + 1), np.float32)
            manager.allreduce(grad).wait()
            if manager.should_commit():
                committed += 1
        return {"gid": gid, "committed": committed, "grad": grad}
    finally:
        manager.shutdown(wait=False)
        store.shutdown()


def test_manager_2replica_quorum_commit_events():
    """2-replica CPU-mesh run: quorum + commit events must land in the
    trail and the catalog metrics must move."""
    from concurrent.futures import ThreadPoolExecutor

    from torchft_tpu.coordination import LighthouseServer

    telemetry.EVENTS.clear()
    # a quorum RPC carries 64 spans: what earlier tests of this worker left
    # pending would fill all three steps' batches ahead of this run's spans
    telemetry.TRACER.clear()
    quorums0 = telemetry.QUORUMS_TOTAL.value
    commits0 = telemetry.COMMITS_TOTAL.labels(outcome="committed").value
    lh = LighthouseServer(bind="[::]:0", min_replicas=2)
    steps = 3
    barrier = threading.Barrier(2)
    try:
        with ThreadPoolExecutor(max_workers=2) as pool:
            futs = [
                pool.submit(_train_group, g, lh.address(), steps, barrier)
                for g in range(2)
            ]
            results = [f.result(timeout=120) for f in futs]
        # while the lighthouse is still up: the cluster aggregation must
        # have received each replica's piggybacked telemetry (rides the
        # quorum traffic — no extra RPCs to trigger here)
        from torchft_tpu.telemetry.native import fetch_merged_trace, poll_cluster

        cluster = poll_cluster(lh.address())
        trace = fetch_merged_trace(lh.address())
    finally:
        lh.shutdown()

    assert cluster is not None
    groups = [
        rid for rid in cluster["replicas"] if rid.startswith("telemetry_g")
    ]
    assert len(groups) == 2, cluster
    for rid in groups:
        assert cluster["replicas"][rid]["step"] >= 0
        assert "quorums" in cluster["replicas"][rid]["summary"]

    # merged Chrome trace carries spans from BOTH replicas, and their
    # trace ids correlate on quorum epoch (trace_id = replica:step:epoch)
    assert trace is not None
    xs = [e for e in trace["traceEvents"] if e.get("ph") == "X"]
    epochs_by_replica = {}
    for e in xs:
        tid = e.get("args", {}).get("trace_id", "")
        rid, _, rest = tid.partition(":")
        _, _, epoch = rest.partition(":")
        if rid.startswith("telemetry_g"):
            epochs_by_replica.setdefault(rid, set()).add(epoch)
    assert len(epochs_by_replica) == 2, epochs_by_replica
    e1, e2 = epochs_by_replica.values()
    assert e1 & e2, f"no correlated quorum epoch: {epochs_by_replica}"

    assert all(r["committed"] == steps for r in results)
    # both groups averaged (1+2)/2 = 1.5 every step
    for r in results:
        np.testing.assert_allclose(r["grad"], 1.5)

    # events: every group emitted quorum_start/quorum_ready per step and a
    # commit per committed step (shared process ring holds both groups)
    kinds = [e["event"] for e in telemetry.EVENTS.recent()]
    assert kinds.count("quorum_ready") >= 2 * steps
    assert kinds.count("commit") == 2 * steps
    ready = telemetry.EVENTS.recent("quorum_ready")[-1]
    assert ready["num_participants"] == 2
    assert len(ready["participants"]) == 2

    # metrics: quorum RPC latency observed, commits counted
    assert telemetry.QUORUMS_TOTAL.value >= quorums0 + 2 * steps
    assert (
        telemetry.COMMITS_TOTAL.labels(outcome="committed").value
        == commits0 + 2 * steps
    )
    assert telemetry.QUORUM_LATENCY.count > 0
    assert telemetry.ALLREDUCE_BYTES.labels(plane="python-ring").value > 0 or any(
        child.value > 0
        for _v, child in telemetry.ALLREDUCE_BYTES._snapshot_children()
    )

    # summary digest is JSON-serializable and consistent
    s = json.loads(json.dumps(telemetry.summary()))
    assert s["commits"]["committed"] >= 2 * steps


# ---------------------------------------------------------------------------
# kill one replica: peer_death -> heal_end readable from the trail
# ---------------------------------------------------------------------------


def _death_then_heal_recorded(r):
    """True iff the trail shows the INDUCED failure: the victim's death
    detected (peer_death naming it, from the kill onward — startup-churn
    false positives about other replicas don't count) and the respawned
    victim's heal_end after it."""
    victim_prefix = f"group{len(r.trail_paths) - 1}_"
    survivor_events = []
    for path in r.trail_paths[:-1]:
        survivor_events.extend(read_trail(path))
    victim_events = read_trail(r.trail_paths[-1])

    deaths = [
        e
        for e in survivor_events
        if e["event"] == "peer_death"
        and str(e.get("replica", "")).startswith(victim_prefix)
        and e["ts"] >= r.t_kill_unix - 0.5
    ]
    heals = [
        e
        for e in victim_events
        if e["event"] == "heal_end" and e["ts"] >= r.t_respawn_unix
    ]
    return bool(
        deaths
        and heals
        and any(h["ts"] > min(d["ts"] for d in deaths) for h in heals)
        and any(h.get("bytes", 0) > 0 for h in heals)
    )


@pytest.mark.soak
def test_kill_one_replica_trail_records_death_then_heal():
    """Acceptance: a 2-replica run that SIGKILLs one replica produces an
    event trail containing peer_death followed by heal_end, and the
    recovery cost is readable from the recorded step-duration outliers.

    One retry, same as test_recovery: on a contended box the kill can
    land between plane epochs where no socket FIN reaches the survivor,
    so the death watch (legitimately) has nothing to report.

    total_steps leaves the survivor ~3s of post-kill runway: with the
    25-step default it can FINISH and exit ~1.2s after the kill — about
    one python+jax startup — so the respawned victim sometimes finds an
    empty lighthouse, forms a singleton quorum and replays from step 0
    with no one to heal from (no heal_end in the trail, by design)."""
    import warnings

    from torchft_tpu.faultinject.recovery import measure_recovery

    for attempt in range(2):
        r = measure_recovery(
            total_steps=60,
            kill_at_step=6,
            step_sleep=0.05,
            op_timeout=1.0,
            heartbeat_timeout_ms=1000,
            timeout_s=120.0,
            num_groups=2,
        )
        if _death_then_heal_recorded(r):
            break
        warnings.warn(
            f"attempt {attempt}: trail lacks victim peer_death -> heal_end "
            f"({r.ft_events}); retrying once",
            stacklevel=1,
        )
    assert r.ft_events, "workers produced no event trail"
    assert r.ft_events.get("commit", 0) > 0, r.ft_events
    assert _death_then_heal_recorded(r), r.ft_events

    # recovery cost is readable from recorded outliers: the survivor's
    # step_outlier records (death-watch re-quorum) carry the blackout
    # duration, and the rejoiner's first measured step is tagged heal
    merged = []
    for path in r.trail_paths:
        merged.extend(read_trail(path))
    outliers = [e for e in merged if e["event"] == "step_outlier"]
    assert any("quorum" in e.get("tags", ()) for e in outliers), outliers
    victim_outliers = [
        e
        for e in read_trail(r.trail_paths[-1])
        if e["event"] == "step_outlier" and e["ts"] >= r.t_respawn_unix
    ]
    assert any("heal" in e.get("tags", ()) for e in victim_outliers), (
        victim_outliers
    )

    # acceptance (PR 2): the kill/respawn run produced a merged Chrome
    # trace at the lighthouse /trace endpoint with spans from BOTH
    # replicas carrying correlated quorum epochs
    assert r.merged_trace_path and os.path.exists(r.merged_trace_path)
    with open(r.merged_trace_path) as f:
        trace = json.load(f)
    xs = [e for e in trace["traceEvents"] if e.get("ph") == "X"]
    epochs_by_replica = {}
    for e in xs:
        tid = e.get("args", {}).get("trace_id", "")
        rid, _, rest = tid.partition(":")
        _, _, epoch = rest.partition(":")
        if rid:
            epochs_by_replica.setdefault(rid, set()).add(epoch)
    assert len(epochs_by_replica) >= 2, epochs_by_replica
    # some PAIR of replicas shares a quorum epoch (the pre-kill victim,
    # the survivor and the respawned victim are three distinct ids — the
    # dead id and its replacement never coexist in one epoch)
    ids = list(epochs_by_replica)
    assert any(
        epochs_by_replica[a] & epochs_by_replica[b]
        for i, a in enumerate(ids)
        for b in ids[i + 1 :]
    ), epochs_by_replica
    # ... and the per-replica health snapshot reflects both groups
    assert r.cluster and len(r.cluster["replicas"]) >= 2
