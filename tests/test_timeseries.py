"""The lighthouse's time-series store and what rides the same piggyback.

Covers the native tsdb (piggyback ingest → /timeseries.json range
queries, same-step overwrite, kill/respawn ring persistence, fan-out-cap
loud degrade, C-ABI snapshot), the 64 KiB anatomy-digest cap (dropped
loudly, never truncated), `merge_lathist` overflow-bucket exactness, the
series builder, and the faultinject `after` onset rule.
"""

from __future__ import annotations

import json
import urllib.request
from datetime import timedelta
from types import SimpleNamespace

import pytest

from torchft_tpu import _native, telemetry
from torchft_tpu.telemetry.anatomy import (
    LOG2_BUCKETS,
    lathist_quantile,
    merge_lathist,
)


@pytest.fixture
def lighthouse():
    from torchft_tpu.coordination import LighthouseClient, LighthouseServer

    _native.tsdb_reset()
    lh = LighthouseServer(bind="[::]:0", min_replicas=1)
    client = LighthouseClient(lh.address(), connect_timeout=timedelta(seconds=5))
    try:
        yield lh, client
    finally:
        client.close()
        lh.shutdown()
        _native.tsdb_reset()


def _feed(client, rid, step, series, epoch=1, **extra):
    client.heartbeat(
        rid,
        telemetry_payload={
            "step": step, "epoch": epoch, "series": series, **extra,
        },
    )


def _get_json(url):
    with urllib.request.urlopen(url, timeout=5) as resp:
        return json.loads(resp.read().decode())


# ---------------------------------------------------------------------------
# native tsdb store + /timeseries.json
# ---------------------------------------------------------------------------


class TestNativeTsdb:
    def test_ingest_snapshot_and_range_query(self, lighthouse):
        lh, client = lighthouse
        for step in range(6):
            _feed(client, "repA", step, {"local_s": 0.1 + step * 0.01})
        snap = _native.tsdb_snapshot()
        samples = snap["repA"]["local_s"]["samples"]
        assert [s[1] for s in samples] == list(range(6))  # step order
        assert samples[0][0] == 1  # epoch travels
        assert abs(samples[3][2] - 0.13) < 1e-9

        ts = _get_json(lh.address() + "/timeseries.json")
        body = ts["replicas"]["repA"]["local_s"]
        assert body["count"] == 6 and body["stride"] == 1
        assert ts["cursor"]["max_step"] == 5
        assert ts["retain"] >= 1

    def test_since_cursor_and_downsampling(self, lighthouse):
        lh, client = lighthouse
        for step in range(10):
            _feed(client, "repA", step, {"local_s": float(step)})
        ts = _get_json(lh.address() + "/timeseries.json?since=3")
        steps = [s[1] for s in ts["replicas"]["repA"]["local_s"]["samples"]]
        assert steps == [4, 5, 6, 7, 8, 9]  # exclusive cursor
        ts = _get_json(
            lh.address() + "/timeseries.json?since=3&max_points=3"
        )
        body = ts["replicas"]["repA"]["local_s"]
        steps = [s[1] for s in body["samples"]]
        assert body["stride"] == 2
        assert steps[-1] == 9, "newest sample must survive downsampling"
        assert len(steps) <= 4
        # an empty window must ECHO the cursor, never regress it — an
        # idle fleet would otherwise reset incremental consumers into
        # refetching the whole retention window
        ts = _get_json(lh.address() + "/timeseries.json?since=9")
        assert ts["cursor"]["max_step"] == 9

    def test_replica_and_series_filters(self, lighthouse):
        lh, client = lighthouse
        _feed(client, "groupA", 1, {"local_s": 0.1, "wall_s": 0.2})
        _feed(client, "groupB", 1, {"local_s": 0.3})
        ts = _get_json(lh.address() + "/timeseries.json?replica=groupB")
        assert list(ts["replicas"]) == ["groupB"]
        ts = _get_json(lh.address() + "/timeseries.json?series=wall")
        assert list(ts["replicas"]["groupA"]) == ["wall_s"]

    def test_same_step_report_overwrites_not_appends(self, lighthouse):
        # reports ride every quorum RPC; a re-quorum within one step must
        # refresh the sample, not burn retention
        lh, client = lighthouse
        _feed(client, "repA", 3, {"local_s": 0.1})
        _feed(client, "repA", 3, {"local_s": 0.5})
        samples = _native.tsdb_snapshot()["repA"]["local_s"]["samples"]
        assert len(samples) == 1
        assert abs(samples[0][2] - 0.5) < 1e-9

    def test_kill_respawn_full_history_served(self, lighthouse):
        # a dead incarnation's ring is RETAINED; the respawn (fresh uuid)
        # gets its own — /timeseries.json serves both (the acceptance's
        # persistence property, at the protocol level)
        lh, client = lighthouse
        for step in range(5):
            _feed(client, "g1-uuid-dead", step, {"local_s": 0.1})
        # "kill": the old incarnation simply stops reporting
        for step in range(3, 9):
            _feed(client, "g1-uuid-respawn", step, {"local_s": 0.2})
        ts = _get_json(lh.address() + "/timeseries.json?replica=g1-uuid")
        rings = ts["replicas"]
        assert set(rings) == {"g1-uuid-dead", "g1-uuid-respawn"}
        assert len(rings["g1-uuid-dead"]["local_s"]["samples"]) == 5
        assert rings["g1-uuid-respawn"]["local_s"]["samples"][-1][1] == 8

    def test_series_fanout_cap_degrades_loudly(self, lighthouse):
        # past TORCHFT_TSDB_MAX_SERIES (default 64) per replica, new
        # series are refused AND counted — never silently absorbed
        lh, client = lighthouse
        series = {f"s{i:03d}": float(i) for i in range(80)}
        _feed(client, "chatty", 1, series)
        ts = _get_json(lh.address() + "/timeseries.json?replica=chatty")
        assert len(ts["replicas"]["chatty"]) <= 64
        assert ts["dropped_series"] > 0
        metrics = urllib.request.urlopen(
            lh.address() + "/metrics", timeout=5
        ).read().decode()
        assert "torchft_tsdb_dropped_series_total" in metrics

    def test_non_numeric_and_stepless_reports_ignored(self, lighthouse):
        lh, client = lighthouse
        _feed(client, "repA", -1, {"local_s": 0.1})  # no step coordinate
        client.heartbeat(
            "repA",
            telemetry_payload={
                "step": 2, "epoch": 1,
                "series": {"ok": 1.0, "bad": "not-a-number"},
            },
        )
        snap = _native.tsdb_snapshot()
        assert "bad" not in snap.get("repA", {})
        assert len(snap["repA"]["ok"]["samples"]) == 1


# ---------------------------------------------------------------------------
# 64 KiB anatomy piggyback cap (satellite): loud degrade, never truncate
# ---------------------------------------------------------------------------


class TestAnatomyOversizeCap:
    def test_lighthouse_drops_and_counts_oversized_digest(self, lighthouse):
        lh, client = lighthouse
        good = json.dumps({"steps": 1})
        client.heartbeat(
            "repA", telemetry_payload={"step": 1, "anatomy": good}
        )
        oversized = "{" + "x" * (1 << 16) + "}"
        client.heartbeat(
            "repA", telemetry_payload={"step": 2, "anatomy": oversized}
        )
        cluster = _get_json(lh.address() + "/cluster.json")
        rec = cluster["replicas"]["repA"]
        # dropped, not truncated — and the previously-stored digest is
        # cleared too (a stale splice would misattribute the incident)
        assert rec["anatomy"] == {}
        assert rec["anatomy_oversized"] == 1
        metrics = urllib.request.urlopen(
            lh.address() + "/metrics", timeout=5
        ).read().decode()
        assert "torchft_telemetry_oversized_total 1" in metrics

    def test_cluster_json_stays_parseable_after_drop(self, lighthouse):
        # the whole point of dropping instead of truncating: the page
        # must still parse
        lh, client = lighthouse
        client.heartbeat(
            "repA",
            telemetry_payload={
                "step": 1, "anatomy": "{" + "y" * (1 << 16) + "}",
            },
        )
        cluster = _get_json(lh.address() + "/cluster.json")  # parses
        assert "repA" in cluster["replicas"]

    def test_manager_side_guard_replaces_oversized_digest(self, monkeypatch):
        # the replica end of the same cap: _telemetry_payload must send
        # an {"_oversized_bytes": n} marker, not the oversize itself.
        # Legacy full-JSON path only — the delta encoder (ISSUE 16)
        # degrades field-by-field instead (tests/test_fleet_telemetry.py)
        monkeypatch.setenv("TORCHFT_TELEMETRY_DELTA", "0")
        from torchft_tpu.manager import Manager

        big = {"rows": ["z" * 1024] * 100}
        monkeypatch.setattr(telemetry.LEDGER, "summary", lambda: big)
        fake = SimpleNamespace(
            _slo=SimpleNamespace(breached=lambda: False),
            _watchdog=SimpleNamespace(stalled=False),
            _step=3,
            _quorum_id=2,
            _last_heal_ts=0.0,
            _divergence_latched=False,
            _logger=SimpleNamespace(warning=lambda *a, **k: None),
        )
        fake._telemetry_payload_json = Manager._telemetry_payload_json.__get__(
            fake
        )
        payload = Manager._telemetry_payload(fake)
        assert payload is not None
        anatomy = json.loads(payload["anatomy"])
        assert "_oversized_bytes" in anatomy
        assert anatomy["_oversized_bytes"] > (1 << 16)
        assert payload["epoch"] == 2


# ---------------------------------------------------------------------------
# merge_lathist overflow-bucket handling (satellite)
# ---------------------------------------------------------------------------


class TestLathistOverflow:
    N = len(LOG2_BUCKETS) + 1  # 27 finite bounds + the overflow slot

    def _hist(self, finite=0, overflow=0):
        counts = [0] * self.N
        if finite:
            counts[10] = finite
        counts[-1] = overflow
        return {
            "counts": counts,
            "count": finite + overflow,
            "sum_ns": (finite + overflow) * 1000,
        }

    def test_overflow_counts_merge_exactly(self):
        a = {"op": self._hist(finite=3, overflow=2)}
        b = {"op": self._hist(finite=1, overflow=5)}
        merged = merge_lathist(a, b)["op"]
        assert merged["counts"][-1] == 7  # overflow slot is elementwise too
        assert merged["counts"][10] == 4
        assert merged["count"] == 11
        assert merged["sum_ns"] == 11000

    def test_overflow_only_quantile_clamps_to_last_bound(self):
        # all mass past 2^6 s: the interpolated quantile must clamp to
        # the last FINITE bound, never invent a value or divide by zero
        h = self._hist(overflow=10)
        assert lathist_quantile(h, 0.5) == LOG2_BUCKETS[-1]
        assert lathist_quantile(h, 0.99) == LOG2_BUCKETS[-1]

    def test_bucket_count_mismatch_is_loud(self):
        a = {"op": self._hist(finite=1)}
        bad = self._hist(finite=1)
        bad["counts"] = bad["counts"][:-1]  # overflow slot missing
        with pytest.raises(ValueError, match="bucket count mismatch"):
            merge_lathist(a, {"op": bad})

    def test_one_sided_merge_preserves_overflow(self):
        merged = merge_lathist({"op": self._hist(overflow=4)}, {})
        assert merged["op"]["counts"][-1] == 4


# ---------------------------------------------------------------------------
# series builder
# ---------------------------------------------------------------------------


class TestBuildSeries:
    def setup_method(self):
        telemetry.reset()

    def teardown_method(self):
        telemetry.reset()

    def test_series_from_last_row_with_flags(self):
        import time

        from torchft_tpu.telemetry.timeseries import build_series

        telemetry.LEDGER.tick(step=0)
        telemetry.LEDGER.record("compute", 0.08)
        telemetry.LEDGER.record("wire", 0.02)
        time.sleep(0.12)  # real wall between ticks so the row has one
        telemetry.LEDGER.tick(step=1)
        s = build_series(slo_breach=True, divergence=False)
        assert s is not None
        assert s["phase.compute"] == pytest.approx(0.08)
        assert s["phase.wire"] == pytest.approx(0.02)
        assert s["wall_s"] >= 0.12 and s["local_s"] > 0
        # local excludes the barrier phase by construction
        assert s["local_s"] <= s["wall_s"] - 0.02 + 1e-6
        assert s["flag.slo_breach"] == 1.0
        assert s["flag.divergence"] == 0.0

    def test_none_before_first_row_and_when_disabled(self, monkeypatch):
        from torchft_tpu.telemetry.timeseries import build_series

        assert build_series() is None  # no rows yet
        telemetry.LEDGER.tick(step=0)
        telemetry.LEDGER.tick(step=1)
        monkeypatch.setenv("TORCHFT_TSDB_SERIES", "0")
        assert build_series() is None

    def test_fanout_cap_trims_by_priority(self, monkeypatch):
        # a trim must cut diagnostics (flags, lat quantiles) before the
        # series the critical-path/regression planes depend on — an
        # alphabetical trim would cut wall_s FIRST and keep flag.*
        from torchft_tpu.telemetry import timeseries

        telemetry.LEDGER.tick(step=0)
        telemetry.LEDGER.record("compute", 0.01)
        telemetry.LEDGER.tick(step=1)
        monkeypatch.setenv("TORCHFT_TSDB_MAX_SERIES", "4")
        s = timeseries.build_series(slo_breach=True)
        assert s is not None and len(s) == 4
        for essential in ("wall_s", "local_s", "local_p50_s",
                          "phase.compute"):
            assert essential in s, s
        assert not any(k.startswith("flag.") for k in s)


# ---------------------------------------------------------------------------
# faultinject `after` onset rule
# ---------------------------------------------------------------------------


class TestAfterRule:
    def test_after_fires_from_onset_onward(self):
        from torchft_tpu.faultinject.core import FaultPlane

        plane = FaultPlane({
            "seed": 1,
            "rules": [{
                "site": "collective.issue", "match": "allreduce",
                "after": 4, "action": "delay", "ms": 1,
            }],
        })
        fired = [
            plane.hit("collective.issue", "allreduce", {}) is not None
            for _ in range(8)
        ]
        assert fired == [False] * 3 + [True] * 5

    def test_after_exclusive_with_nth(self):
        from torchft_tpu.faultinject.core import FaultPlane

        with pytest.raises(ValueError, match="at most one"):
            FaultPlane({
                "rules": [{
                    "site": "rpc.send", "nth": 2, "after": 3,
                    "action": "delay", "ms": 1,
                }],
            })
