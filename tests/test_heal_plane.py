"""Heal plane (ISSUE 9): stripe planning, the native blob plane,
striped multi-source recv (incl. a source dying mid-heal), differential
heal serialization, the commit trail, staging-window consistency, and
the heal/compile overlap hook. See docs/heal_plane.md."""

from __future__ import annotations

import threading
import time
from datetime import timedelta

import numpy as np
import pytest

from torchft_tpu.checkpointing import delta as dm
from torchft_tpu.checkpointing.http_transport import HTTPTransport
from torchft_tpu.checkpointing.serialization import (
    flatten_state,
    spec_tree_from_header,
    unflatten_state,
)
from torchft_tpu.checkpointing.stripes import (
    slice_buffers,
    stripe_ranges,
)

T = timedelta(seconds=20)


def _state(seed: int = 0):
    rng = np.random.default_rng(seed)
    return {
        "big": rng.standard_normal((512, 512)).astype(np.float32),
        "small": np.arange(37, dtype=np.int64),
        "empty": np.zeros(0, dtype=np.float32),
        "scalar": np.float32(3.25),
        "obj": {"step": seed, "note": "x"},
    }


# ---------------------------------------------------------------------------
# stripe planning
# ---------------------------------------------------------------------------


class TestStripeRanges:
    def test_covers_exactly_and_balances(self):
        total = 10_000_000 + 13
        for n in (1, 2, 3, 7):
            ranges = stripe_ranges(total, n)
            assert sum(ln for _, ln in ranges) == total
            # contiguous, ordered, non-overlapping
            pos = 0
            for off, ln in ranges:
                assert off == pos and ln > 0
                pos += ln
            # byte balance: one large leaf cannot skew a stripe — ranges
            # differ by at most the alignment quantum + remainder
            lens = [ln for _, ln in ranges]
            assert max(lens) - min(lens) <= 64 + total % 64

    def test_deterministic_and_degenerate(self):
        assert stripe_ranges(1000, 3) == stripe_ranges(1000, 3)
        assert stripe_ranges(0, 4) == []
        # tiny blob: fewer ranges than requested, still covering
        ranges = stripe_ranges(10, 8)
        assert sum(ln for _, ln in ranges) == 10

    def test_slice_buffers_round_trip_with_zero_len(self):
        bufs = [
            np.arange(100, dtype=np.uint8),
            np.zeros(0, dtype=np.uint8),
            np.arange(50, dtype=np.float32).view(np.uint8),
        ]
        sizes = [b.nbytes for b in bufs]
        total = sum(sizes)
        flat = b"".join(bytes(b) for b in bufs)
        for off, ln in stripe_ranges(total, 3) + [(0, total), (99, 150)]:
            got = b"".join(
                bytes(mv) for mv in slice_buffers(bufs, sizes, off, ln)
            )
            assert got == flat[off : off + ln], (off, ln)


# ---------------------------------------------------------------------------
# native blob plane
# ---------------------------------------------------------------------------


class TestNativeBlob:
    def test_round_trip_stale_and_unstage(self):
        from torchft_tpu import _native

        srv = _native.BlobServer()
        try:
            a = np.arange(5000, dtype=np.float32)
            z = np.zeros(0, dtype=np.uint8)
            b = np.arange(17, dtype=np.uint8)
            bufs = [a, z, b]
            srv.stage([x.ctypes.data for x in bufs],
                      [x.nbytes for x in bufs], token=7)
            total = sum(x.nbytes for x in bufs)
            dst = memoryview(bytearray(total))
            # ranges crossing buffer boundaries
            for off, ln in stripe_ranges(total, 3):
                _native.blob_fetch(
                    "localhost", srv.port, 7, off, ln, dst[off : off + ln]
                )
            assert bytes(dst) == bytes(a.view(np.uint8)) + bytes(b)
            # stale token is a loud error, never stale bytes
            with pytest.raises(ConnectionError, match="stale"):
                _native.blob_fetch("localhost", srv.port, 8, 0, 4, dst[:4])
            with pytest.raises(ConnectionError, match="range"):
                _native.blob_fetch(
                    "localhost", srv.port, 7, total - 2, 8, dst[:8]
                )
            srv.unstage()
            with pytest.raises(ConnectionError, match="stale"):
                _native.blob_fetch("localhost", srv.port, 7, 0, 4, dst[:4])
        finally:
            srv.close()


# ---------------------------------------------------------------------------
# striped multi-source recv
# ---------------------------------------------------------------------------


@pytest.fixture
def transports():
    made = []

    def make():
        t = HTTPTransport(T, hostname="localhost")
        made.append(t)
        return t

    yield make
    for t in made:
        t.shutdown()


def _tree_equal(a, b):
    import jax

    la, ta = jax.tree_util.tree_flatten(a)
    lb, tb = jax.tree_util.tree_flatten(b)
    assert str(ta) == str(tb)
    for x, y in zip(la, lb):
        assert np.array_equal(np.asarray(x), np.asarray(y))


class TestStripedMultiSource:
    def test_two_sources_bit_identical(self, transports):
        state = _state(1)
        s1, s2, rx = transports(), transports(), transports()
        s1.send_checkpoint([1], 3, state, T)
        s2.send_checkpoint([1], 3, state, T)
        out = rx.recv_checkpoint_multi([s1.metadata(), s2.metadata()], 3, T)
        _tree_equal(out, state)
        stats = rx.last_heal_stats
        assert stats["mode"] == "striped"
        assert stats["nsources"] == 2
        # per-source throughput attribution present for every source
        for src_stats in stats["sources"].values():
            assert src_stats["bytes"] > 0 and "gb_per_sec" in src_stats
        assert {"meta_s", "recv_s", "decode_s"} <= set(stats["stages"])

    def test_divergent_source_excluded(self, transports):
        # a source staging DIFFERENT bytes (diverged LocalSGD inner
        # state) must be excluded, never mixed in
        state, other = _state(1), _state(2)
        s1, s2, rx = transports(), transports(), transports()
        s1.send_checkpoint([1], 4, state, T)
        s2.send_checkpoint([1], 4, other, T)
        out = rx.recv_checkpoint_multi([s1.metadata(), s2.metadata()], 4, T)
        _tree_equal(out, state)
        assert rx.last_heal_stats["nsources"] == 1

    def test_healed_round_trip_source_not_excluded(self, transports):
        # pickle is not canonical: a heal-round-tripped tree serializes
        # to a different HEADER than a freshly-built one — the digest
        # must be over buffer bytes so such a source still stripes
        state = _state(1)
        h, b = flatten_state(state)
        rebuilt = unflatten_state(h, b)  # the once-healed lineage
        s1, s2, rx = transports(), transports(), transports()
        s1.send_checkpoint([1], 5, state, T)
        s2.send_checkpoint([1], 5, rebuilt, T)
        out = rx.recv_checkpoint_multi([s1.metadata(), s2.metadata()], 5, T)
        _tree_equal(out, state)
        assert rx.last_heal_stats["nsources"] == 2

    def test_source_death_mid_heal_re_stripes(self, transports):
        state = _state(3)
        s1, rx = transports(), transports()
        s2 = HTTPTransport(T, hostname="localhost")
        s1.send_checkpoint([1], 6, state, T)
        s2.send_checkpoint([1], 6, state, T)
        s2.shutdown()  # dies after planning sees it — ranges must move
        out = rx.recv_checkpoint_multi([s1.metadata(), s2.metadata()], 6, T)
        _tree_equal(out, state)

    def test_header_cb_fires_with_spec_tree(self, transports):
        state = _state(4)
        s1, rx = transports(), transports()
        s1.send_checkpoint([1], 7, state, T)
        seen = []
        rx.recv_checkpoint_multi(
            [s1.metadata()], 7, T, header_cb=lambda h: seen.append(h)
        )
        assert len(seen) == 1
        spec = spec_tree_from_header(seen[0])
        assert spec["big"].shape == (512, 512)
        assert np.dtype(spec["big"].dtype) == np.float32
        assert spec["empty"].shape == (0,)
        assert spec["obj"] == {"step": 4, "note": "x"}  # obj leaves verbatim

    def test_single_source_path(self, transports):
        state = _state(5)
        s1, rx = transports(), transports()
        s1.send_checkpoint([1], 8, state, T)
        out = rx.recv_checkpoint_multi([s1.metadata()], 8, T)
        _tree_equal(out, state)

    def test_slow_staging_is_waited_for(self, transports, monkeypatch):
        """Flatten + digest of a multi-GB state outlasts the brief
        staging-window bound. First contact at 647M parameters (7.8 GB of
        state on a v5e host): every heal answered 503 "no checkpoint
        staged within timeout" and no step ever committed. A source that
        IS staging must be waited for."""
        monkeypatch.setenv("TORCHFT_HEAL_META_TIMEOUT_S", "0.3")
        real = dm.leaf_digests

        def slow_digests(buffers):
            time.sleep(1.5)  # well past the 0.3 s bound
            return real(buffers)

        monkeypatch.setattr(dm, "leaf_digests", slow_digests)
        state = _state(9)
        s1, rx = transports(), transports()
        staging = threading.Thread(
            target=s1.send_checkpoint, args=([1], 9, state, T)
        )
        staging.start()
        try:
            out = rx.recv_checkpoint_multi([s1.metadata()], 9, T)
        finally:
            staging.join(timeout=30)
        assert not staging.is_alive()
        _tree_equal(out, state)

    def test_source_that_never_stages_fails_fast(self, transports, monkeypatch):
        monkeypatch.setenv("TORCHFT_HEAL_META_TIMEOUT_S", "0.3")
        s1, rx = transports(), transports()
        t0 = time.perf_counter()
        with pytest.raises(ConnectionError, match="no heal source reachable"):
            rx.recv_checkpoint_multi([s1.metadata()], 9, T)
        # the brief bound, not the 20 s transfer timeout
        assert time.perf_counter() - t0 < 5.0


# ---------------------------------------------------------------------------
# differential heal
# ---------------------------------------------------------------------------


class TestDifferentialHeal:
    def _staged_pair(self):
        """(state@S as the healer holds it, state@S+1 with one changed
        leaf) — 'frozen' and 'empty' unchanged, 'big'/'scalar'/'obj'
        changed."""
        s0 = _state(1)
        s1 = dict(s0)
        s1["big"] = s0["big"] * 2.0
        s1["scalar"] = np.float32(4.5)
        s1["obj"] = {"step": 99, "note": "x"}
        return s0, s1

    def test_delta_ships_strictly_fewer_bytes_and_round_trips(self, transports):
        s0, s1 = self._staged_pair()
        h0, b0 = flatten_state(s0)
        srv, rx = transports(), transports()
        trail = dm.CommitTrail(horizon=4)
        srv.commit_trail = trail
        d0 = trail.record(3, b0)
        own = (b0, dm.tree_digest(d0))
        srv.send_checkpoint([1], 4, s1, T)
        out = rx.recv_checkpoint_multi(
            [srv.metadata()], 4, T, since_step=3, own=own
        )
        _tree_equal(out, s1)  # dtype/shape/zero-length preserved
        stats = rx.last_heal_stats
        assert stats["mode"] == "delta"
        full_bytes = len(h0) + sum(int(b.nbytes) for b in b0)
        # the acceptance criterion: a 1-step absence ships STRICTLY
        # fewer bytes than the full heal
        assert stats["bytes"] < full_bytes
        # only the changed array buffer travelled (big; scalar/obj are
        # non-ndarray leaves riding the header, frozen/empty are reused
        # from the healer's own buffers)
        assert stats["delta"]["changed"] == 1

    def test_digest_mismatch_falls_back_to_full(self, transports):
        s0, s1 = self._staged_pair()
        _, b0 = flatten_state(s0)
        srv, rx = transports(), transports()
        trail = dm.CommitTrail(horizon=4)
        srv.commit_trail = trail
        trail.record(3, b0)
        srv.send_checkpoint([1], 4, s1, T)
        out = rx.recv_checkpoint_multi(
            [srv.metadata()], 4, T, since_step=3, own=(b0, "0badd1635")
        )
        _tree_equal(out, s1)
        assert rx.last_heal_stats["mode"] == "striped"

    def test_trail_horizon_eviction_forces_full(self, transports):
        s0, s1 = self._staged_pair()
        _, b0 = flatten_state(s0)
        srv, rx = transports(), transports()
        trail = dm.CommitTrail(horizon=2)
        srv.commit_trail = trail
        d0 = trail.record(3, b0)
        own = (b0, dm.tree_digest(d0))
        # two more steps evict step 3 past the horizon
        trail.record(4, b0)
        trail.record(5, b0)
        assert trail.get(3) is None
        assert trail.steps() == [4, 5]
        srv.send_checkpoint([1], 6, s1, T)
        out = rx.recv_checkpoint_multi(
            [srv.metadata()], 6, T, since_step=3, own=own
        )
        _tree_equal(out, s1)
        assert rx.last_heal_stats["mode"] == "striped"

    def test_apply_delta_layout_checks(self):
        s0, _ = self._staged_pair()
        h, b = flatten_state(s0)
        with pytest.raises(ValueError, match="truncated"):
            dm.apply_delta(
                {"header": h, "changed": [0], "sizes": [8]}, b"", b
            )
        with pytest.raises(ValueError, match="out of range"):
            dm.apply_delta(
                {"header": h, "changed": [99], "sizes": [1]}, b"\0", b
            )

    def test_build_delta_refusals(self):
        s0, s1 = self._staged_pair()
        h1, b1 = flatten_state(s1)
        d1 = dm.leaf_digests(b1)
        # no trail entry
        assert dm.build_delta(h1, b1, d1, None, "x") is None
        # tree digest mismatch
        ent = {"tree": "notit", "leaves": d1, "sizes": [b.nbytes for b in b1]}
        assert dm.build_delta(h1, b1, d1, ent, "x") is None
        # leaf-count drift
        ent = {"tree": "t", "leaves": d1 + ["extra"], "sizes": []}
        assert dm.build_delta(h1, b1, d1, ent, "t") is None


# ---------------------------------------------------------------------------
# staging-window consistency (serve overlapping a commit)
# ---------------------------------------------------------------------------


class TestServingWindowConsistency:
    def test_restage_never_serves_mixed_bytes(self, transports):
        """A slow reader overlapping disallow+restage must get either the
        OLD staging in full or a loud failure — never bytes of both. The
        write lock waits readers out; the blob plane's token turns any
        post-restage fetch into a stale error."""
        state_a, state_b = _state(1), _state(2)
        srv = transports()
        rx = transports()
        srv.send_checkpoint([1], 1, state_a, T)
        total = srv._total
        meta_a = __import__("pickle").loads(
            b"".join(srv._render_stripemeta())
        )
        errors, goods = [], []

        def reader():
            dst = memoryview(bytearray(total))
            try:
                from torchft_tpu import _native

                for off, ln in stripe_ranges(total, 4):
                    _native.blob_fetch(
                        "localhost", meta_a["blob_port"], meta_a["token"],
                        off, ln, dst[off : off + ln],
                    )
                goods.append(bytes(dst))
            except ConnectionError as e:
                errors.append(str(e))

        threads = [threading.Thread(target=reader) for _ in range(4)]
        for t in threads:
            t.start()
        time.sleep(0.005)
        srv.send_checkpoint([1], 2, state_b, T)  # disallow + restage
        for t in threads:
            t.join()
        _, bufs_a = flatten_state(state_a)
        flat_a = b"".join(bytes(np.ascontiguousarray(b).view(np.uint8))
                          for b in bufs_a)
        for g in goods:
            assert g == flat_a  # completed reads are the OLD bytes, whole
        for e in errors:
            assert "stale" in e or "recv" in e or "closed" in e

    def test_rwlock_per_acquire_timeout(self):
        from torchft_tpu.checkpointing._rwlock import RWLock

        lock = RWLock(timeout=30.0)
        lock.w_acquire()
        t0 = time.perf_counter()
        with pytest.raises(TimeoutError):
            lock.r_acquire(timeout=0.1)
        assert time.perf_counter() - t0 < 5.0  # bounded, not the default

    def test_commit_trail_thread_consistency(self):
        """Concurrent record (commit boundary) and get (a serve) must
        always observe a complete entry or none."""
        trail = dm.CommitTrail(horizon=4)
        bufs = [np.arange(64, dtype=np.uint8)]
        stop = threading.Event()
        bad = []

        def server():
            while not stop.is_set():
                for s in range(16):
                    ent = trail.get(s)
                    if ent is not None and (
                        "tree" not in ent or len(ent["leaves"]) != 1
                    ):
                        bad.append(ent)

        th = threading.Thread(target=server)
        th.start()
        for s in range(16):
            trail.record(s, bufs)
        stop.set()
        th.join()
        assert not bad
        assert len(trail.steps()) == 4  # horizon enforced throughout


# ---------------------------------------------------------------------------
# quorum plumbing + manager staging fan-out
# ---------------------------------------------------------------------------


class TestQuorumHealSources:
    def _quorum(self, steps):
        members = [
            {
                "replica_id": f"g{i}",
                "address": f"addr{i}",
                "store_address": f"store{i}",
                "step": s,
                "world_size": 1,
                "shrink_only": False,
            }
            for i, s in enumerate(steps)
        ]
        return {"quorum_id": 9, "participants": members, "created": 0}

    def test_cohort_addresses_and_heal_pending(self):
        from torchft_tpu import _native

        # g2 behind: sources = the whole max-step cohort, everyone sees
        # heal_pending
        out = _native.compute_quorum_results(self._quorum([5, 5, 3]), "g0", 0)
        assert out["heal_pending"] is True
        assert out["recover_src_addresses"] == ["addr0", "addr1"]
        out2 = _native.compute_quorum_results(self._quorum([5, 5, 3]), "g2", 0)
        assert out2["heal"] is True
        assert out2["recover_src_addresses"] == ["addr0", "addr1"]

    def test_bootstrap_single_source(self):
        from torchft_tpu import _native

        # max_step == 0: states are not yet proven identical — only the
        # bootstrap source is a sound stripe source
        out = _native.compute_quorum_results(self._quorum([0, 0, 0]), "g1", 0)
        assert out["heal_pending"] is True
        assert out["recover_src_addresses"] == ["addr0"]

    def test_no_heal_no_pending(self):
        from torchft_tpu import _native

        out = _native.compute_quorum_results(self._quorum([4, 4, 4]), "g1", 0)
        assert out["heal_pending"] is False
