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
    def test_two_sources_bit_identical(self, transports, monkeypatch):
        from torchft_tpu import _native

        # WHICH source serves a range is a race between threads of one
        # busy host: a source whose streams are scheduled late finds the
        # queue drained. So no range moves before each source has been
        # asked for one — then a plan that gives both sources streams has
        # both serve bytes, and one that does not fails every range here
        asked, both = set(), threading.Event()
        real_fetch = _native.blob_fetch

        def fetch_once_both_were_asked(host, port, *args, **kw):
            asked.add(port)
            if len(asked) == 2:
                both.set()
            if not both.wait(T.total_seconds()):
                raise ConnectionError(f"only the source at {asked} was asked")
            return real_fetch(host, port, *args, **kw)

        monkeypatch.setattr(_native, "blob_fetch", fetch_once_both_were_asked)
        state = _state(1)
        s1, s2, rx = transports(), transports(), transports()
        s1.send_checkpoint([1], 3, state, T)
        s2.send_checkpoint([1], 3, state, T)
        out = rx.recv_checkpoint_multi([s1.metadata(), s2.metadata()], 3, T)
        _tree_equal(out, state)
        stats = rx.last_heal_stats
        assert stats["mode"] == "striped" and both.is_set()
        # the plan: both staged the same tree, both were given streams,
        # neither failed, and between them they served every byte
        assert set(stats["sources"]) == {s1.metadata(), s2.metadata()}
        assert stats["nsources"] == 2 and stats["failures"] == {}
        _, buffers = flatten_state(state)
        served = [src["bytes"] for src in stats["sources"].values()]
        assert min(served) > 0 and sum(served) == sum(b.nbytes for b in buffers)
        assert all("gb_per_sec" in src for src in stats["sources"].values())
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

        def slow_digests(buffers, **kw):
            time.sleep(1.5)  # well past the 0.3 s bound
            return real(buffers, **kw)

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


# ---------------------------------------------------------------------------
# leaves side by side (ISSUE 49): pooled digests, several streams a source,
# a destination that is never zeroed
# ---------------------------------------------------------------------------


def _digest_tree(kind: str):
    import ml_dtypes

    rng = np.random.default_rng(49)

    def big(n):
        return rng.integers(0, 255, n, dtype=np.uint8)

    if kind == "zero_length":
        return {
            "a": big(5 << 20).view(np.float32),
            "e": np.zeros(0, np.float32),
            "b": big(5 << 20),
        }
    if kind == "bfloat16":
        return {
            "a": big(6 << 20).view(ml_dtypes.bfloat16).reshape(-1, 1024),
            "b": big(6 << 20).view(np.float32),
        }
    if kind == "dominant":
        return {
            "w": big(24 << 20),
            "x": big(1 << 10),
            "y": big(3 << 10),
            "z": np.arange(7, dtype=np.int64),
        }
    assert kind == "many"
    return {f"l{i:02d}": big((1 << 20) + i * 4096) for i in range(37)}


# (tree digest, first leaf's, last leaf's) as the parent commit 3ca545f
# computes them for the same trees: the digests are the protocol's
PARENT_DIGESTS = {
    "zero_length": ("3c70439c2605b890", "80060e1b309087f2", "e4a6a0577479b2b4"),
    "bfloat16": ("0f78d6e456b90768", "19b961dd491f4fd3", "d960e781cdd3222a"),
    "dominant": ("efac0d2609348518", "dd757824cc4d059b", "f1b4a959661d99e0"),
    "many": ("a6078d664bd87f44", "7a9c3d0ee0847bc8", "064a16b5e33725bf"),
}


class TestPooledDigests:
    @pytest.mark.parametrize("workers", [2, 3, 8])
    @pytest.mark.parametrize("kind", sorted(PARENT_DIGESTS))
    def test_pooled_equals_inline_in_order(self, kind, workers):
        _, bufs = flatten_state(_digest_tree(kind))
        inline = dm.leaf_digests(bufs)
        # handed over one by one, as buffers still landing are
        pooled = dm.leaf_digests(iter(bufs), workers=workers)
        assert pooled == inline
        tree, first, last = PARENT_DIGESTS[kind]
        assert (inline[0], inline[-1]) == (first, last)
        assert dm.tree_digest(pooled) == tree

    @pytest.mark.parametrize(
        "nbytes,nbuffers,cores,want",
        [
            (0, 0, 8, 1),
            (7 << 20, 40, 8, 1),  # under twice a worker's worth: inline
            (8 << 20, 40, 8, 2),
            (7_304_702_144, 40, 30, 16),  # the four-group cell's state
            (7_304_702_144, 40, 4, 4),  # no more than the cores seen
            (7_304_702_144, 3, 30, 3),  # one buffer is one stream
        ],
    )
    def test_width_follows_cores_leaves_bytes(
        self, monkeypatch, nbytes, nbuffers, cores, want
    ):
        monkeypatch.setattr(
            dm.os, "sched_getaffinity", lambda _pid: set(range(cores))
        )
        assert dm.digest_workers(nbytes, nbuffers) == want

    def test_small_state_starts_no_thread(self, transports, monkeypatch):
        def no_pool(*a, **kw):
            raise AssertionError("a state under the threshold hashed on a pool")

        monkeypatch.setattr(dm, "ThreadPoolExecutor", no_pool)
        before = {t.ident for t in threading.enumerate()}
        srv = transports()
        during = {t.ident for t in threading.enumerate()} - before
        srv.send_checkpoint([1], 1, _state(1), T)
        assert srv._stage_stats["digest_workers"] == 1
        # nothing but the transport's own server thread came to be
        assert {t.ident for t in threading.enumerate()} - before == during

    def test_large_state_staged_on_a_pool(self, transports, monkeypatch):
        monkeypatch.setattr(
            dm.os, "sched_getaffinity", lambda _pid: set(range(4))
        )
        names = []
        real = dm._digest

        def spy(buf):
            names.append(threading.current_thread().name)
            return real(buf)

        monkeypatch.setattr(dm, "_digest", spy)
        state = _digest_tree("many")
        srv = transports()
        srv.send_checkpoint([1], 1, state, T)
        stage = srv._stage_stats
        assert stage["digest_workers"] == 4
        assert len(names) == 37
        assert all(n.startswith("tft_heal_digest") for n in names)
        # joined when staging ends: the pool takes no core from a step
        assert not [
            t for t in threading.enumerate()
            if t.name.startswith("tft_heal_digest")
        ]
        assert srv._tree_digest == PARENT_DIGESTS["many"][0]
        assert stage["stage_s"] >= stage["d2h_s"] + stage["digest_s"] - 1e-3


def _slow_fetches(monkeypatch, native: bool, delay: float = 0.15, fail=None):
    """Make every range fetch take ``delay`` s, so that the ranges of one
    pass are in flight together, and let ``fail(src_port)`` refuse a
    source's ranges after that delay. Returns the list of fetches made,
    ``(port, offset, length, ok)``."""
    from torchft_tpu import _native
    from torchft_tpu.checkpointing import http_transport as ht

    calls = []
    if native:
        real = _native.blob_fetch

        def fetch(host, port, token, off, length, view, timeout_ms=60000):
            time.sleep(delay)
            if fail is not None and fail(port):
                calls.append((port, off, length, False))
                raise ConnectionError(f"blob fetch: injected, port {port}")
            real(host, port, token, off, length, view, timeout_ms=timeout_ms)
            calls.append((port, off, length, True))

        monkeypatch.setattr(_native, "blob_fetch", fetch)
    else:
        monkeypatch.setenv("TORCHFT_HEAL_NATIVE", "0")
        real_open = ht._traced_urlopen

        def urlopen(url, timeout):
            if "/range_" not in url:
                return real_open(url, timeout)
            import urllib.parse

            port = urllib.parse.urlsplit(url).port
            _, off, length = url.rsplit("/", 1)[1].split("_")
            time.sleep(delay)
            if fail is not None and fail(port):
                calls.append((port, int(off), int(length), False))
                raise ConnectionError(f"range fetch: injected, port {port}")
            calls.append((port, int(off), int(length), True))
            return real_open(url, timeout)

        monkeypatch.setattr(ht, "_traced_urlopen", urlopen)
    return calls


def _port_of(transport, native: bool) -> int:
    return transport._blob.port if native else transport._port


SENTINEL = 0xA5


def _sentinel_free_state(seed: int):
    """A state none of whose bytes is the sentinel the test poisons the
    un-zeroed destination with."""
    rng = np.random.default_rng(seed)
    return {
        "a": rng.integers(0, 100, (300, 1000), dtype=np.uint8),
        "b": rng.integers(0, 100, 70_001, dtype=np.uint8),
        "e": np.zeros(0, dtype=np.uint8),
        "c": rng.integers(0, 100, (64, 64), dtype=np.uint8),
    }


@pytest.fixture
def poisoned_dest(monkeypatch):
    from torchft_tpu.checkpointing import http_transport as ht

    made = []

    def alloc(total):
        made.append(np.full(total, SENTINEL, dtype=np.uint8))
        return made[-1]

    monkeypatch.setattr(ht, "_alloc_dest", alloc)
    return made


class TestStreamsPerSource:
    @pytest.mark.parametrize("native", [True, False], ids=["blob", "http"])
    def test_one_source_several_ranges_in_flight(
        self, transports, monkeypatch, poisoned_dest, native
    ):
        monkeypatch.setenv("TORCHFT_HEAL_STRIPES", "4")
        calls = _slow_fetches(monkeypatch, native)
        state = _sentinel_free_state(11)
        src, rx = transports(), transports()
        src.send_checkpoint([1], 3, state, T)
        out = rx.recv_checkpoint_multi([src.metadata()], 3, T)
        _tree_equal(out, state)
        assert len(poisoned_dest) == 1
        assert not (poisoned_dest[0] == SENTINEL).any()
        stats = rx.last_heal_stats
        assert stats["streams"] == 4 and len(calls) == 4
        assert stats["nsources"] == 1 and stats["failures"] == {}
        (srcstat,) = stats["sources"].values()
        assert srcstat["ranges"] == 4 and srcstat["bytes"] == src._total
        # four ranges side by side: the source's wall seconds are one
        # range's, not four
        assert 0.15 <= srcstat["seconds"] < 0.45
        stages = stats["stages"]
        assert {"meta_s", "alloc_s", "fetch_s", "recv_s", "decode_s"} <= set(stages)
        assert stages["recv_s"] >= stages["alloc_s"] + stages["fetch_s"] - 1e-3
        assert stats["source_stage"] == src._stage_stats
        assert set(stats["source_stage"]) == {
            "stage_s", "d2h_s", "digest_s", "digest_workers",
        }

    def test_stripes_follow_the_bytes_where_unset(self, monkeypatch):
        from torchft_tpu.checkpointing import stripes

        monkeypatch.delenv("TORCHFT_HEAL_STRIPES", raising=False)
        monkeypatch.setattr(
            stripes.os, "sched_getaffinity", lambda _pid: set(range(30))
        )
        assert stripes.heal_stripes_per_source() == 2
        assert stripes.heal_stripes_per_source(300_000) == 2
        assert stripes.heal_stripes_per_source(7_304_702_144) == 7
        assert stripes.heal_stripes_per_source(1 << 40) == 30  # the cores
        monkeypatch.setenv("TORCHFT_HEAL_STRIPES", "12")  # a user's floor
        assert stripes.heal_stripes_per_source(7_304_702_144) == 12
        monkeypatch.setenv("TORCHFT_HEAL_STRIPES", "1")
        assert stripes.heal_stripes_per_source(300_000) == 1
        assert stripes.heal_stripes_per_source(7_304_702_144) == 7

    @pytest.mark.parametrize("native", [True, False], ids=["blob", "http"])
    @pytest.mark.parametrize("survivor", [True, False], ids=["survivor", "alone"])
    def test_range_failing_with_several_streams_on_its_source(
        self, transports, monkeypatch, poisoned_dest, native, survivor
    ):
        monkeypatch.setenv("TORCHFT_HEAL_STRIPES", "3")
        state = _sentinel_free_state(12)
        good, bad, rx = transports(), transports(), transports()
        sources = [bad] + ([good] if survivor else [])
        for s in sources:
            s.send_checkpoint([1], 4, state, T)
        bad_port = _port_of(bad, native)
        calls = _slow_fetches(
            monkeypatch, native, fail=lambda port: port == bad_port
        )
        urls = [s.metadata() for s in sources]
        if not survivor:
            with pytest.raises(ConnectionError, match="striped heal incomplete"):
                rx.recv_checkpoint_multi(urls, 4, T)
            # three streams stood on the source: each range failed once
            assert [c[3] for c in calls] == [False] * 3
            return
        out = rx.recv_checkpoint_multi(urls, 4, T)
        _tree_equal(out, state)
        assert not (poisoned_dest[0] == SENTINEL).any()
        stats = rx.last_heal_stats
        assert list(stats["failures"]) == [bad.metadata()]
        assert stats["nsources"] == 1
        # every range was fetched exactly once in the end, the failed
        # ones re-queued once each onto the survivor
        ok = sorted((off, ln) for _, off, ln, good_ in calls if good_)
        assert ok == stripe_ranges(good._total, 6)
        failed = [(off, ln) for _, off, ln, good_ in calls if not good_]
        assert len(failed) == len(set(failed)) == 3
        assert stats["sources"][good.metadata()]["bytes"] == good._total
