"""Collectives conformance tests.

Mirrors the reference's PG test strategy (process_group_test.py:67-251):
every collective exercised on world-size-1, then multi-rank semantics checks
with rank threads sharing one store, then reconfiguration.
"""

from concurrent.futures import ThreadPoolExecutor
from datetime import timedelta

import numpy as np
import pytest

from torchft_tpu.collectives import (
    CollectivesDummy,
    CollectivesTcp,
    ErrorSwallowingCollectives,
    ReduceOp,
)
from torchft_tpu.store import StoreServer


@pytest.fixture()
def store():
    s = StoreServer()
    yield s
    s.shutdown()


def _run_world(store, world, fn, prefix="test", **coll_kwargs):
    """Run fn(coll, rank) on `world` configured TCP collectives, one thread
    per rank (the reference's in-process multi-rank harness). Extra kwargs
    go to the CollectivesTcp constructors (e.g. wire_dtype)."""
    coll_kwargs.setdefault("timeout", timedelta(seconds=10))
    colls = [
        CollectivesTcp(hostname="localhost", **coll_kwargs)
        for _ in range(world)
    ]

    def start(rank):
        colls[rank].configure(f"{store.address()}/{prefix}", rank, world)
        try:
            return fn(colls[rank], rank)
        finally:
            colls[rank].shutdown()

    with ThreadPoolExecutor(max_workers=world) as ex:
        return list(ex.map(start, range(world)))


class TestSingleRank:
    def test_all_ops(self, store):
        c = CollectivesTcp(timeout=timedelta(seconds=5), hostname="localhost")
        c.configure(f"{store.address()}/solo", 0, 1)
        a = np.arange(8, dtype=np.float32)

        out = c.allreduce([a.copy()], ReduceOp.SUM).wait()
        np.testing.assert_array_equal(out[0], a)

        ag = c.allgather(a).wait()
        assert len(ag) == 1
        np.testing.assert_array_equal(ag[0], a)

        b = a.copy()
        c.broadcast(b, root=0).wait()
        np.testing.assert_array_equal(b, a)

        rs = c.reduce_scatter([a.copy()], ReduceOp.SUM).wait()
        np.testing.assert_array_equal(rs, a)

        a2a = c.alltoall([a.copy()]).wait()
        np.testing.assert_array_equal(a2a[0], a)

        c.barrier().wait()
        assert c.size() == 1 and c.rank() == 0
        c.shutdown()


class TestMultiRank:
    @pytest.mark.parametrize("world", [2, 3, 4])
    def test_allreduce_sum(self, store, world):
        def fn(c, rank):
            a = np.full(13, float(rank + 1), dtype=np.float32)
            return c.allreduce([a], ReduceOp.SUM).wait(timedelta(seconds=10))[0]

        outs = _run_world(store, world, fn, prefix=f"ar{world}")
        want = sum(range(1, world + 1))
        for out in outs:
            np.testing.assert_allclose(out, want)

    def test_allreduce_bfloat16_ring(self, store):
        """ml_dtypes buffers must cross the ring (gradients are bf16; plain
        memoryview() rejects them — _bytes_view reinterprets as uint8)."""
        import ml_dtypes

        def fn(c, rank):
            a = np.full(300, float(rank + 1), dtype=ml_dtypes.bfloat16)
            return c.allreduce([a], ReduceOp.AVG).wait(timedelta(seconds=10))[0]

        outs = _run_world(store, 2, fn, prefix="arbf16")
        for out in outs:
            assert out.dtype == ml_dtypes.bfloat16
            np.testing.assert_allclose(out.astype(np.float32), 1.5)

    def test_allreduce_avg_and_max(self, store):
        def fn(c, rank):
            a = np.full(5, float(rank), dtype=np.float64)
            avg = c.allreduce([a.copy()], ReduceOp.AVG).wait()[0]
            mx = c.allreduce([a.copy()], ReduceOp.MAX).wait()[0]
            return avg, mx

        outs = _run_world(store, 3, fn, prefix="avgmax")
        for avg, mx in outs:
            np.testing.assert_allclose(avg, 1.0)  # (0+1+2)/3
            np.testing.assert_allclose(mx, 2.0)

    def test_allreduce_multiple_arrays_and_dtypes(self, store):
        def fn(c, rank):
            xs = [
                np.full(3, rank + 1, dtype=np.float32),
                np.full((2, 2), rank + 1, dtype=np.int64),
            ]
            return c.allreduce(xs, ReduceOp.SUM).wait()

        outs = _run_world(store, 2, fn, prefix="multi")
        for xs in outs:
            np.testing.assert_allclose(xs[0], 3.0)
            np.testing.assert_array_equal(xs[1], np.full((2, 2), 3))

    def test_allgather(self, store):
        def fn(c, rank):
            return c.allgather(np.full(4, rank, dtype=np.float32)).wait()

        outs = _run_world(store, 3, fn, prefix="ag")
        for got in outs:
            for r in range(3):
                np.testing.assert_allclose(got[r], float(r))

    def test_broadcast(self, store):
        def fn(c, rank):
            a = (
                np.arange(6, dtype=np.float32)
                if rank == 1
                else np.zeros(6, dtype=np.float32)
            )
            c.broadcast(a, root=1).wait()
            return a

        outs = _run_world(store, 3, fn, prefix="bc")
        for a in outs:
            np.testing.assert_allclose(a, np.arange(6, dtype=np.float32))

    def test_reduce_scatter(self, store):
        world = 3

        def fn(c, rank):
            # arrays[j] is this rank's contribution to rank j
            arrays = [
                np.full(4, (rank + 1) * 10 + j, dtype=np.float32)
                for j in range(world)
            ]
            return c.reduce_scatter(arrays, ReduceOp.SUM).wait()

        outs = _run_world(store, world, fn, prefix="rs")
        for j, got in enumerate(outs):
            want = sum((r + 1) * 10 + j for r in range(world))
            np.testing.assert_allclose(got, float(want))

    def test_alltoall(self, store):
        world = 3

        def fn(c, rank):
            arrays = [
                np.full(2, rank * 10 + j, dtype=np.int32) for j in range(world)
            ]
            return c.alltoall(arrays).wait()

        outs = _run_world(store, world, fn, prefix="a2a")
        for j, got in enumerate(outs):
            for r in range(world):
                np.testing.assert_array_equal(got[r], r * 10 + j)

    def test_send_recv(self, store):
        def fn(c, rank):
            if rank == 0:
                c.send(np.arange(5, dtype=np.float32), dst=1, tag=7).wait()
                return None
            buf = np.zeros(5, dtype=np.float32)
            c.recv(buf, src=0, tag=7).wait()
            return buf

        outs = _run_world(store, 2, fn, prefix="p2p")
        np.testing.assert_allclose(outs[1], np.arange(5, dtype=np.float32))

    def test_barrier(self, store):
        def fn(c, rank):
            c.barrier().wait(timedelta(seconds=10))
            return True

        assert all(_run_world(store, 3, fn, prefix="bar"))

    def test_large_uneven_allreduce(self, store):
        # array smaller than world and a large one exercising chunking
        def fn(c, rank):
            small = np.full(2, float(rank), dtype=np.float32)
            big = np.full(100003, float(rank + 1), dtype=np.float32)
            return c.allreduce([small, big], ReduceOp.SUM).wait(
                timedelta(seconds=30)
            )

        outs = _run_world(store, 4, fn, prefix="big")
        for small, big in outs:
            np.testing.assert_allclose(small, 6.0)
            np.testing.assert_allclose(big, 10.0)

    def test_reconfigure_changes_world(self, store):
        # same objects reconfigured into a smaller epoch, like a shrinking
        # quorum (process_group_test.py:346-380 reconfiguration checks)
        colls = [
            CollectivesTcp(timeout=timedelta(seconds=10), hostname="localhost")
            for _ in range(3)
        ]

        def epoch1(rank):
            colls[rank].configure(f"{store.address()}/e1", rank, 3)
            a = np.ones(4, dtype=np.float32)
            return colls[rank].allreduce([a], ReduceOp.SUM).wait()[0]

        with ThreadPoolExecutor(max_workers=3) as ex:
            outs = list(ex.map(epoch1, range(3)))
        for out in outs:
            np.testing.assert_allclose(out, 3.0)

        def epoch2(rank):
            colls[rank].configure(f"{store.address()}/e2", rank, 2)
            a = np.ones(4, dtype=np.float32)
            out = colls[rank].allreduce([a], ReduceOp.SUM).wait()[0]
            colls[rank].shutdown()
            return out

        with ThreadPoolExecutor(max_workers=2) as ex:
            outs = list(ex.map(epoch2, range(2)))
        for out in outs:
            np.testing.assert_allclose(out, 2.0)
        colls[2].shutdown()


class TestWirePipeline:
    """Round-3 data-plane upgrades: bf16 wire compression, tag-matched
    receives surviving out-of-order concurrent p2p traffic, and windowed
    (≤k in flight) transfer pipelines — the host-path answer to the role
    NCCL's async streams play in the reference
    (process_group.py:431-447)."""

    def test_bf16_wire_allreduce(self, store):
        def fn(c, rank):
            arr = np.linspace(-3.0, 3.0, 4099, dtype=np.float32) * (rank + 1)
            return c.allreduce([arr], ReduceOp.AVG).wait(
                timedelta(seconds=20)
            )[0]

        outs = _run_world(store, 3, fn, prefix="bf16w", wire_dtype="bfloat16")
        expect = np.linspace(-3.0, 3.0, 4099, dtype=np.float32) * 2.0
        for out in outs:
            assert out.dtype == np.float32
            # bf16 has ~3 decimal digits; per-hop requantization over a
            # 3-ring stays within a few ulps of that
            np.testing.assert_allclose(out, expect, rtol=3e-2, atol=3e-2)
        # lossy wire must still be DETERMINISTICALLY lossy: every rank
        # holds the bitwise-identical result, or replica groups that use
        # bf16-wire gradient averaging silently diverge (round-3 advisor
        # high finding: the chunk owner kept full f32 while peers stored
        # the bf16-rounded copy)
        for out in outs[1:]:
            np.testing.assert_array_equal(out, outs[0])

    def test_bf16_wire_bitwise_identical_world4(self, store):
        # uneven chunks + SUM: same bitwise-equality invariant
        def fn(c, rank):
            rng = np.random.default_rng(17 + rank)
            arr = rng.standard_normal(7331).astype(np.float32)
            return c.allreduce([arr], ReduceOp.SUM).wait(
                timedelta(seconds=30)
            )[0]

        outs = _run_world(store, 4, fn, prefix="bf16bw4", wire_dtype="bfloat16")
        for out in outs[1:]:
            np.testing.assert_array_equal(out, outs[0])

    def test_out_of_order_tags_are_matched(self, store):
        # rank 0 sends tag B then tag A; rank 1 waits for A first: the
        # B frame must be stashed, not declared a desync
        def fn(c, rank):
            if rank == 0:
                c.send(np.full(4, 7.0, dtype=np.float32), dst=1, tag=22).wait()
                c.send(np.full(4, 5.0, dtype=np.float32), dst=1, tag=11).wait()
                return None
            a = np.zeros(4, dtype=np.float32)
            b = np.zeros(4, dtype=np.float32)
            wa = c.recv(a, src=0, tag=11)
            wb = c.recv(b, src=0, tag=22)
            wa.wait(timedelta(seconds=10))
            wb.wait(timedelta(seconds=10))
            return a, b

        outs = _run_world(store, 2, fn, prefix="ooo")
        a, b = outs[1]
        np.testing.assert_allclose(a, 5.0)
        np.testing.assert_allclose(b, 7.0)

    def test_windowed_p2p_pipeline(self, store):
        # ≤3 concurrent sends/recvs with per-buffer tags complete and land
        # in the right buffers (the checkpoint-transport schedule)
        n_bufs, size = 10, 2048

        def fn(c, rank):
            if rank == 0:
                works = []
                for i in range(n_bufs):
                    works.append(
                        c.send(
                            np.full(size, float(i), dtype=np.float32),
                            dst=1,
                            tag=100 + i,
                        )
                    )
                    while len(works) >= 3:
                        works.pop(0).wait(timedelta(seconds=10))
                for w in works:
                    w.wait(timedelta(seconds=10))
                return None
            bufs = [np.zeros(size, dtype=np.float32) for _ in range(n_bufs)]
            works = [
                c.recv(bufs[i], src=0, tag=100 + i) for i in range(n_bufs)
            ]
            for w in works:
                w.wait(timedelta(seconds=20))
            return bufs

        outs = _run_world(store, 2, fn, prefix="win")
        for i, buf in enumerate(outs[1]):
            np.testing.assert_allclose(buf, float(i))

    def test_concurrent_streams_soak(self, store):
        # 30 rounds of simultaneous ring allreduce + bidirectional windowed
        # p2p on the same socket pair: the stash must route every frame to
        # its op with no desync, leak, or value corruption
        rounds, nbuf = 30, 4

        def fn(c, rank):
            peer = 1 - rank
            for r in range(rounds):
                ring = np.full(1024, float(rank + 1 + r), dtype=np.float32)
                ar = c.allreduce([ring], ReduceOp.SUM)
                sends = [
                    c.send(
                        np.full(256, float(r * nbuf + i), dtype=np.float32),
                        dst=peer,
                        tag=(rank << 12) | (r * nbuf + i) & 0xFFF,
                    )
                    for i in range(nbuf)
                ]
                bufs = [np.zeros(256, dtype=np.float32) for _ in range(nbuf)]
                recvs = [
                    c.recv(
                        bufs[i],
                        src=peer,
                        tag=(peer << 12) | (r * nbuf + i) & 0xFFF,
                    )
                    for i in range(nbuf)
                ]
                ar.wait(timedelta(seconds=30))
                for w in sends + recvs:
                    w.wait(timedelta(seconds=30))
                np.testing.assert_array_equal(
                    ring, float((1 + r) + (2 + r)), err_msg=f"{rank}/{r}"
                )
                for i, buf in enumerate(bufs):
                    np.testing.assert_array_equal(
                        buf, float(r * nbuf + i), err_msg=f"{rank}/{r}/{i}"
                    )
            # stash drained: nothing parked once all ops completed
            for p in c._peers.values():
                assert p.stash_bytes == 0, p.stash
            return True

        assert all(_run_world(store, 2, fn, prefix="soak"))

    def test_bf16_wire_world4_uneven(self, store):
        # 4-rank ring with chunk sizes that don't divide evenly, compressed
        def fn(c, rank):
            arr = np.full(10007, float(rank + 1), dtype=np.float32)
            return c.allreduce([arr], ReduceOp.SUM).wait(
                timedelta(seconds=30)
            )[0]

        outs = _run_world(
            store, 4, fn, prefix="bf16w4", wire_dtype="bfloat16"
        )
        for out in outs:
            np.testing.assert_allclose(out, 10.0, rtol=2e-2)

    def test_p2p_overlaps_ring_traffic(self, store):
        # a checkpoint-style p2p transfer issued while ring allreduces run
        # on the op thread: tag matching keeps both streams intact
        def fn(c, rank):
            ring = np.full(4096, float(rank + 1), dtype=np.float32)
            ar = c.allreduce([ring], ReduceOp.SUM)
            if rank == 0:
                pw = c.send(np.arange(512, dtype=np.float32), dst=1, tag=9)
            else:
                side = np.zeros(512, dtype=np.float32)
                pw = c.recv(side, src=0, tag=9)
            ar.wait(timedelta(seconds=20))
            pw.wait(timedelta(seconds=20))
            return ring if rank == 0 else (ring, )

        outs = _run_world(store, 2, fn, prefix="olap")
        np.testing.assert_allclose(outs[0], 3.0)


class TestWedgedPeers:
    """Round-1 review weak #2: a dead/silent peer must not wedge the op
    thread forever, and teardown must not leak blocked threads
    (reference: process_group_test.py:346-397 reconfigure/leak checks)."""

    def _pair(self, store, timeout_s):
        colls = [
            CollectivesTcp(
                timeout=timedelta(seconds=timeout_s), hostname="localhost"
            )
            for _ in range(2)
        ]
        with ThreadPoolExecutor(max_workers=2) as ex:
            list(
                ex.map(
                    lambda r: colls[r].configure(
                        f"{store.address()}/wedge", r, 2
                    ),
                    range(2),
                )
            )
        return colls

    def test_silent_peer_times_out(self, store):
        import time

        c0, c1 = self._pair(store, timeout_s=1)
        try:
            # rank 1 never participates: rank 0's ring recv must fail with a
            # timeout within the configured deadline, not block forever
            a = np.ones(8, dtype=np.float32)
            t0 = time.monotonic()
            with pytest.raises(Exception):
                c0.allreduce([a], ReduceOp.SUM).wait(timedelta(seconds=5))
            assert time.monotonic() - t0 < 4.0
        finally:
            c0.shutdown()
            c1.shutdown()

    def test_shutdown_unblocks_wedged_op_and_leaks_no_threads(self, store):
        import threading
        import time

        def coll_threads():
            return [
                t
                for t in threading.enumerate()
                if t.name.startswith("tft_coll")
            ]

        baseline = len(coll_threads())
        c0, c1 = self._pair(store, timeout_s=30)
        a = np.ones(8, dtype=np.float32)
        work = c0.allreduce([a], ReduceOp.SUM)  # blocks: peer is silent
        queued = c0.allreduce([a.copy()], ReduceOp.SUM)  # parked behind it
        time.sleep(0.2)
        t0 = time.monotonic()
        c0.shutdown()  # must wake the blocked op and join the executor
        assert time.monotonic() - t0 < 5.0
        with pytest.raises(Exception):
            work.wait(timedelta(seconds=1))
        # the cancelled queued op must resolve too, not hang its waiter
        with pytest.raises(Exception):
            queued.wait(timedelta(seconds=1))
        c1.shutdown()
        deadline = time.monotonic() + 5
        while len(coll_threads()) > baseline and time.monotonic() < deadline:
            time.sleep(0.05)
        assert len(coll_threads()) <= baseline

    def test_repeated_reconfigure_leaks_no_threads(self, store):
        import threading
        import time

        before = threading.active_count()
        c = CollectivesTcp(timeout=timedelta(seconds=5), hostname="localhost")
        for epoch in range(5):
            c.configure(f"{store.address()}/re{epoch}", 0, 1)
            c.allreduce([np.ones(4, dtype=np.float32)]).wait()
        c.shutdown()
        deadline = time.monotonic() + 5
        while threading.active_count() > before and time.monotonic() < deadline:
            time.sleep(0.05)
        assert threading.active_count() <= before + 1  # store client slack


class TestWrappers:
    def test_dummy(self):
        c = CollectivesDummy(rank=0, world_size=2)
        a = np.ones(3, dtype=np.float32)
        assert c.allreduce([a]).wait()[0] is a
        assert len(c.allgather(a).wait()) == 2
        c.configure("x", 0, 2)
        assert c.configure_count == 1

    def test_error_swallowing_latches(self, store):
        inner = CollectivesTcp(timeout=timedelta(seconds=5), hostname="localhost")
        wrap = ErrorSwallowingCollectives(inner)
        # not configured -> first op errors and latches; later ops no-op
        a = np.ones(3, dtype=np.float32)
        out = wrap.allreduce([a]).wait()
        assert wrap.error() is not None
        out2 = wrap.allreduce([a]).wait()
        assert out2 == [a]
        # reconfigure clears the latch
        wrap.configure(f"{store.address()}/esw", 0, 1)
        assert wrap.error() is None
        res = wrap.allreduce([a]).wait()
        np.testing.assert_allclose(res[0], 1.0)
        wrap.shutdown()


class TestNativePlane:
    """Round-4 native data plane (native/dataplane.cc): the NCCL-role
    striped C++ ring with one-copy CMA pulls for same-host peers. The
    default fixture path already exercises CMA (in-process ranks share a
    pid); these pin down the forced-TCP mode, routing introspection,
    bitwise bf16 on the striped wire, and peer-death attribution."""

    def test_plane_info_modes(self, store, monkeypatch):
        def fn(c, rank):
            return c.plane_info()

        assert set(_run_world(store, 2, fn, prefix="pi1")) == {"cma"}
        monkeypatch.setenv("TORCHFT_DP_CMA", "0")
        assert set(_run_world(store, 2, fn, prefix="pi2")) == {"tcp-striped"}
        assert set(
            _run_world(store, 2, fn, prefix="pi3", native_plane=False)
        ) == {"python-ring"}

    @pytest.mark.parametrize("world", [2, 3])
    def test_tcp_striped_matches_python_ring(self, store, monkeypatch, world):
        monkeypatch.setenv("TORCHFT_DP_CMA", "0")

        def fn(c, rank):
            assert c.plane_info() == "tcp-striped"
            rng = np.random.default_rng(5 + rank)
            a = rng.standard_normal(100003).astype(np.float32)
            b = a.copy()
            out = c.allreduce([a], ReduceOp.AVG).wait(timedelta(seconds=20))
            return b, out[0]

        outs = _run_world(store, world, fn, prefix=f"tsm{world}")
        expect = np.mean([b for b, _ in outs], axis=0)
        for _, got in outs:
            np.testing.assert_allclose(got, expect, rtol=1e-6, atol=1e-6)
        # all ranks bitwise identical (owner-chunk distribution invariant)
        for _, got in outs[1:]:
            np.testing.assert_array_equal(got, outs[0][1])

    def test_tcp_striped_bf16_wire_bitwise(self, store, monkeypatch):
        monkeypatch.setenv("TORCHFT_DP_CMA", "0")

        def fn(c, rank):
            rng = np.random.default_rng(23 + rank)
            a = rng.standard_normal(40961).astype(np.float32)
            return c.allreduce([a], ReduceOp.SUM).wait(
                timedelta(seconds=20)
            )[0]

        outs = _run_world(
            store, 3, fn, prefix="tsbf", wire_dtype="bfloat16"
        )
        for out in outs[1:]:
            np.testing.assert_array_equal(out, outs[0])

    def test_max_min_ops(self, store):
        def fn(c, rank):
            a = np.array([rank, -rank, 7], dtype=np.float32)
            mx = c.allreduce([a.copy()], ReduceOp.MAX).wait(
                timedelta(seconds=10)
            )[0]
            mn = c.allreduce([a.copy()], ReduceOp.MIN).wait(
                timedelta(seconds=10)
            )[0]
            return mx, mn

        outs = _run_world(store, 3, fn, prefix="mxmn")
        for mx, mn in outs:
            np.testing.assert_array_equal(mx, [2.0, 0.0, 7.0])
            np.testing.assert_array_equal(mn, [0.0, -2.0, 7.0])

    @pytest.mark.parametrize("cma", ["1", "0"])
    def test_peer_death_attribution(self, store, monkeypatch, cma):
        """A rank vanishing mid-allreduce surfaces PeerGoneError with the
        dead ring rank, on both the CMA and striped-TCP transports."""
        monkeypatch.setenv("TORCHFT_DP_CMA", cma)
        from torchft_tpu.collectives import PeerGoneError

        def fn(c, rank):
            if rank == 1:
                return "died"  # shutdown() in the harness closes sockets
            a = np.ones(1 << 20, dtype=np.float32)
            try:
                c.allreduce([a], ReduceOp.SUM).wait(timedelta(seconds=15))
                return "completed"
            except PeerGoneError as e:
                return ("gone", e.peer_rank)
            except Exception as e:  # noqa: BLE001
                return ("other", type(e).__name__, str(e)[:100])

        outs = _run_world(store, 2, fn, prefix=f"pd{cma}")
        assert outs[1] == "died"
        assert outs[0][0] == "gone", outs[0]
        assert outs[0][1] == 1


class TestCmaP2P:
    """Round-4 p2p CMA fast path: frames >= TORCHFT_CMA_P2P_MIN ship a
    pull descriptor instead of streaming bytes (heal transfers at memcpy
    class speed). The in-process fixture ranks share a pid, so the CMA
    negotiation arms the path."""

    def test_large_send_recv_roundtrip(self, store, monkeypatch):
        monkeypatch.setenv("TORCHFT_CMA_P2P_MIN", str(64 * 1024))
        n = 1 << 18  # 1 MB of f32 — above the lowered threshold

        def fn(c, rank):
            assert c.plane_info() == "cma"
            if rank == 0:
                payload = np.arange(n, dtype=np.float32)
                c.send(payload, dst=1, tag=77).wait(timedelta(seconds=20))
                return payload[:4].copy()
            buf = np.zeros(n, dtype=np.float32)
            c.recv(buf, src=0, tag=77).wait(timedelta(seconds=20))
            return buf[:4].copy()

        outs = _run_world(store, 2, fn, prefix="cmap2p")
        np.testing.assert_array_equal(outs[0], outs[1])

    def test_large_frame_for_other_tag_is_stashed(self, store, monkeypatch):
        """A CMA descriptor for a tag nobody is waiting on yet must be
        pulled immediately (the sender's buffer is parked until the ack)
        and stashed for the later recv."""
        monkeypatch.setenv("TORCHFT_CMA_P2P_MIN", str(64 * 1024))
        n = 1 << 16  # 256 KB

        def fn(c, rank):
            if rank == 0:
                c.send(np.full(n, 7.0, np.float32), dst=1, tag=22).wait(
                    timedelta(seconds=20)
                )
                c.send(np.full(n, 5.0, np.float32), dst=1, tag=11).wait(
                    timedelta(seconds=20)
                )
                return None
            a = np.zeros(n, np.float32)
            b = np.zeros(n, np.float32)
            wa = c.recv(a, src=0, tag=11)
            wb = c.recv(b, src=0, tag=22)
            wa.wait(timedelta(seconds=20))
            wb.wait(timedelta(seconds=20))
            return float(a[0]), float(b[0])

        outs = _run_world(store, 2, fn, prefix="cmastash")
        assert outs[1] == (5.0, 7.0)

    def test_checkpoint_transport_rides_cma(self, store, monkeypatch):
        monkeypatch.setenv("TORCHFT_CMA_P2P_MIN", str(64 * 1024))
        from torchft_tpu.checkpointing.collectives_transport import (
            CollectivesTransport,
        )

        state = {"w": np.random.default_rng(3).standard_normal(1 << 18).astype(np.float32)}

        def fn(c, rank):
            t = CollectivesTransport(c, timeout=timedelta(seconds=20))
            if rank == 0:
                t.send_checkpoint([1], 0, state, timedelta(seconds=20))
                return None
            got = t.recv_checkpoint(0, t.metadata(), 0, timedelta(seconds=20))
            return np.asarray(got["w"])

        outs = _run_world(store, 2, fn, prefix="cmaheal")
        np.testing.assert_array_equal(outs[1], state["w"])

    def test_ack_timeout_quarantines_and_poisons(self, store, monkeypatch):
        """If the pull-ack never arrives, the sender must pin the buffer
        process-wide (a dangling descriptor may still be pulled later) and
        poison the stream — never surface a retryable timeout that lets
        the caller reuse the memory."""
        monkeypatch.setenv("TORCHFT_CMA_P2P_MIN", str(64 * 1024))
        import time

        import torchft_tpu.collectives as C

        before = len(C._CMA_QUARANTINE)
        n = 1 << 16

        def fn(c, rank):
            if rank == 1:
                time.sleep(3.0)  # never posts the recv inside the timeout
                return "slept"
            payload = np.full(n, 3.0, np.float32)
            try:
                c.send(payload, dst=1, tag=33).wait(timedelta(seconds=8))
                return "sent"
            except Exception as e:  # noqa: BLE001
                return type(e).__name__

        outs = _run_world(
            store, 2, fn, prefix="cmaq", timeout=timedelta(seconds=1)
        )
        assert outs[1] == "slept"
        # the send failed terminally (poisoned epoch), not retryably
        assert outs[0] in ("PeerGoneError", "ConnectionError"), outs
        assert len(C._CMA_QUARANTINE) == before + 1
        assert C._CMA_QUARANTINE[-1].nbytes == n * 4

    def test_pull_failure_latches_cma_off(self, store, monkeypatch):
        """Round-4 advisor medium: the negotiation probes only the ring-left
        neighbor, but a passing vote arms pulls between ARBITRARY pairs. If
        a pull then fails at op time (pairwise-asymmetric process_vm_readv
        permission), the process must latch CMA off so the NEXT epoch's
        negotiation converges the whole group to TCP — not retry into the
        same failure every epoch."""
        monkeypatch.setenv("TORCHFT_CMA_P2P_MIN", str(64 * 1024))
        import torchft_tpu._native as N
        import torchft_tpu.collectives as C

        monkeypatch.setattr(C, "_CMA_BROKEN", False)

        def broken(pid, addr, view):
            raise OSError(1, "Operation not permitted")

        monkeypatch.setattr(N, "cma_read_into", broken)
        n = 1 << 18

        def fn(c, rank):
            assert c.plane_info() == "cma"  # probe (cma_read) still passes
            got_err = False
            if rank == 0:
                try:
                    c.send(np.ones(n, np.float32), dst=1, tag=9).wait(
                        timedelta(seconds=15)
                    )
                except Exception:  # noqa: BLE001
                    got_err = True
            else:
                buf = np.zeros(n, np.float32)
                try:
                    c.recv(buf, src=0, tag=9).wait(timedelta(seconds=15))
                except Exception:  # noqa: BLE001
                    got_err = True
            # next epoch: the latch must force the WHOLE group to TCP,
            # and ops must work there with process_vm_readv still broken
            c.configure(f"{store.address()}/cmalatch2", rank, 2)
            plane2 = c.plane_info()
            out = c.allreduce(
                [np.full(4, float(rank + 1), np.float32)], ReduceOp.SUM
            ).wait(timedelta(seconds=15))
            return got_err, plane2, float(out[0][0])

        outs = _run_world(
            store, 2, fn, prefix="cmalatch", timeout=timedelta(seconds=5)
        )
        assert C._CMA_BROKEN is True
        # the receiver's pull failed; the sender's ack never arrived
        assert outs[0][0] and outs[1][0], outs
        assert outs[0][1] == "tcp-striped" and outs[1][1] == "tcp-striped"
        assert outs[0][2] == 3.0 and outs[1][2] == 3.0


def _bits(a):
    return np.ascontiguousarray(a).view(np.uint32)


class TestAverageInRing:
    """The divisor travels with the allreduce. The native ring applies it
    where an element's final f32 value is written — the chunk owner's
    last reduce-scatter step on the exact planes, the decode of the
    owner's wire bytes on the lossy ones — and the result is bit for bit
    what a trailing ``np.divide(sum, n, out=sum)`` gave; everything else
    divides through ``average_in_place``."""

    # sizes that are no multiple of world x stripes x 16; 67 < stripes x 64
    # takes the one-stripe route
    SIZES = (100003, 67)

    @staticmethod
    def _sum_and_avg(world, divisor, zero_last=False):
        def fn(c, rank):
            rng = np.random.default_rng(11 + rank)
            out = []
            for size in TestAverageInRing.SIZES:
                a = (rng.standard_normal(size) * 3).astype(np.float32)
                if zero_last and rank == world - 1:
                    a[...] = 0  # a healing group's contribution
                t = timedelta(seconds=20)
                summed = c.allreduce([a.copy()], ReduceOp.SUM).wait(t)[0]
                before = c.avg_in_ring_ops()
                avg = c.allreduce([a.copy()], ReduceOp.SUM, divisor).wait(t)[0]
                out.append((summed, avg, c.avg_in_ring_ops() - before))
            return c.plane_info(), out

        return fn

    @pytest.mark.parametrize("cma", ["1", "0"])
    @pytest.mark.parametrize(
        "world,divisor", [(2, 1), (2, 2), (3, 3), (3, 2), (4, 4), (4, 3), (4, 1)]
    )
    def test_native_f32_equals_np_divide_bit_for_bit(
        self, store, monkeypatch, cma, world, divisor
    ):
        monkeypatch.setenv("TORCHFT_DP_CMA", cma)
        outs = _run_world(
            store, world,
            self._sum_and_avg(world, divisor, zero_last=divisor < world),
            prefix=f"air{cma}{world}{divisor}",
        )
        assert {p for p, _ in outs} == {"cma" if cma == "1" else "tcp-striped"}
        for _, per_size in outs:
            for k, (summed, avg, in_ring) in enumerate(per_size):
                expect = summed.copy()
                np.divide(expect, divisor, out=expect)
                np.testing.assert_array_equal(_bits(avg), _bits(expect))
                # every rank holds identical bits
                np.testing.assert_array_equal(_bits(avg), _bits(outs[0][1][k][1]))
                assert in_ring == (1 if divisor > 1 else 0)

    @pytest.mark.parametrize("codec", ["bfloat16", "int8"])
    @pytest.mark.parametrize("world,divisor", [(2, 2), (3, 3), (4, 3)])
    def test_lossy_codec_equals_encode_decode_divide_bit_for_bit(
        self, store, monkeypatch, codec, world, divisor
    ):
        monkeypatch.setenv("TORCHFT_DP_CMA", "0")  # cma bypasses the codec
        outs = _run_world(
            store, world, self._sum_and_avg(world, divisor),
            prefix=f"airc{codec}{world}", wire_dtype=codec,
        )
        assert {p for p, _ in outs} == {"tcp-striped"}
        for _, per_size in outs:
            for k, (summed, avg, in_ring) in enumerate(per_size):
                expect = summed.copy()  # the decoded sum, as today
                np.divide(expect, divisor, out=expect)
                np.testing.assert_array_equal(_bits(avg), _bits(expect))
                np.testing.assert_array_equal(_bits(avg), _bits(outs[0][1][k][1]))
                assert in_ring == 1

    @pytest.mark.parametrize("cma", ["1", "0"])
    @pytest.mark.parametrize("world", [2, 3])
    def test_avg_is_sum_over_world(self, store, monkeypatch, cma, world):
        monkeypatch.setenv("TORCHFT_DP_CMA", cma)

        def fn(c, rank):
            a = (np.random.default_rng(3 + rank).standard_normal(4099) * 7).astype(
                np.float32
            )
            t = timedelta(seconds=20)
            summed = c.allreduce([a.copy()], ReduceOp.SUM).wait(t)[0]
            return summed, c.allreduce([a.copy()], ReduceOp.AVG).wait(t)[0]

        for summed, avg in _run_world(store, world, fn, prefix=f"avg{cma}{world}"):
            np.divide(summed, world, out=summed)  # a division, not x * (1/3)
            np.testing.assert_array_equal(_bits(avg), _bits(summed))

    def test_a_divisor_goes_with_sum_only(self, store):
        def fn(c, rank):
            a = np.ones(4, np.float32)
            with pytest.raises(ValueError, match="SUM only"):
                c.allreduce([a], ReduceOp.MAX, 2)
            with pytest.raises(ValueError, match="SUM only"):
                c.allreduce([a], ReduceOp.AVG, 2)
            with pytest.raises(ValueError, match="SUM only"):
                c.allreduce([a], ReduceOp.SUM, 0)
            return True

        assert all(_run_world(store, 2, fn, prefix="sumonly"))

    @pytest.mark.parametrize(
        "case", ["python-ring", "f64-beside-f32", "python-codec", "world-1"]
    )
    def test_what_cannot_fuse_goes_through_the_one_helper(
        self, store, monkeypatch, case
    ):
        from torchft_tpu import collectives

        seen = []
        real = collectives.average_in_place

        def spy(arrays, divisor):
            if divisor != 1 and arrays:
                seen.append((len(arrays), divisor))
            return real(arrays, divisor)

        monkeypatch.setattr(collectives, "average_in_place", spy)
        kwargs = {"python-ring": {"native_plane": False},
                  "python-codec": {"native_plane": False, "wire_dtype": "bfloat16"}}
        world = 1 if case == "world-1" else 2

        def fn(c, rank):
            arrays = [np.full(4099, rank + 1.0, np.float32)]
            if case == "f64-beside-f32":
                arrays.append(np.full(515, rank + 1.0, np.float64))
            out = c.allreduce(arrays, ReduceOp.SUM, 2).wait(timedelta(seconds=20))
            return [a.copy() for a in out], c.avg_in_ring_ops()

        outs = _run_world(store, world, fn, prefix=f"fb{case}", **kwargs.get(case, {}))
        total = sum(range(1, world + 1)) / 2
        for arrays, in_ring in outs:
            for a in arrays:
                np.testing.assert_array_equal(a, np.full(a.shape, total, a.dtype))
            assert in_ring == 0  # an op counts only if EVERY array fused
        # one pass per op and rank, over what the ring did not divide
        assert seen == [(1, 2)] * world

    def test_dummy_divides_through_the_helper(self, monkeypatch):
        from torchft_tpu import collectives

        seen = []
        real = collectives.average_in_place
        monkeypatch.setattr(
            collectives, "average_in_place",
            lambda arrays, d: (seen.append(d), real(arrays, d))[1],
        )
        c = CollectivesDummy(rank=0, world_size=1)
        a = np.array([3.0, 6.0], np.float32)
        assert c.allreduce([a], ReduceOp.SUM, 3).wait()[0] is a
        np.testing.assert_array_equal(a, [1.0, 2.0])
        assert seen == [3] and c.avg_in_ring_ops() == 0

    def test_error_swallowing_forwards_divisor_and_count(self, store):
        inner = CollectivesTcp(timeout=timedelta(seconds=5), hostname="localhost")
        wrap = ErrorSwallowingCollectives(inner)
        wrap.configure(f"{store.address()}/eswd", 0, 1)
        a = np.array([2.0, 4.0], np.float32)
        np.testing.assert_array_equal(wrap.allreduce([a], ReduceOp.SUM, 2).wait()[0], [1.0, 2.0])
        inner._avg_in_ring_ops = 7
        assert wrap.avg_in_ring_ops() == 7
        wrap.shutdown()
