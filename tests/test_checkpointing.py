"""Checkpoint transport tests.

Ports the reference's transport coverage (http_transport_test.py,
pg_transport_test.py, rwlock_test.py, transport_test.py shared harness) to
JAX pytree state dicts.
"""

import io
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from datetime import timedelta

import numpy as np
import pytest

from torchft_tpu.checkpointing import (
    CollectivesTransport,
    HTTPTransport,
    RWLock,
)
from torchft_tpu.checkpointing.serialization import (
    dumps_state,
    flatten_state,
    loads_state,
    unflatten_state,
)
from torchft_tpu.collectives import CollectivesTcp
from torchft_tpu.store import StoreServer


def assert_state_equal(a, b):
    import jax

    la, ta = jax.tree_util.tree_flatten(a)
    lb, tb = jax.tree_util.tree_flatten(b)
    assert ta == tb
    for x, y in zip(la, lb):
        if isinstance(x, (np.ndarray,)) or hasattr(x, "dtype"):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
        else:
            assert x == y


STATE = {
    "model": {
        "w": np.arange(12, dtype=np.float32).reshape(3, 4),
        "b": np.ones(4, dtype=np.bfloat16)
        if hasattr(np, "bfloat16")
        else np.ones(4, dtype=np.float16),
    },
    "opt": {"lr": 0.1, "mu": np.zeros((2, 2), dtype=np.float64)},
    "meta": ("strings", 7, None),
}


class TestSerialization:
    def test_roundtrip(self):
        out = loads_state(dumps_state(STATE))
        assert_state_equal(STATE, out)

    def test_jax_arrays(self):
        import jax.numpy as jnp

        state = {"x": jnp.arange(8, dtype=jnp.bfloat16), "y": jnp.float32(3.5)}
        out = loads_state(dumps_state(state))
        np.testing.assert_array_equal(
            np.asarray(state["x"]), np.asarray(out["x"])
        )

    def test_flatten_unflatten(self):
        header, bufs = flatten_state(STATE)
        raw = [np.frombuffer(memoryview(b).cast("B"), dtype=np.uint8) for b in bufs]
        assert_state_equal(STATE, unflatten_state(header, raw))

    def test_to_host_tree_copy_never_aliases(self):
        from torchft_tpu.checkpointing.serialization import to_host_tree

        params = {"w": np.arange(6, dtype=np.float32)}
        backup = to_host_tree(params, copy=True)
        assert not np.shares_memory(backup["w"], params["w"])
        params["w"][...] = -1  # in-place inner update
        np.testing.assert_array_equal(
            backup["w"], np.arange(6, dtype=np.float32)
        )
        # without copy, a contiguous numpy leaf passes through unchanged
        assert to_host_tree(params)["w"] is params["w"]


def _landing_state(kind: str):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from torchft_tpu.parallel.mesh import MeshConfig, make_mesh

    if kind == "dense":
        return {
            "w": jnp.arange(24, dtype=jnp.float32).reshape(4, 6),
            "h": jnp.ones((3, 5), dtype=jnp.bfloat16),
            "count": jnp.int32(7),
            "empty": jnp.zeros((0, 4), dtype=jnp.float32),
            "host": np.arange(5, dtype=np.int64),
            "obj": ("note", 3),
        }
    mesh = make_mesh(MeshConfig(tp=2), devices=jax.devices()[:2])
    split = jax.device_put(
        jnp.arange(16, dtype=jnp.float32).reshape(4, 4),
        NamedSharding(mesh, P(None, "tp")),
    )
    whole = jax.device_put(
        jnp.arange(6, dtype=jnp.float32), NamedSharding(mesh, P())
    )
    return {"split": split, "whole": whole, "lr": 0.1}


class TestLanding:
    """``Flattening``: a bounded number of copies off the device is in
    flight beyond the one being read, and what lands is what
    ``flatten_state`` returned before it existed."""

    @pytest.mark.parametrize("kind", ["dense", "sharded"])
    def test_copies_are_issued_a_few_ahead_of_the_read(self, kind, monkeypatch):
        import jax

        from torchft_tpu.checkpointing import serialization as ser

        events = []
        impl = type(jax.numpy.zeros(1))
        real_issue, real_to_host = impl.copy_to_host_async, ser._to_host

        def issue(self):
            events.append("issue")
            return real_issue(self)

        def to_host(leaf, copy=False):
            events.append("read")
            return real_to_host(leaf, copy=copy)

        monkeypatch.setattr(impl, "copy_to_host_async", issue)
        monkeypatch.setattr(ser, "_to_host", to_host)
        assert ser._COPIES_AHEAD == 2
        flat = ser.Flattening(_landing_state(kind))
        assert events == []  # nothing moves before the stream is read
        bufs = list(flat.buffers())
        want = {
            # count, empty, h | host (no copy to issue), w
            "dense": ["issue"] * 3 + ["read", "read", "issue", "read", "read", "read"],
            # two distinct shards of the split leaf, the replicated leaf once
            "sharded": ["issue"] * 3 + ["read"] * 3,
        }[kind]
        assert events == want
        assert flat.nbuffers == len(bufs) == events.count("read")
        assert flat.nbytes == sum(int(b.nbytes) for b in bufs)
        assert all(a is b for a, b in zip(flat.landed, bufs))

    @pytest.mark.parametrize("kind", ["dense", "sharded"])
    def test_header_is_complete_once_everything_landed(self, kind):
        from torchft_tpu.checkpointing.serialization import (
            Flattening,
            buffer_sizes,
        )
        import pickle

        state = _landing_state(kind)
        flat = Flattening(state)
        landing = flat.buffers()
        first = next(landing)
        with pytest.raises(AssertionError, match="exhausted"):
            flat.header
        bufs = [first] + list(landing)
        header, again = flatten_state(state)
        assert flat.header == header
        assert [b.tobytes() for b in bufs] == [b.tobytes() for b in again]
        _, infos = pickle.loads(header)
        assert buffer_sizes(infos) == [int(b.nbytes) for b in bufs]
        if kind == "dense":
            # a 0-d device leaf travels as one element, as it always has
            by_shape = {i[2]: i for i in infos if i[0] == "arr"}
            assert (1,) in by_shape and by_shape[(1,)][1] == "int32"


class TestRWLock:
    def test_readers_shared_writer_exclusive(self):
        lock = RWLock(timeout=1.0)
        lock.r_acquire()
        lock.r_acquire()  # second reader ok
        with pytest.raises(TimeoutError):
            lock.w_acquire()
        lock.r_release()
        lock.r_release()
        with lock.write_lock():
            with pytest.raises(TimeoutError):
                lock.r_acquire()
        lock.r_acquire()
        lock.r_release()

    def test_pending_writer_blocks_new_readers(self):
        lock = RWLock(timeout=5.0)
        lock.r_acquire()
        t = threading.Thread(target=lock.w_acquire)  # parks behind the reader
        t.start()
        time.sleep(0.1)
        # a new reader must queue behind the pending writer, not starve it
        got_read = threading.Event()

        def late_reader():
            lock.r_acquire()
            got_read.set()
            lock.r_release()

        r = threading.Thread(target=late_reader)
        r.start()
        assert not got_read.wait(0.3)
        lock.r_release()  # writer wins first...
        t.join(timeout=5)
        assert lock.w_locked()
        lock.w_release()  # ...then the late reader proceeds
        assert got_read.wait(5)
        r.join(timeout=5)

    def test_writer_timeout_wakes_blocked_readers(self):
        # a writer that times out must notify readers parked on
        # `_want_write == 0`, or they stall until their own timeout
        lock = RWLock(timeout=0.3)
        lock.r_acquire()  # keeps the writer from ever acquiring
        got_read = threading.Event()

        def late_reader():
            lock.r_acquire()
            got_read.set()
            lock.r_release()

        writer_done = threading.Event()

        def failing_writer():
            with pytest.raises(TimeoutError):
                lock.w_acquire()
            writer_done.set()

        w = threading.Thread(target=failing_writer)
        w.start()
        time.sleep(0.05)  # writer is pending; reader queues behind it
        r = threading.Thread(target=late_reader)
        r.start()
        assert writer_done.wait(2)
        # reader must wake promptly after the writer's timeout, well before
        # its own 0.3s deadline from this instant
        assert got_read.wait(0.2)
        w.join(timeout=2)
        r.join(timeout=2)
        lock.r_release()


@pytest.mark.parametrize("num_chunks", [0, 3])
def test_http_transport_roundtrip(num_chunks):
    send = HTTPTransport(timeout=timedelta(seconds=10), num_chunks=num_chunks)
    recv = HTTPTransport(timeout=timedelta(seconds=10), num_chunks=num_chunks)
    try:
        send.send_checkpoint([1], step=5, state_dict=STATE, timeout=timedelta(seconds=10))
        out = recv.recv_checkpoint(
            src_rank=0, metadata=send.metadata(), step=5, timeout=timedelta(seconds=10)
        )
        assert_state_equal(STATE, out)
        # wrong step is rejected
        with pytest.raises(Exception):
            recv.recv_checkpoint(
                src_rank=0,
                metadata=send.metadata(),
                step=99,
                timeout=timedelta(seconds=5),
            )
    finally:
        send.shutdown()
        recv.shutdown()


def test_http_transport_blocks_until_staged():
    send = HTTPTransport(timeout=timedelta(seconds=10))
    recv = HTTPTransport(timeout=timedelta(seconds=10))
    try:
        results = {}

        def fetch():
            results["state"] = recv.recv_checkpoint(
                src_rank=0,
                metadata=send.metadata(),
                step=1,
                timeout=timedelta(seconds=10),
            )

        t = threading.Thread(target=fetch)
        t.start()
        time.sleep(0.3)
        assert "state" not in results  # GET is parked on the write lock
        send.send_checkpoint([1], step=1, state_dict=STATE, timeout=timedelta(seconds=10))
        t.join(timeout=10)
        assert_state_equal(STATE, results["state"])

        # after disallow, subsequent fetches park until the next staging
        send.disallow_checkpoint()
        with pytest.raises(Exception):
            recv2 = HTTPTransport(timeout=timedelta(milliseconds=300))
            try:
                recv2.recv_checkpoint(
                    src_rank=0,
                    metadata=send.metadata(),
                    step=1,
                    timeout=timedelta(milliseconds=500),
                )
            finally:
                recv2.shutdown()
    finally:
        send.shutdown()
        recv.shutdown()


def test_collectives_transport_roundtrip():
    store = StoreServer()
    try:
        colls = [CollectivesTcp(timeout=timedelta(seconds=10)) for _ in range(2)]
        with ThreadPoolExecutor(max_workers=2) as pool:
            list(
                pool.map(
                    lambda i: colls[i].configure(store.address(), i, 2), range(2)
                )
            )
        transports = [
            CollectivesTransport(c, timeout=timedelta(seconds=10)) for c in colls
        ]

        def send():
            transports[0].send_checkpoint(
                [1], step=3, state_dict=STATE, timeout=timedelta(seconds=10)
            )

        def recv():
            return transports[1].recv_checkpoint(
                src_rank=0, metadata="<collectives>", step=3, timeout=timedelta(seconds=10)
            )

        with ThreadPoolExecutor(max_workers=2) as pool:
            fs = pool.submit(send)
            fr = pool.submit(recv)
            fs.result(timeout=20)
            out = fr.result(timeout=20)
        assert_state_equal(STATE, out)
        for c in colls:
            c.shutdown()
    finally:
        store.shutdown()


def test_collectives_transport_parallel_fanout_windowed():
    """Round-3: ≤3 in-flight buffers per destination, destinations in
    parallel (the reference's pg_transport.py:171-198 pipeline). A
    many-buffer state dict to TWO healing replicas at once must land
    intact on both."""
    store = StoreServer()
    state = {f"leaf{i}": np.full(4096, float(i), dtype=np.float32) for i in range(24)}
    try:
        colls = [CollectivesTcp(timeout=timedelta(seconds=20)) for _ in range(3)]
        with ThreadPoolExecutor(max_workers=3) as pool:
            list(
                pool.map(
                    lambda i: colls[i].configure(store.address(), i, 3), range(3)
                )
            )
        transports = [
            CollectivesTransport(c, timeout=timedelta(seconds=20)) for c in colls
        ]

        with ThreadPoolExecutor(max_workers=3) as pool:
            fs = pool.submit(
                transports[0].send_checkpoint,
                [1, 2],
                5,
                state,
                timedelta(seconds=20),
            )
            frs = [
                pool.submit(
                    transports[r].recv_checkpoint,
                    0,
                    "<collectives>",
                    5,
                    timedelta(seconds=20),
                )
                for r in (1, 2)
            ]
            fs.result(timeout=30)
            outs = [fr.result(timeout=30) for fr in frs]
        for out in outs:
            assert_state_equal(state, out)
        for c in colls:
            c.shutdown()
    finally:
        store.shutdown()


# ---------------------------------------------------------------------------
# DiskCheckpointer (periodic user-owned checkpoints; reference workflow
# train_ddp.py:141-148 + manager.py:83-85 docs)
# ---------------------------------------------------------------------------


class _ManagerStub:
    def __init__(self) -> None:
        self.step = 0
        self.batches = 0

    def current_step(self) -> int:
        return self.step

    def state_dict(self):
        return {"step": self.step, "batches_committed": self.batches}

    def load_state_dict(self, s) -> None:
        self.step = s["step"]
        self.batches = s["batches_committed"]


def test_disk_checkpointer_cadence_retention_restore(tmp_path):
    from torchft_tpu.checkpointing.disk import DiskCheckpointer

    mgr = _ManagerStub()
    state = {"w": np.arange(4, dtype=np.float32)}
    ck = DiskCheckpointer(
        str(tmp_path),
        mgr,
        state_dict=lambda: dict(state),
        load_state_dict=lambda s: state.update(s),
        every=2,
        keep=2,
        tag="g0",
    )
    saved = []
    for step in range(1, 9):
        mgr.step = step
        mgr.batches = step * 2
        state["w"] = state["w"] + 1.0
        if ck.maybe_save():
            saved.append(step)
    assert saved == [2, 4, 6, 8]  # cadence honored, no re-save on stall
    mgr.step = 8
    assert ck.maybe_save() is None  # no progress since last save
    names = sorted(p.name for p in tmp_path.glob("g0_step*.ckpt"))
    assert names == ["g0_step6.ckpt", "g0_step8.ckpt"]  # keep=2 pruned

    # total failure: fresh process state, restore latest
    mgr2 = _ManagerStub()
    state2 = {}
    ck2 = DiskCheckpointer(
        str(tmp_path),
        mgr2,
        state_dict=lambda: dict(state2),
        load_state_dict=lambda s: state2.update(s),
        every=2,
        tag="g0",
    )
    assert ck2.restore() is True
    assert mgr2.step == 8 and mgr2.batches == 16
    np.testing.assert_array_equal(state2["w"], np.arange(4, dtype=np.float32) + 8)


def test_disk_checkpointer_non_writer_and_empty(tmp_path):
    from torchft_tpu.checkpointing.disk import DiskCheckpointer

    mgr = _ManagerStub()
    ck = DiskCheckpointer(
        str(tmp_path),
        mgr,
        state_dict=dict,
        load_state_dict=lambda s: None,
        tag="g1",
        is_writer=False,
    )
    mgr.step = 5
    assert ck.maybe_save() is None  # readers never write
    assert ck.restore() is False  # nothing to restore


def test_disk_checkpointer_sharded_leaves(tmp_path):
    """A sharded param tree round-trips per shard: the restored leaves are
    ShardedArray placeholders rebuilt on the local mesh."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from torchft_tpu.checkpointing.disk import DiskCheckpointer
    from torchft_tpu.checkpointing.serialization import from_transfer_tree
    from torchft_tpu.parallel.mesh import MeshConfig, make_mesh

    mesh = make_mesh(MeshConfig(tp=2), devices=jax.devices()[:2])
    w = jax.device_put(
        jnp.arange(16, dtype=jnp.float32).reshape(4, 4),
        NamedSharding(mesh, P(None, "tp")),
    )
    mgr = _ManagerStub()
    holder = {"w": w}
    ck = DiskCheckpointer(
        str(tmp_path),
        mgr,
        state_dict=lambda: dict(holder),
        load_state_dict=lambda s: holder.update(
            from_transfer_tree(s, mesh)
        ),
        every=1,
        tag="g0",
    )
    mgr.step = 1
    assert ck.maybe_save()
    holder.clear()
    assert ck.restore()
    np.testing.assert_array_equal(np.asarray(holder["w"]), np.asarray(w))
    assert holder["w"].sharding.spec == P(None, "tp")


def test_disk_checkpointer_async_save_tear_free(tmp_path):
    """async_save: the snapshot is captured at maybe_save() time — numpy
    leaves mutated immediately afterward must not leak into the file."""
    import os

    from torchft_tpu.checkpointing.disk import DiskCheckpointer

    mgr = _ManagerStub()
    state = {"w": np.full(1 << 16, 1.0, dtype=np.float32)}
    ck = DiskCheckpointer(
        str(tmp_path),
        mgr,
        state_dict=lambda: dict(state),
        load_state_dict=lambda s: state.update(s),
        every=1,
        tag="g0",
        async_save=True,
    )
    mgr.step = 1
    path = ck.maybe_save()
    assert path is not None
    state["w"][...] = 999.0  # in-place mutation racing the writer
    ck.flush()
    assert os.path.exists(path)

    mgr2 = _ManagerStub()
    got = {}
    ck2 = DiskCheckpointer(
        str(tmp_path),
        mgr2,
        state_dict=dict,
        load_state_dict=lambda s: got.update(s),
        tag="g0",
    )
    assert ck2.restore()
    np.testing.assert_array_equal(got["w"], 1.0)  # snapshot-time value
    assert mgr2.step == 1


def test_disk_checkpointer_per_process_merge(tmp_path):
    """Multi-host sharded checkpoints (round-2 advisor finding): one writer
    per group cannot serialize a cross-process-sharded leaf, so every
    process writes a ``procIofN`` shard file and restore() merges the set.
    Two simulated hosts each hold half the shards of an ('x',)-sharded
    (8,4) leaf; restore must pool them so the full array is recoverable."""
    from torchft_tpu.checkpointing.disk import DiskCheckpointer, _NAME
    from torchft_tpu.checkpointing.serialization import ShardedArray, save_state

    full = np.arange(32, dtype=np.float32).reshape(8, 4)
    mesh_desc = (("x",), (4,))
    spec = ("x",)

    def half(lo_rows):
        shards = [
            (((r, r + 2), (0, 4)), full[r : r + 2]) for r in lo_rows
        ]
        return ShardedArray(np.dtype(np.float32), (8, 4), mesh_desc, spec, shards)

    # hand-write the two per-process files (the write path on a real
    # multi-host deployment produces exactly this layout via _target_path)
    for pidx, rows in ((0, (0, 2)), (1, (4, 6))):
        torchft = {"step": 5, "batches_committed": 10}
        path = tmp_path / f"g0_step5.proc{pidx}of2.ckpt"
        with open(path, "wb") as f:
            save_state({"torchft": torchft, "user": {"w": half(rows)}}, f)
        assert _NAME.match(path.name)

    mgr = _ManagerStub()
    got = {}
    ck = DiskCheckpointer(
        str(tmp_path),
        mgr,
        state_dict=dict,
        load_state_dict=lambda s: got.update(s),
        tag="g0",
    )
    assert ck.restore() is True
    assert mgr.step == 5
    merged = got["w"]
    assert isinstance(merged, ShardedArray)
    assert len(merged.shards) == 4  # both halves pooled
    np.testing.assert_array_equal(merged.full(), full)


def test_disk_checkpointer_incomplete_proc_set_not_restorable(tmp_path):
    """A per-process set missing a writer (host died mid-save) must not be
    offered as restorable — restore falls back to an older complete step."""
    from torchft_tpu.checkpointing.disk import DiskCheckpointer
    from torchft_tpu.checkpointing.serialization import save_state

    # complete dense checkpoint at step 3
    with open(tmp_path / "g0_step3.ckpt", "wb") as f:
        save_state(
            {
                "torchft": {"step": 3, "batches_committed": 6},
                "user": {"w": np.ones(2, np.float32)},
            },
            f,
        )
    # step 5: only proc0of2 present — incomplete
    with open(tmp_path / "g0_step5.proc0of2.ckpt", "wb") as f:
        save_state(
            {
                "torchft": {"step": 5, "batches_committed": 10},
                "user": {"w": np.zeros(2, np.float32)},
            },
            f,
        )
    mgr = _ManagerStub()
    got = {}
    ck = DiskCheckpointer(
        str(tmp_path),
        mgr,
        state_dict=dict,
        load_state_dict=lambda s: got.update(s),
        tag="g0",
    )
    assert ck.restore() is True
    assert mgr.step == 3  # fell back to the complete step
    np.testing.assert_array_equal(got["w"], 1.0)


def test_disk_checkpointer_needs_per_process_detection():
    """Single-process (even with an 8-device mesh) state is fully
    addressable — the dense single-writer layout stays in effect."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from torchft_tpu.checkpointing.disk import _needs_per_process

    devs = jax.devices("cpu")[:4]
    mesh = Mesh(np.array(devs), ("x",))
    arr = jax.device_put(
        jnp.arange(8, dtype=jnp.float32), NamedSharding(mesh, P("x"))
    )
    assert arr.is_fully_addressable
    assert _needs_per_process({"w": arr}) is False
    assert _needs_per_process({"w": np.ones(3)}) is False


def test_disk_dense_vs_proc_set_same_step_prefers_newer(tmp_path):
    """Elastic resize can leave BOTH a dense file and a complete procIofN
    set at the same step; restore must take the newer write, never merge
    the stale one (round-3 review finding on _existing())."""
    import os

    from torchft_tpu.checkpointing.disk import DiskCheckpointer
    from torchft_tpu.checkpointing.serialization import save_state

    mgr = _ManagerStub()
    mgr.step = 5
    state = {"w": np.zeros(4, dtype=np.float32)}
    ck = DiskCheckpointer(
        str(tmp_path),
        mgr,
        state_dict=lambda: dict(state),
        load_state_dict=lambda s: state.update(s),
        tag="g0",
    )

    def write(path, w):
        with open(path, "wb") as f:
            save_state(
                {"torchft": mgr.state_dict(), "user": {"w": w}}, f
            )

    stale = np.full(4, 1.0, dtype=np.float32)
    fresh = np.full(4, 2.0, dtype=np.float32)

    # older: a complete 2-process set; newer: a dense re-save (shrink to 1)
    write(ck._proc_path(5, 0, 2), stale)
    write(ck._proc_path(5, 1, 2), stale)
    stale_mtime = os.path.getmtime(ck._proc_path(5, 0, 2))
    write(ck._path(5), fresh)
    # explicit times: guarantees strictly-newer even on coarse-granularity
    # filesystems where sleep+now would truncate to the same second
    os.utime(ck._path(5), (stale_mtime + 2, stale_mtime + 2))

    assert ck.latest() == ck._path(5)
    assert ck.restore()
    np.testing.assert_array_equal(state["w"], fresh)

    # the reverse: dense older, proc set newer -> proc set wins
    for p in [ck._path(5), ck._proc_path(5, 0, 2), ck._proc_path(5, 1, 2)]:
        os.remove(p)
    write(ck._path(5), stale)
    stale_mtime = os.path.getmtime(ck._path(5))
    write(ck._proc_path(5, 0, 1), fresh)  # 1-process "set"
    os.utime(ck._proc_path(5, 0, 1), (stale_mtime + 2, stale_mtime + 2))
    assert ck.latest() == ck._proc_path(5, 0, 1)


def test_disk_write_generation_beats_mtime(tmp_path):
    """Deterministic dense-vs-procset arbitration (round-3 advisor low):
    a later incarnation's write wins via its higher write generation even
    when filesystem mtimes tie or INVERT (1 s granularity, clock skew)."""
    import os

    from torchft_tpu.checkpointing.disk import DiskCheckpointer
    from torchft_tpu.checkpointing.serialization import save_state

    mgr = _ManagerStub()
    mgr.step = 5

    def write(path, w):
        with open(path, "wb") as f:
            save_state({"torchft": mgr.state_dict(), "user": {"w": w}}, f)

    stale = np.full(4, 1.0, dtype=np.float32)
    fresh = np.full(4, 2.0, dtype=np.float32)

    # incarnation 1 (fresh dir -> gen 0, legacy names): 2-process set
    ck1 = DiskCheckpointer(
        str(tmp_path), mgr, state_dict=dict, load_state_dict=lambda s: None, tag="g0"
    )
    assert ck1._gen == 0
    write(ck1._proc_path(5, 0, 2), stale)
    write(ck1._proc_path(5, 1, 2), stale)

    # incarnation 2 (resized to 1 process): scans -> gen 1
    state2 = {}
    ck2 = DiskCheckpointer(
        str(tmp_path),
        mgr,
        state_dict=dict,
        load_state_dict=lambda s: state2.update(s),
        tag="g0",
    )
    assert ck2._gen == 1
    write(ck2._path(5), fresh)
    # adversarial: make the NEWER write look mtime-OLDER; gen must win
    old = os.path.getmtime(ck1._proc_path(5, 0, 2)) - 10
    os.utime(ck2._path(5), (old, old))
    assert ck2.latest() == ck2._path(5)
    assert ck2.restore()
    np.testing.assert_array_equal(state2["w"], fresh)

    # a third incarnation keeps climbing
    ck3 = DiskCheckpointer(
        str(tmp_path), mgr, state_dict=dict, load_state_dict=lambda s: None, tag="g0"
    )
    assert ck3._gen == 2


def test_disk_prune_removes_superseded_generations(tmp_path):
    """A crash-restart loop re-saving around the same step must not leak
    one full checkpoint per incarnation: _prune deletes same-step files of
    strictly lower generation than the arbitration winner."""
    from torchft_tpu.checkpointing.disk import DiskCheckpointer

    state = {"w": np.zeros(2, dtype=np.float32)}
    names = lambda: sorted(  # noqa: E731
        p.name for p in tmp_path.iterdir() if p.suffix == ".ckpt"
    )
    for incarnation in range(3):
        mgr = _ManagerStub()
        ck = DiskCheckpointer(
            str(tmp_path),
            mgr,
            state_dict=lambda: dict(state),
            load_state_dict=lambda s: state.update(s),
            every=1,
            keep=3,
            tag="g0",
        )
        assert ck._gen == incarnation
        ck.restore()
        mgr.step = 5  # dies near the same step every time
        ck.save()
    # only the newest generation's file survives at step 5
    assert names() == ["g0_step5.g2.ckpt"]
