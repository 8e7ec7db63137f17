"""DDP bucketing / pipeline unit tests (torchft/ddp.py:32-71 analogue;
the per-bucket schedule is what the round-3 host pipeline rides)."""

import numpy as np
import pytest

from torchft_tpu.ddp import flatten_buckets, plan_buckets, unflatten_buckets


def test_plan_respects_bucket_bytes_and_dtype():
    meta = [
        (np.dtype(np.float32), 60),
        (np.dtype(np.float32), 60),   # fits with first under 128
        (np.dtype(np.float32), 60),   # overflows -> new bucket
        (np.dtype(np.float16), 10),   # dtype change -> new bucket
        (np.dtype(np.float16), 10),
    ]
    plan = plan_buckets(meta, bucket_bytes=128)
    assert plan == [[0, 1], [2], [3, 4]]


def test_plan_empty_and_oversized():
    assert plan_buckets([], bucket_bytes=128) == []
    # a single leaf larger than the bucket still gets its own bucket
    assert plan_buckets([(np.dtype(np.float32), 10**9)], 128) == [[0]]


def test_flatten_unflatten_roundtrip():
    rng = np.random.default_rng(0)
    leaves = [
        rng.standard_normal(13).astype(np.float32),
        rng.standard_normal((3, 5)).astype(np.float32),
        rng.standard_normal(7).astype(np.float16),
        np.float32(rng.standard_normal()).reshape(()),  # scalar leaf
    ]
    buckets = flatten_buckets(leaves, bucket_bytes=64)
    # every element lands in exactly one bucket
    total = sum(buf.size for buf, _ in buckets)
    assert total == sum(l.size for l in leaves)
    out = unflatten_buckets(buckets, leaves)
    for a, b in zip(leaves, out):
        np.testing.assert_array_equal(a, np.asarray(b))
        assert b.shape == a.shape and b.dtype == a.dtype


def test_pipeline_issues_one_managed_op_per_bucket():
    """The host path must submit buckets as separate managed ops (that is
    the pipelining) and reassemble exact averages."""
    import jax.numpy as jnp

    from torchft_tpu.ddp import allreduce_gradients
    from torchft_tpu.futures import Future

    calls = []

    class ManagerStub:
        def device_data_plane(self):
            return False

        def allreduce_many(self, tensors):
            calls.append([t.copy() for t in tensors])
            for t in tensors:
                np.divide(t, 1.0, out=t)  # identity "average", world 1
            return Future.completed(tensors)

    grads = {f"g{i}": jnp.full((16,), float(i)) for i in range(5)}
    out = allreduce_gradients(ManagerStub(), grads, bucket_bytes=64)
    assert len(calls) == 5  # one op per bucket at 64B buckets
    for i in range(5):
        np.testing.assert_allclose(np.asarray(out[f"g{i}"]), float(i))


# -- bucket buffers kept between exchanges (ddp._KeptBuckets) -----------------


class RingStub:
    """A manager whose "ring" averages with one imaginary peer, in place, and
    hands back the very list it was given — as the real ring does. It records
    the buffers it saw; ``err`` is what ``errored()`` returns."""

    def __init__(self):
        self.seen = []
        self.err = None

    def device_data_plane(self):
        return False

    def errored(self):
        return self.err

    def ring(self, buf):
        if buf.dtype.kind == "f":
            peer = np.arange(buf.size, dtype=np.float64).astype(buf.dtype)
            buf += peer
            np.divide(buf, 2, out=buf)

    def allreduce_many(self, tensors):
        from torchft_tpu.futures import Future

        self.seen.append(tensors[0])
        self.ring(tensors[0])
        return Future.completed(tensors)


def exchange(manager, tree, **kwargs):
    """(averaged tree, the ``exchange`` span's attributes)."""
    from torchft_tpu.ddp import allreduce_gradients
    from torchft_tpu.telemetry import tracing

    out = allreduce_gradients(manager, tree, **kwargs)
    return out, tracing.TRACER.recent("exchange")[-1]["attrs"]


def tree_of(value, kind="jnp", n=5):
    import jax.numpy as jnp

    make = {"jnp": jnp.full, "numpy": np.full}[kind]
    return {f"g{i}": make((16,), np.float32(value + i)) for i in range(n)}


@pytest.fixture
def off_cpu(monkeypatch):
    """Pretend ``device_put`` copies, as it does on the chip: device leaves
    make their buckets keepable. (Here it may alias an aligned buffer, so these
    tests read an exchange's outputs before the next one.)"""
    import jax

    from torchft_tpu import ddp

    monkeypatch.setattr(ddp, "_put_copies", lambda src: isinstance(src, jax.Array))


def test_second_exchange_of_a_plan_packs_into_the_first_ones_buffers(off_cpu):
    m = RingStub()
    _, first = exchange(m, tree_of(1.0), bucket_bytes=128)
    assert (first["buckets"], first["buckets_reused"]) == (3, 0)
    out, second = exchange(m, tree_of(7.0), bucket_bytes=128)
    assert (second["buckets"], second["buckets_reused"]) == (3, 3)
    assert all(a is b for a, b in zip(m.seen[:3], m.seen[3:]))
    peer = np.arange(32, dtype=np.float32)  # two 16-float leaves to a bucket
    np.testing.assert_array_equal(np.asarray(out["g1"]), (8.0 + peer[16:]) / 2)
    np.testing.assert_array_equal(np.asarray(out["g4"]), (11.0 + peer[:16]) / 2)


@pytest.mark.parametrize("kind", ["jnp", "numpy"])
def test_what_an_exchange_returned_is_never_rewritten_by_the_next(kind):
    """Rule 3: a NumPy leaf goes out as a slice of its bucket, and on the CPU
    backend a device_put may alias it — such a bucket is not kept."""
    from torchft_tpu import ddp

    m = RingStub()
    first, _ = exchange(m, tree_of(1.0, kind), bucket_bytes=128)
    held = {k: np.array(v) for k, v in first.items()}
    _, attrs = exchange(m, tree_of(50.0, kind), bucket_bytes=128)
    assert attrs["buckets_reused"] == 0
    for k, v in first.items():
        np.testing.assert_array_equal(np.asarray(v), held[k])
    np.testing.assert_array_equal(held["g0"], (1.0 + np.arange(16)) / 2)
    assert m not in ddp._KEPT


def test_a_bucket_is_kept_only_if_every_piece_of_it_is_placed(off_cpu):
    from torchft_tpu import ddp

    m = RingStub()

    def tree(value):
        t = tree_of(value)  # g0 g1 | g2 g3 | g4
        t["g3"] = np.asarray(t["g3"])
        return t

    exchange(m, tree(1.0), bucket_bytes=128)
    first, attrs = exchange(m, tree(2.0), bucket_bytes=128)
    assert (attrs["buckets"], attrs["buckets_reused"]) == (3, 2)
    host_piece = np.array(first["g3"])
    exchange(m, tree(3.0), bucket_bytes=128)
    np.testing.assert_array_equal(first["g3"], host_piece)
    assert [b is None for b in ddp._KEPT[m].bufs] == [False, True, False]


@pytest.mark.parametrize("change", ["tree", "bucket_bytes", "dtype"])
def test_another_plan_replaces_the_kept_set(change, off_cpu):
    import jax.numpy as jnp

    m = RingStub()
    base = dict(bucket_bytes=128)
    exchange(m, tree_of(1.0), **base)
    assert exchange(m, tree_of(1.0), **base)[1]["buckets_reused"] == 3
    if change == "tree":
        other, kwargs = tree_of(1.0, n=4), base
    elif change == "bucket_bytes":
        other, kwargs = tree_of(1.0), dict(bucket_bytes=64)
    else:
        other = {k: v.astype(jnp.float16) for k, v in tree_of(1.0).items()}
        kwargs = dict(bucket_bytes=64)  # 16 halves: two leaves, as before
    _, attrs = exchange(m, other, **kwargs)
    assert attrs["buckets_reused"] == 0
    _, attrs = exchange(m, other, **kwargs)
    assert attrs["buckets_reused"] == attrs["buckets"]
    # one plan per manager: the first one's buffers are gone
    assert exchange(m, tree_of(1.0), **base)[1]["buckets_reused"] == 0


def test_two_managers_share_no_buffer(off_cpu):
    a, b = RingStub(), RingStub()
    exchange(a, tree_of(1.0), bucket_bytes=128)
    _, attrs = exchange(b, tree_of(1.0), bucket_bytes=128)
    assert attrs["buckets_reused"] == 0
    assert exchange(a, tree_of(2.0), bucket_bytes=128)[1]["buckets_reused"] == 3
    assert exchange(b, tree_of(2.0), bucket_bytes=128)[1]["buckets_reused"] == 3
    assert not any(np.shares_memory(x, y) for x in a.seen for y in b.seen)


def test_an_exchange_that_ends_errored_keeps_nothing(off_cpu):
    """Rule 2b, the latch: once ``errored()`` is set ``allreduce_many`` hands
    the buffers back untouched, and the op thread may still hold earlier ones."""
    from torchft_tpu.futures import Future

    class Latching(RingStub):
        fail_at = None

        def allreduce_many(self, tensors):
            if len(self.seen) == self.fail_at:
                self.err = RuntimeError("peer gone")
            if self.err is not None:
                self.seen.append(tensors[0])
                return Future.completed(tensors)
            return super().allreduce_many(tensors)

    m = Latching()
    exchange(m, tree_of(1.0), bucket_bytes=128)
    m.fail_at = 4  # the second bucket of the second exchange
    _, attrs = exchange(m, tree_of(2.0), bucket_bytes=128)
    assert attrs["buckets_reused"] == 3 and m.err is not None
    m.err, m.fail_at = None, None  # the next quorum clears the latch
    out, attrs = exchange(m, tree_of(3.0), bucket_bytes=128)
    assert attrs["buckets_reused"] == 0
    assert not any(np.shares_memory(x, y) for x in m.seen[:6] for y in m.seen[6:])
    np.testing.assert_array_equal(
        np.asarray(out["g0"]), (3.0 + np.arange(16, dtype=np.float32)) / 2
    )
    assert exchange(m, tree_of(4.0), bucket_bytes=128)[1]["buckets_reused"] == 3


def test_a_ring_still_writing_after_its_future_completed_cannot_reach_the_next_step(off_cpu):
    """Rule 2b, the timeout: ``wrap_future`` completes a timed-out op with the
    buffers it was given while the op thread is still inside the ring."""
    import threading

    from torchft_tpu.futures import Future

    class TimesOut(RingStub):
        stale = None
        release = threading.Event()

        def allreduce_many(self, tensors):
            if self.stale == "arm":
                buf = tensors[0]

                def late_ring():
                    self.release.wait(10)
                    buf[...] = -1e9

                self.stale = threading.Thread(target=late_ring)
                self.stale.start()
                self.err = TimeoutError("ring deadline")  # as wrap_future latches it
                return Future.completed(tensors)
            if self.err is not None:
                return Future.completed(tensors)
            if isinstance(self.stale, threading.Thread) and self.stale.is_alive():
                # the stale ring wakes up in the middle of the next step
                self.release.set()
                self.stale.join()
            return super().allreduce_many(tensors)

    m = TimesOut()
    exchange(m, tree_of(1.0), bucket_bytes=128)
    m.stale = "arm"
    exchange(m, tree_of(2.0), bucket_bytes=128)
    m.err = None
    out, attrs = exchange(m, tree_of(3.0), bucket_bytes=128)
    assert not m.stale.is_alive()
    assert attrs["buckets_reused"] == 0
    peer = np.arange(32, dtype=np.float32)
    for i in range(5):
        np.testing.assert_array_equal(
            np.asarray(out[f"g{i}"]), (3.0 + i + peer[16 * (i % 2):][:16]) / 2
        )


@pytest.mark.parametrize("given_away", [False, True], ids=["placed", "donated"])
def test_a_kept_set_waits_for_what_was_placed_from_it(given_away):
    """Rule 2a: the arrays still in flight when the last exchange returned are
    waited for; one that was deleted since cannot be, and the set goes."""
    import jax.numpy as jnp

    from torchft_tpu import ddp

    m, key = RingStub(), ((np.dtype(np.float32), 16),)
    kept = ddp._KeptBuckets(key)
    kept.bufs[0] = np.zeros(16, np.float32)
    kept.placed = [jnp.ones(16)]
    if given_away:
        kept.placed[0].delete()
    ddp._KEPT[m] = kept
    taken = ddp._take_kept(m, key)
    assert m not in ddp._KEPT  # until the exchange ends clean
    assert (taken is kept) is not given_away
    assert taken.placed == [] and (taken.bufs[0] is None) is given_away


@pytest.mark.parametrize("with_ef", [False, True], ids=["plain", "error_feedback"])
@pytest.mark.parametrize("keeping", [False, True], ids=["fresh", "kept"])
def test_results_equal_the_fresh_buffer_pack_bit_for_bit(with_ef, keeping, request):
    """Against the pack this replaced (``flatten_buckets``: ``np.concatenate``
    into a new buffer every step), three steps of a mixed-dtype tree."""
    import jax.numpy as jnp

    from torchft_tpu.wire_codec import ErrorFeedback, get_codec

    if keeping:
        request.getfixturevalue("off_cpu")
    rng = np.random.default_rng(3)

    def tree():
        return [
            jnp.asarray(rng.standard_normal((7, 5)).astype(np.float32)),
            jnp.asarray(rng.standard_normal((3, 4, 2)).astype(np.float32)).transpose(2, 0, 1),
            jnp.asarray(rng.standard_normal(33).astype(np.float32)).astype(jnp.bfloat16),
            jnp.asarray(rng.standard_normal(9).astype(np.float16)),
            jnp.float32(rng.standard_normal()),
            jnp.asarray(rng.integers(0, 9, 6).astype(np.int32)),
            jnp.asarray(rng.standard_normal(40).astype(np.float32)),
        ]

    m = RingStub()
    ef = ErrorFeedback(get_codec("bfloat16")) if with_ef else None
    ref_ef = ErrorFeedback(get_codec("bfloat16")) if with_ef else None
    for step in range(3):
        leaves = tree()
        host = [np.asarray(l) for l in leaves]
        buckets = flatten_buckets(host, bucket_bytes=160)
        for ordinal, (buf, _) in enumerate(buckets):
            if ref_ef is not None:
                ref_ef.apply(f"b{ordinal}_{buf.size}", buf)
            m.ring(buf)
        want = unflatten_buckets(buckets, host)
        got, attrs = exchange(m, leaves, bucket_bytes=160, error_feedback=ef)
        assert attrs["buckets"] == len(buckets) >= 5
        assert attrs["buckets_reused"] == (len(buckets) if keeping and step else 0)
        for g, w in zip(got, want):
            g = np.asarray(g)
            assert g.dtype == w.dtype and g.shape == w.shape
            assert g.tobytes() == w.tobytes()
        for e in (ef, ref_ef):
            if e is not None:
                e.commit()


# The mid-pipeline data-plane-death path (error latch + default-resolving
# futures + commit veto) runs against a REAL Manager in
# tests/test_manager.py::test_pipelined_averaging_latches_midway_error.


# -- device-to-host copies land on pages the process keeps (ddp._landing_on_kept_pages) --


class MalloptStub:
    """Stands in for glibc's ``mallopt``: records (parameter, value) and
    answers ``ok`` as the real one answers 1 or 0."""

    def __init__(self, ok=1):
        self.calls, self.ok = [], ok

    def __call__(self, param, value):
        self.calls.append((param, value))
        return self.ok


@pytest.fixture
def mallopt(monkeypatch):
    """The exchange under test sets the stub, not this process's allocator."""
    from torchft_tpu import ddp

    stub = MalloptStub()
    monkeypatch.setattr(ddp, "_mallopt", lambda: stub)
    return stub


KEEP = [(-1, -1), (-4, 0)]  # M_TRIM_THRESHOLD never, M_MMAP_MAX none
PUT_BACK = (-4, 65536)


def test_copies_are_issued_between_the_allocator_settings(off_cpu, mallopt, monkeypatch):
    """Large blocks come from the heap exactly while the copies are issued:
    ``copy_to_host_async`` allocates where it lands on the calling thread."""
    from jax._src import array

    issue = array.ArrayImpl.copy_to_host_async

    def recording(self):
        mallopt.calls.append("copy")
        return issue(self)

    monkeypatch.setattr(array.ArrayImpl, "copy_to_host_async", recording)
    out, attrs = exchange(RingStub(), tree_of(1.0), bucket_bytes=128)
    assert mallopt.calls == KEEP + ["copy"] * 5 + [PUT_BACK]
    assert attrs["d2h_pages_kept"] == 1
    np.testing.assert_array_equal(np.asarray(out["g0"]), (1.0 + np.arange(16)) / 2)


@pytest.mark.parametrize("kind", ["numpy", "cpu_device"])
def test_a_tree_whose_copies_allocate_nothing_leaves_the_allocator_alone(kind, mallopt):
    """A NumPy leaf is not copied, and the CPU backend's host view of a device
    leaf is the device buffer: no landing block, so no setting."""
    tree = tree_of(1.0, "numpy" if kind == "numpy" else "jnp")
    _, attrs = exchange(RingStub(), tree, bucket_bytes=128)
    assert mallopt.calls == [] and attrs["d2h_pages_kept"] == 0


def test_one_device_leaf_among_host_leaves_engages_it(off_cpu, mallopt):
    import jax.numpy as jnp

    tree = tree_of(1.0, "numpy")
    tree["g2"] = jnp.asarray(tree["g2"])
    _, attrs = exchange(RingStub(), tree, bucket_bytes=128)
    assert mallopt.calls == KEEP + [PUT_BACK] and attrs["d2h_pages_kept"] == 1


@pytest.mark.parametrize("why", ["no_mallopt", "refused"])
def test_without_a_willing_allocator_the_exchange_is_what_it_was(why, off_cpu, monkeypatch):
    """musl and macOS have no ``mallopt``; glibc's answers 0 when it refuses.
    Nothing is put back that was not set, and the averages are the same."""
    from torchft_tpu import ddp

    stub = MalloptStub(ok=0)
    monkeypatch.setattr(ddp, "_mallopt", (lambda: None) if why == "no_mallopt" else (lambda: stub))
    out, attrs = exchange(RingStub(), tree_of(3.0), bucket_bytes=128)
    assert attrs["d2h_pages_kept"] == 0
    assert stub.calls == ([] if why == "no_mallopt" else KEEP[:1])
    np.testing.assert_array_equal(np.asarray(out["g1"]), (4.0 + np.arange(16, 32)) / 2)


def test_a_copy_that_raises_still_puts_the_allocator_back(off_cpu, mallopt, monkeypatch):
    from jax._src import array

    from torchft_tpu.ddp import allreduce_gradients

    def refusing(self):
        raise RuntimeError("prefetch refused")

    monkeypatch.setattr(array.ArrayImpl, "copy_to_host_async", refusing)
    with pytest.raises(RuntimeError, match="prefetch refused"):
        allreduce_gradients(RingStub(), tree_of(1.0), bucket_bytes=128)
    assert mallopt.calls == KEEP + [PUT_BACK]


@pytest.mark.parametrize("steps", [1, 3])
def test_every_exchange_sets_and_restores_and_counts(steps, off_cpu, mallopt):
    """The counter is per exchange (1 or 0), the kept buckets are untouched by
    it, and the results are those of the fresh-buffer pack."""
    m = RingStub()
    for step in range(steps):
        out, attrs = exchange(m, tree_of(float(step)), bucket_bytes=128)
        assert attrs["d2h_pages_kept"] == 1
        assert attrs["buckets_reused"] == (3 if step else 0)
        np.testing.assert_array_equal(
            np.asarray(out["g4"]), (step + 4.0 + np.arange(16)) / 2
        )
    assert mallopt.calls == (KEEP + [PUT_BACK]) * steps


def test_the_counter_reaches_the_profilers_annotation(off_cpu, mallopt, monkeypatch):
    """``tft.exchange.counters`` carries what the ``exchange`` span carries."""
    from torchft_tpu.telemetry import tracing

    seen = {}
    real = tracing.annotate

    def annotate(name, **stats):
        if name == "exchange.counters":
            seen.update(stats)
        return real(name, **stats)

    monkeypatch.setattr(tracing, "annotate", annotate)
    _, attrs = exchange(RingStub(), tree_of(1.0), bucket_bytes=128)
    assert seen["d2h_pages_kept"] == attrs["d2h_pages_kept"] == 1
    assert seen["buckets"] == 3


def test_on_glibc_a_large_block_stays_on_the_heap_and_the_heap_stays():
    """The real allocator, in a process of its own (the settings are the
    process's): inside the block 64 MB come from the heap, which then keeps
    them; outside it they are mapped and the heap does not move."""
    import subprocess
    import sys
    import textwrap

    code = textwrap.dedent(
        """
        import ctypes, numpy as np
        from torchft_tpu import ddp
        if ddp._mallopt() is None:
            print("no mallopt"); raise SystemExit(0)
        libc = ctypes.CDLL(None); libc.sbrk.restype = ctypes.c_void_p
        top = lambda: libc.sbrk(0)
        n = 64 << 20
        t0 = top(); a = np.empty(n, np.uint8); a[::4096] = 1; t1 = top(); del a
        assert t1 == t0, "a mapped block moved the heap"
        with ddp._landing_on_kept_pages(False) as held:
            assert held is False
        with ddp._landing_on_kept_pages(True) as held:
            assert held is True
            a = np.empty(n, np.uint8); a[::4096] = 1; t2 = top(); where = a.ctypes.data
        assert t2 - t1 > n // 2 and where + n <= t2, "the block did not come from the heap"
        del a
        assert top() == t2, "the heap gave the pages back"
        b = np.empty(n, np.uint8); t3 = top(); del b
        assert t3 == t2, "outside the block large blocks are mapped again, or reuse the hole"
        with ddp._landing_on_kept_pages(True):
            c = np.empty(n, np.uint8)
            assert c.ctypes.data == where and top() == t2, "the next copy lands where the last did"
        print("ok")
        """
    )
    done = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, timeout=120
    )
    assert done.returncode == 0, done.stderr[-2000:]
    assert done.stdout.strip().splitlines()[-1] in ("ok", "no mallopt")


def test_cpu_seconds_under_the_clocks_tick_read_as_the_quantum_not_as_zero(monkeypatch):
    """``getrusage`` moves in ticks of 10 ms: an exchange no tick caught in a
    system call still made them, and an exact 0 is what readers take for a
    counter that is not there."""
    import types

    from torchft_tpu import ddp

    usage = iter([(5.0, 2.0), (5.03, 2.0)])
    monkeypatch.setattr(
        ddp.resource, "getrusage",
        lambda who: types.SimpleNamespace(**dict(zip(("ru_utime", "ru_stime"), next(usage)))),
    )
    _, attrs = exchange(RingStub(), tree_of(1.0, "numpy"), bucket_bytes=128)
    assert attrs["utime_s"] == pytest.approx(0.03) and attrs["stime_s"] == 1e-6


# -- the account of the exchange's copies (ddp._pack_account, the ring's) -----


def at_offset(arena, offset, nbytes):
    """``nbytes`` of ``arena`` as float32, starting ``offset`` B into a page."""
    base = arena.__array_interface__["data"][0]
    start = (-base) % 4096 + offset
    return arena[start : start + nbytes].view(np.float32)


@pytest.mark.parametrize(
    "ahead,inside",
    [(0, False), (16, True), (800, True), (1023, True), (1024, False), (4095, False)],
)
def test_the_window_counts_the_copies_whose_destination_is_just_ahead(ahead, inside):
    """0 B ahead is the same offset in a page (no false dependence); 1 to
    1023 B ahead is the window; from 1024 B on, and behind the source
    (4095 = 1 B behind), is outside it."""
    from torchft_tpu.ddp import _pack_account

    src_arena, dst_arena = np.zeros(1 << 16, np.uint8), np.zeros(1 << 16, np.uint8)
    src = at_offset(src_arena, 100 * 4, 4096)
    dst = at_offset(dst_arena, (100 * 4 + ahead) % 4096, 3 * 4096)
    assert _pack_account(dst, [src]) == (ahead, 4096, 4096 if inside else 0)
    # the second leaf lands where the first ended; the largest names the offset
    small = at_offset(src_arena, 8192 + 100 * 4 + 4096 - 16, 512)
    ahead2 = (ahead + 16) % 4096
    assert _pack_account(dst, [src, small]) == (
        ahead, 4096 + 512, (4096 if inside else 0) + (512 if 0 < ahead2 < 1024 else 0)
    )
    assert _pack_account(dst, [small, src])[0] == (ahead + 512) % 4096


def test_the_exchange_says_what_it_copied_and_the_pack_where(off_cpu, monkeypatch):
    """``pack_bytes``, ``pack_aliased_bytes`` and ``h2d_bytes`` on the
    ``exchange`` span and on ``tft.exchange.counters``; ``dst_ahead_b`` on
    each ``tft.exchange.pack``. A manager without ``ring_account`` (this
    stub) still exchanges, and reports no ``ring_*`` sum."""
    from torchft_tpu.telemetry import tracing

    counters, packs = {}, []
    real = tracing.annotate

    def annotate(name, **stats):
        if name == "exchange.counters":
            counters.update(stats)
        elif name == "exchange.pack":
            packs.append(stats)
        return real(name, **stats)

    monkeypatch.setattr(tracing, "annotate", annotate)
    out, attrs = exchange(RingStub(), tree_of(1.0), bucket_bytes=128)
    np.testing.assert_allclose(np.asarray(out["g0"]), (1.0 + np.arange(16)) / 2)
    assert attrs["pack_bytes"] == attrs["h2d_bytes"] == attrs["bytes_d2h"] == 5 * 64
    assert 0 <= attrs["pack_aliased_bytes"] <= attrs["pack_bytes"]
    assert attrs["pack_aliased_bytes"] % 64 == 0  # whole leaf copies
    for key in ("pack_bytes", "pack_aliased_bytes", "h2d_bytes"):
        assert counters[key] == attrs[key]
    assert not [k for k in attrs if k.startswith("ring_")]
    assert len(packs) == 3 and all(0 <= p["dst_ahead_b"] < 4096 for p in packs)
    # host leaves are handed back as slices of their bucket: nothing is put
    _, attrs = exchange(RingStub(), tree_of(1.0, kind="numpy"), bucket_bytes=128)
    assert attrs["pack_bytes"] == 5 * 64 and attrs["h2d_bytes"] == 0


def test_the_rings_account_grows_into_the_steps_sums():
    """What the manager's ``ring_account()`` gained over the exchange lands
    on the span: wait = desc_wait + ack_wait; a field the exchange has no
    sum for (codec, slowest stripe, stripes) is left to the per-op event."""
    from torchft_tpu.collectives import RING_ACCOUNT

    class Accounting(RingStub):
        def __init__(self):
            super().__init__()
            self.total = dict.fromkeys(RING_ACCOUNT, 0)

        def ring_account(self):
            return dict(self.total)

        def ring(self, buf):
            super().ring(buf)
            for k, v in dict(
                desc_wait_s=0.25, ack_wait_s=0.5, pull_s=0.125, reduce_s=1.0, pump_s=2.0,
                codec_s=4.0, pull_bytes=buf.nbytes, reduce_bytes=buf.nbytes // 2, stripes=4,
            ).items():
                self.total[k] += v

    m = Accounting()
    m.total["pull_s"] = 100.0  # what earlier exchanges left: only growth counts
    _, attrs = exchange(m, tree_of(1.0), bucket_bytes=128)
    assert attrs["buckets"] == 3
    assert {k: v for k, v in attrs.items() if k.startswith("ring_")} == {
        "ring_wait_s": 3 * 0.75, "ring_pull_s": 3 * 0.125, "ring_reduce_s": 3.0,
        "ring_pump_s": 6.0, "ring_pull_bytes": 5 * 64, "ring_reduce_bytes": 5 * 32,
    }


# -- a gradient that comes in pieces (utils.pieces.GradPieces) --------------------------


PIECES_META = [  # (dtype, nbytes), and the piece each item belongs to
    (np.dtype(np.float32), 4000), (np.dtype(np.float32), 16),          # the head's
    (np.dtype(np.float32), 1000), (np.dtype(np.float16), 8), (np.dtype(np.float32), 16),  # a layer's
    (np.dtype(np.float32), 1000), (np.dtype(np.float16), 8), (np.dtype(np.float32), 16),  # the layer below
    (np.dtype(np.float32), 4000),                                      # the embedding's
]
PIECE_OF = [0, 0, 1, 1, 1, 2, 2, 2, 3]


@pytest.mark.parametrize("bucket_bytes", [64, 1024, 1 << 30])
def test_a_piece_is_a_bucket_whatever_the_bucket_size(bucket_bytes):
    """Where the tree comes in pieces the plan follows the order given, puts a
    piece's items of one dtype into one bucket — its small leaves with it, and
    never two pieces together — and is a function of the metadata alone."""
    plan = plan_buckets(PIECES_META, bucket_bytes, PIECE_OF)
    assert plan == [[0, 1], [2, 4], [3], [5, 7], [6], [8]]
    assert plan == plan_buckets(list(PIECES_META), bucket_bytes, list(PIECE_OF))
    # every item once, and the buckets start in the order the pieces were given
    assert sorted(i for idxs in plan for i in idxs) == list(range(len(PIECES_META)))
    assert [PIECE_OF[idxs[0]] for idxs in plan] == sorted(PIECE_OF[idxs[0]] for idxs in plan)


@pytest.mark.parametrize("order", [(0, 1, 2, 3), (3, 1, 0, 2), (2, 3, 1, 0)])
def test_the_plan_follows_the_order_given(order):
    """One piece: today's rule by bytes, over the leaves in the order given —
    whichever that is — and equal for equal metadata."""
    meta = [(np.dtype(np.float32), 60 + 4 * i) for i in order]
    plan = plan_buckets(meta, bucket_bytes=128)
    assert plan == plan_buckets(list(meta), bucket_bytes=128)
    assert [i for idxs in plan for i in idxs] == list(range(4))  # positions in the order given, never sorted by size
    assert all(sum(meta[i][1] for i in idxs) <= 128 or len(idxs) == 1 for idxs in plan)


class NotReadyYet:
    """A stand-in for a device array whose program has not ended: its host
    view blocks until ``ready`` is set (and says whether it ever was)."""

    dtype, shape = np.dtype(np.float32), (16,)

    def __init__(self, value):
        import threading

        self.ready, self.value, self.timed_out = threading.Event(), value, False

    def is_ready(self):
        return self.ready.is_set()

    def __array__(self, dtype=None, copy=None):
        self.timed_out = not self.ready.wait(10)
        return np.full(self.shape, self.value, self.dtype)


def pieces_of(last):
    from torchft_tpu.utils.pieces import GradPieces

    return GradPieces([
        {"out": np.full((16,), np.float32(1.0)), "final_norm": np.full((4,), np.float32(2.0))},
        {"w": np.full((16,), np.float32(3.0))},
        {"embed": last},
    ])


def test_a_leaf_that_is_not_ready_does_not_hold_an_earlier_buckets_ring():
    """Where buckets are packed (this stub's plane takes no sources): the head's
    bucket is on the ring, and the layer's after it, while the last piece is
    still being computed — the stand-in becomes ready only once two rings were
    submitted, so an exchange that waited for the whole tree first would sit
    out the stand-in's timeout."""
    late = NotReadyYet(5.0)

    class Releasing(RingStub):
        def allreduce_many(self, tensors):
            fut = super().allreduce_many(tensors)
            if len(self.seen) == 2:
                late.ready.set()
            return fut

    m = Releasing()
    out, attrs = exchange(m, pieces_of(late))
    assert not late.timed_out and [buf.size for buf in m.seen] == [20, 16, 16]
    np.testing.assert_allclose(np.asarray(out[2]["embed"]), (5.0 + np.arange(16)) / 2)
    np.testing.assert_allclose(np.asarray(out[0]["final_norm"]), (2.0 + np.arange(4)) / 2)
    # the first two buckets landed under the last piece's program: 80 + 64 of 208 bytes
    assert (attrs["pieces"], attrs["buckets"], attrs["bytes_under_grads"]) == (3, 3, 144)
    assert attrs["bucket_under_grads"] == "1,1,0"


@pytest.mark.parametrize("tree, pieces, buckets", [("pieces", 3, 3), ("dict", 1, 2)])
def test_pieces_and_bytes_under_grads_are_on_the_span(tree, pieces, buckets):
    """``pieces``: how many the tree came in (1: a tree one program gave whole);
    ``bytes_under_grads``: 0 when every array was there before the first landing
    wait returned; a landing time and a ring's end a bucket, in order."""
    from torchft_tpu.utils.pieces import GradPieces

    done = np.full((16,), np.float32(5.0))
    grads = pieces_of(done) if tree == "pieces" else {f"g{i}": np.full((16,), np.float32(i)) for i in range(3)}
    out, attrs = exchange(RingStub(), grads, bucket_bytes=128)
    assert isinstance(out, GradPieces) == (tree == "pieces")
    assert (attrs["pieces"], attrs["buckets"], attrs["bytes_under_grads"]) == (pieces, buckets, 0)
    for key in ("bucket_landed_s", "bucket_ring_end_s"):
        at = [float(t) for t in attrs[key].split(",")]
        assert len(at) == buckets and at == sorted(at)


def test_the_op_thread_account_of_an_untraced_run_says_what_each_ring_waited_for(tmp_path):
    """``scripts/op_thread_account.py`` on a worker's ``result.<g>.json``: from
    the ``exchange`` span's by-bucket attributes, beside each ring when its
    piece was ready and whether the op thread waited for the chip, for the
    landing copy, or was still on the ring before."""
    import importlib.util
    import json
    import os

    late = NotReadyYet(5.0)

    class Releasing(RingStub):
        def allreduce_many(self, tensors):
            fut = super().allreduce_many(tensors)
            if len(self.seen) == 2:
                late.ready.set()
            return fut

    _, attrs = exchange(Releasing(), pieces_of(late))
    path = tmp_path / "result.0.json"
    path.write_text(json.dumps({"exchange_spans": [{"dur_s": 0.5, **attrs}, {"dur_s": 0.1, "step": 0, "buckets": 1}]}))
    spec = importlib.util.spec_from_file_location(
        "op_thread_account", os.path.join(os.path.dirname(__file__), "..", "scripts", "op_thread_account.py")
    )
    script = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(script)
    out = script.untraced(str(path))
    (step,) = out["steps"]  # a span without the by-bucket attributes (an older program's) is left out
    assert (step["pieces"], step["bytes_under_grads"]) == (3, 144)
    assert [b["bucket"] for b in step["buckets"]] == [0, 1, 2]
    # this stub's ring is over before its call returns: the op thread is free when the next piece lands
    assert [b["waited_for"] for b in step["buckets"]] == ["chip", "chip", "landing"]
    assert all(b["op_thread_idle_before_s"] >= 0 and b["ring_end_s"] >= b["piece_ready_s"] for b in step["buckets"])
    assert out["median"]["waited_for_chip_s"] == step["waited_for_chip_s"] > 0


@pytest.mark.parametrize("plane, ready_when_submitted", [
    ("cma", [True, True, True]),           # the groups on one host: every landing, then the rings
    ("tcp-striped", [False, False, True]),  # across hosts: a ring rides while the next bucket lands
])
def test_only_on_one_hosts_memory_every_bucket_lands_before_the_first_ring(plane, ready_when_submitted):
    """A plane that ``takes_sources()`` reads the landing arrays themselves. On
    ``cma`` its ring and the landing copies draw on one host's memory bandwidth,
    so no ring is submitted beside a landing — the first ``allreduce_many`` sees
    the last piece on the host — while the landings still follow the programs
    (``bytes_under_grads``). On ``tcp-striped`` the ring is the network's: the
    head's bucket is on it while the last piece is still being computed."""
    import threading

    from torchft_tpu.collectives import fill_from_sources

    late = NotReadyYet(5.0)
    ready_at_submit = []

    class ReadsSources(RingStub):
        def takes_sources(self):
            return True

        def plane_info(self):
            return plane

        def allreduce_many(self, tensors, sources=None):
            ready_at_submit.append(late.ready.is_set())
            assert sources is not None  # float32, contiguous, no error feedback: nothing is packed
            fill_from_sources(tensors, sources)
            fut = super().allreduce_many(tensors)
            if len(self.seen) == 2:  # land-first never gets here before the piece is ready
                late.ready.set()
            return fut

    if plane == "cma":
        threading.Timer(0.05, late.ready.set).start()
    out, attrs = exchange(ReadsSources(), pieces_of(late))
    assert ready_at_submit == ready_when_submitted and not late.timed_out
    np.testing.assert_allclose(np.asarray(out[2]["embed"]), (5.0 + np.arange(16)) / 2)
    np.testing.assert_allclose(np.asarray(out[1]["w"]), (3.0 + np.arange(16)) / 2)
    assert (attrs["pieces"], attrs["buckets"], attrs["pack_bytes"]) == (3, 3, 0)
    assert (attrs["bytes_under_grads"], attrs["bucket_under_grads"]) == (144, "1,1,0")
