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
