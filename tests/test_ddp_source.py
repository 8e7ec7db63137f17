"""``ddp``'s choice between handing the ring the landing arrays as a source
and packing them (PR 39): it falls on what the exchange can observe — the
bucket's dtype, ``error_feedback``, the leaves' layout and the data plane's
``takes_sources()`` — and either way the averaged tree is the same bits and
the caller's tree is only read. Real Managers on real rings
(``test_manager``'s harness) for the path itself; a stub for the choice.
"""

from concurrent.futures import ThreadPoolExecutor
from datetime import timedelta

import numpy as np
import pytest

from torchft_tpu import ddp
from torchft_tpu.collectives import RING_ACCOUNT, CollectivesTcp, fill_from_sources
from tests.test_ddp import RingStub, exchange, off_cpu  # noqa: F401 — the fixture
from tests.test_manager import harness, quorum_result, store_server  # noqa: F401

WAIT = timedelta(seconds=20)


def _tree(rank, step):
    """Float32 leaves of a model's kinds: matrices, a scalar, an empty one, a
    NumPy leaf the caller owns; sizes that divide by nothing."""
    import jax.numpy as jnp

    rng = np.random.default_rng(100 * step + rank)
    f32 = lambda *shape: (rng.standard_normal(shape) * 3).astype(np.float32)  # noqa: E731
    return {
        "embed": jnp.asarray(f32(257, 33)),
        "w": [jnp.asarray(f32(64, 67)), jnp.asarray(f32(3, 5, 7))],
        "scale": jnp.float32(f32()),
        "none": jnp.zeros((0, 4), jnp.float32),
        "host": f32(1031),
        "bias": jnp.asarray(f32(4099)),
    }


def _bytes_of(tree):
    import jax

    return [np.asarray(l).tobytes() for l in jax.tree_util.tree_leaves(tree)]


@pytest.mark.parametrize("cma", ["1", "0"])
def test_four_groups_average_from_where_the_gradients_landed(
    store_server, harness, monkeypatch, off_cpu, cma
):
    """World 4 on the native ring, three steps: the first two hand the ring
    the landing arrays, the third packs (the plane made to say no). Every
    bucket engages, the ring's own copy is a quarter of the bytes (plane tcp,
    for its pump) or nothing (plane cma offers the chunk where it lies), the kept
    buffers are reused, the caller's tree reads as before, and a step's
    average is bit for bit the packing path's on the same gradients."""
    monkeypatch.setenv("TORCHFT_DP_CMA", cma)
    world = 4
    hs = [
        harness(collectives=CollectivesTcp(hostname="localhost", timeout=WAIT))
        for _ in range(world)
    ]

    def run(r):
        h = hs[r]
        q = quorum_result(
            max_rank=r, max_world_size=world, replica_rank=r, replica_world_size=world
        )
        q.store_address = f"{store_server.address()}/ddpsrc{cma}"
        h.client._quorum.return_value = q
        h.manager.start_quorum()
        h.manager.wait_quorum()
        steps = []
        for step, takes in enumerate([True, True, False]):
            if not takes:
                h.manager.takes_sources = lambda: False
            grads = _tree(r, step=min(step, 1))  # steps 1 and 2: the same gradients
            before = _bytes_of(grads)
            out, attrs = _exchange_on_this_thread(h.manager, grads)
            assert h.manager.errored() is None
            assert _bytes_of(grads) == before
            steps.append((_bytes_of(out), attrs))
        return steps

    with ThreadPoolExecutor(max_workers=world) as ex:
        outs = list(ex.map(run, range(world)))
    for steps in outs:
        (_, first), (from_source, second), (packed, third) = steps
        assert from_source == packed
        assert from_source == outs[0][1][0]  # every group holds the same bits
        for attrs in (first, second):
            assert attrs["buckets"] >= 4
            assert attrs["buckets_from_source"] == attrs["buckets_avg_in_ring"] == attrs["buckets"]
            # the chunk each stripe sends raw at step 0: a quarter, to rounding
            assert attrs["pack_bytes"] <= attrs["bytes_d2h"] / 4 + 64 * attrs["buckets"]
            assert (attrs["pack_bytes"] == 0) == (cma == "1")
            assert 0 <= attrs["pack_aliased_bytes"] <= attrs["pack_bytes"]
            assert attrs["pack_s"] > 0
            if cma == "0":
                assert attrs["pack_bytes"] + attrs["ring_reduce_bytes"] == attrs["bytes_d2h"]
        assert first["buckets_reused"] == 0
        # every bucket but the one holding the caller's NumPy leaf, whose
        # averaged piece is handed back as a slice of it
        assert second["buckets_reused"] == second["buckets"] - 1 == third["buckets_reused"]
        assert third["buckets_from_source"] == 0
        assert third["pack_bytes"] == third["bytes_d2h"]


def _exchange_on_this_thread(manager, grads):
    """``tests.test_ddp.exchange`` for rank threads: the newest ``exchange``
    span is whichever thread's ended last, so pick this thread's."""
    import threading

    from torchft_tpu.telemetry import tracing

    out = ddp.allreduce_gradients(manager, grads, bucket_bytes=16 * 1024)
    tid = threading.get_ident() & 0x7FFFFFFF
    mine = [s for s in tracing.TRACER.recent("exchange") if s["tid"] == tid]
    return out, mine[-1]["attrs"]


class TakesSources(RingStub):
    """A ``RingStub`` whose plane takes sources: it records what each bucket
    came with and accounts for it as the native ring does."""

    def __init__(self):
        super().__init__()
        self.sources = []
        self.total = dict.fromkeys(RING_ACCOUNT, 0)

    def takes_sources(self):
        return True

    def ring_account(self):
        return dict(self.total)

    def allreduce_many(self, tensors, sources=None):
        self.sources.append(None if sources is None else sources[0])
        if sources is not None and self.err is None:
            fill_from_sources(tensors, sources)
            self.total["from_source"] += 1
        return super().allreduce_many(tensors)


def _f32_tree(kind="jnp"):
    import jax.numpy as jnp

    make = {"jnp": jnp.asarray, "numpy": np.asarray}[kind]
    return {f"g{i}": make(np.arange(16, dtype=np.float32) + i) for i in range(5)}


def test_a_plane_that_takes_sources_gets_the_landing_arrays_and_no_pack():
    m = TakesSources()
    tree = _f32_tree()
    out, attrs = exchange(m, tree, bucket_bytes=128)
    assert attrs["buckets"] == attrs["buckets_from_source"] == 3
    assert attrs["pack_bytes"] == 0  # the stub's "ring" copied nothing itself
    for bucket, source in zip(m.seen, m.sources):
        assert sum(s.size for s in source) == bucket.size
        assert not any(np.shares_memory(bucket, s) for s in source)
    peer = np.arange(32, dtype=np.float32)
    for i in range(5):
        np.testing.assert_array_equal(
            np.asarray(out[f"g{i}"]),
            (np.arange(16, dtype=np.float32) + i + peer[16 * (i % 2):][:16]) / 2,
        )


@pytest.mark.parametrize(
    "why", ["error_feedback", "bf16-leaves", "strided-leaf", "duck-typed", "python-ring"]
)
def test_what_the_ring_cannot_read_in_place_is_packed_as_before(
    why, store_server, harness
):
    import jax.numpy as jnp

    from torchft_tpu.wire_codec import ErrorFeedback, get_codec

    kwargs = {"bucket_bytes": 128}
    tree = _f32_tree("numpy" if why == "strided-leaf" else "jnp")
    if why == "error_feedback":
        kwargs["error_feedback"] = ErrorFeedback(get_codec("bfloat16"))
    elif why == "bf16-leaves":
        tree = {k: v.astype(jnp.bfloat16) for k, v in tree.items()}
    elif why == "strided-leaf":
        tree["g0"] = np.arange(32, dtype=np.float32)[::2]

    if why == "python-ring":
        hs = [
            harness(collectives=CollectivesTcp(
                hostname="localhost", timeout=WAIT, native_plane=False))
            for _ in range(2)
        ]

        def run(r):
            q = quorum_result(max_rank=r, replica_rank=r)
            q.store_address = f"{store_server.address()}/ddppy"
            hs[r].client._quorum.return_value = q
            hs[r].manager.start_quorum()
            hs[r].manager.wait_quorum()
            assert not hs[r].manager.takes_sources()
            return _exchange_on_this_thread(hs[r].manager, tree)[1]

        with ThreadPoolExecutor(max_workers=2) as ex:
            for attrs in ex.map(run, range(2)):
                assert attrs["buckets_from_source"] == 0
                assert attrs["pack_bytes"] == attrs["bytes_d2h"]
        return

    m = RingStub() if why == "duck-typed" else TakesSources()
    _, attrs = exchange(m, tree, **kwargs)
    if why == "strided-leaf":  # only the bucket that holds it: g0 and g1
        assert [s is None for s in m.sources] == [True, False, False]
        assert attrs["buckets_from_source"] == 2 and attrs["pack_bytes"] == 2 * 64
        return
    assert attrs["buckets_from_source"] == 0
    assert attrs["pack_bytes"] == attrs["bytes_d2h"]
    if why != "duck-typed":
        assert m.sources == [None] * attrs["buckets"]


def test_a_failed_ring_with_a_source_latches_and_keeps_nothing(off_cpu):
    m = TakesSources()
    exchange(m, _f32_tree(), bucket_bytes=128)
    assert exchange(m, _f32_tree(), bucket_bytes=128)[1]["buckets_reused"] == 3
    real = m.allreduce_many

    def failing(tensors, sources=None):
        if len(m.seen) % 3 == 1:  # the second bucket's ring dies
            m.err = RuntimeError("torn pull")
        return real(tensors, sources)

    m.allreduce_many = failing
    _, attrs = exchange(m, _f32_tree(), bucket_bytes=128)
    assert m.err is not None and m not in ddp._KEPT
    m.err, m.allreduce_many = None, real
    _, attrs = exchange(m, _f32_tree(), bucket_bytes=128)
    assert attrs["buckets_reused"] == 0 and attrs["buckets_from_source"] == 3
