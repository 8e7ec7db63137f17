"""An allreduce with a source (PR 39): the native ring reads this rank's
contribution from read-only segments and only writes the destination, which
ends bit for bit as the in-place op on a packed copy leaves it
(native/dataplane.h ``DataPlane::allreduce``; ``Collectives.allreduce``'s
``sources``; ``Manager.allreduce_many``'s). On ``test_collectives``'s rank
threads sharing one store, and ``test_manager``'s harness for the Manager.
"""

import os
import subprocess
import sys
import textwrap
from concurrent.futures import ThreadPoolExecutor
from datetime import timedelta

import numpy as np
import pytest

from torchft_tpu.collectives import (
    CollectivesDummy,
    CollectivesTcp,
    ErrorSwallowingCollectives,
    ReduceOp,
    Work,
    fill_from_sources,
)
from tests.test_collectives import _bits, _run_world, store  # noqa: F401 — the fixture
from tests.test_manager import (  # noqa: F401 — the fixtures
    WorldSizeMode,
    harness,
    quorum_result,
    store_server,
)

WAIT = timedelta(seconds=20)
# no multiple of world x stripes x 16 (chunk bounds fall inside segments); 67
# < stripes x 64 takes the one-stripe route; the last gives a stripe's chunk
# several segments, which plane cma offers as the pieces of one descriptor
SIZES = (100003, 67, 1200007)
# where a bucket of n elements is cut into segments: one piece, or several —
# a single element, an empty one, a cut off every 16-element boundary — or so
# many that a stripe's chunk holds more pieces than one pull takes (16)
LAYOUTS = {
    "one": lambda n: [0, n],
    "several": lambda n: sorted({0, 1, min(n, 31), min(n, 31), n // 3 + 5, n - 1, n}),
    "many": lambda n: list(range(0, n, 101)) + [n],
}


def _segments(a, layout):
    """``a`` cut into read-only arrays of their own memory (never views of
    one buffer: the ring must walk addresses that have nothing in common)."""
    cuts = LAYOUTS[layout](a.size)
    segs = [a[i:j].copy() for i, j in zip(cuts, cuts[1:])]
    if layout == "several":
        segs.insert(2, np.empty(0, a.dtype))
    for s in segs:
        s.flags.writeable = False
    return segs


def _growth(c, op):
    before = c.ring_account()
    out = op()
    after = c.ring_account()
    return out, {k: after[k] - before[k] for k in after}


def _both_ways(world, layout, op=ReduceOp.SUM, divisor=None, zero_last=False):
    """Per size: (in place on a packed copy, from the source, the source's
    growth of the account, the in-place op's, whether the source is as it
    was)."""

    def fn(c, rank):
        rng = np.random.default_rng(17 + rank)
        out = []
        for size in SIZES:
            a = (rng.standard_normal(size) * 3).astype(np.float32)
            if zero_last and rank == world - 1:
                a[...] = 0
            d = divisor if divisor is not None else 1
            packed, acct0 = _growth(c, lambda: c.allreduce([a.copy()], op, d).wait(WAIT)[0])
            segs = _segments(a, layout)
            dst = np.full(size, np.nan, np.float32)  # whatever it held is ignored
            got, acct = _growth(
                c, lambda: c.allreduce([dst], op, d, sources=[segs]).wait(WAIT)[0]
            )
            assert got is dst
            untouched = np.array_equal(_bits(np.concatenate(segs)), _bits(a))
            out.append((packed, got, acct, acct0, untouched))
        return c.plane_info(), c.takes_sources(), out

    return fn


@pytest.mark.parametrize("layout", list(LAYOUTS))
@pytest.mark.parametrize("cma", ["1", "0"])
@pytest.mark.parametrize("world", [2, 3, 4])
def test_the_destination_is_the_in_place_op_on_a_packed_copy_bit_for_bit(
    store, monkeypatch, world, cma, layout
):
    monkeypatch.setenv("TORCHFT_DP_CMA", cma)
    outs = _run_world(
        store, world, _both_ways(world, layout, divisor=world), f"src{cma}{world}{layout}"
    )
    for plane, takes, per_size in outs:
        assert plane == ("cma" if cma == "1" else "tcp-striped") and takes
        for size, (packed, got, acct, acct0, untouched) in zip(SIZES, per_size):
            np.testing.assert_array_equal(_bits(got), _bits(packed))
            assert untouched
            # each own element is read once: as the first operand of a reduce,
            # or in the chunk sent raw at step 0 (about 1/world of them), which
            # plane cma offers where it lies and plane tcp copies for its pump
            assert acct["from_source"] == 1 and acct0["from_source"] == 0
            if plane == "cma":
                assert acct["copy_bytes"] == acct["copy_s"] == 0
            else:
                assert acct["copy_bytes"] + acct["reduce_bytes"] == 4 * size
                assert abs(acct["copy_bytes"] - 4 * size / world) <= 4 * 4 * world
                assert 0 <= acct["copy_aliased_bytes"] <= acct["copy_bytes"]
            assert acct0["copy_bytes"] == acct0["copy_s"] == 0
            for key in ("pull_bytes", "pump_bytes", "reduce_bytes", "stripes"):
                assert acct[key] == acct0[key]


@pytest.mark.parametrize("world", [2, 4])
def test_plane_cma_pulling_pieces_gives_plane_tcps_bits(store, monkeypatch, world):
    """At step 0 plane cma's neighbour pulls a stripe's own chunk from the
    pieces of the source where they lie, plane tcp's pump sends the stripe's
    copy of it: the same values in the same additions, so the same bits, in
    place and from a source."""
    by_plane = {}
    for cma in ("1", "0"):
        monkeypatch.setenv("TORCHFT_DP_CMA", cma)
        by_plane[cma] = _run_world(
            store, world, _both_ways(world, "several", divisor=world), f"blk{cma}{world}"
        )
    for (plane, _, blocks), (other, _, whole) in zip(by_plane["1"], by_plane["0"]):
        assert (plane, other) == ("cma", "tcp-striped")
        for (packed, got, *_), (packed0, got0, *_) in zip(blocks, whole):
            np.testing.assert_array_equal(_bits(packed), _bits(packed0))
            np.testing.assert_array_equal(_bits(got), _bits(got0))


@pytest.mark.parametrize("cma", ["1", "0"])
@pytest.mark.parametrize(
    "op,divisor,zero_last",
    [(ReduceOp.SUM, 1, False), (ReduceOp.SUM, 2, True), (ReduceOp.MAX, None, False),
     (ReduceOp.MIN, None, False), (ReduceOp.AVG, None, False)],
    ids=["sum", "participants-2-of-3", "max", "min", "avg"],
)
def test_every_op_and_divisor_reduces_from_a_source_as_in_place(
    store, monkeypatch, cma, op, divisor, zero_last
):
    monkeypatch.setenv("TORCHFT_DP_CMA", cma)
    outs = _run_world(
        store, 3, _both_ways(3, "several", op, divisor, zero_last),
        f"srcop{cma}{op.value}{divisor}",
    )
    for _, _, per_size in outs:
        for packed, got, acct, _, untouched in per_size:
            np.testing.assert_array_equal(_bits(got), _bits(packed))
            assert untouched and acct["from_source"] == 1


@pytest.mark.parametrize("codec", ["bfloat16", "int8"])
@pytest.mark.parametrize("world", [2, 3])
def test_a_lossy_codec_copies_its_whole_stripe_first_and_says_so(
    store, monkeypatch, codec, world
):
    """The native codecs re-read the partial sums they wrote, so their
    stripes take all of the source up front (``job.codec`` decides): the
    same bits as the in-place lossy op, and ``copy_bytes`` the whole array."""
    monkeypatch.setenv("TORCHFT_DP_CMA", "0")  # cma bypasses the codec
    outs = _run_world(
        store, world, _both_ways(world, "several", divisor=world),
        f"srcc{codec}{world}", wire_dtype=codec,
    )
    for plane, takes, per_size in outs:
        assert plane == "tcp-striped" and takes
        for size, (packed, got, acct, _, untouched) in zip(SIZES, per_size):
            np.testing.assert_array_equal(_bits(got), _bits(packed))
            assert untouched
            assert acct["from_source"] == 1 and acct["copy_bytes"] == 4 * size


@pytest.mark.parametrize(
    "case", ["python-ring", "python-codec", "world-1", "f64-beside-f32"]
)
def test_where_the_native_ring_does_not_run_the_source_is_filled_in(store, case):
    """Never a dropped contribution: an array the native ring does not take
    (no plane, a codec without a native twin, a world of one, a dtype) gets
    its source copied in on the op thread, and the account says who copied."""
    kwargs = {"python-ring": {"native_plane": False},
              "python-codec": {"native_plane": False, "wire_dtype": "bfloat16"}}
    world = 1 if case == "world-1" else 2

    def fn(c, rank):
        arrays = [np.full(4099, rank + 1.0, np.float32)]
        if case == "f64-beside-f32":
            arrays.append(np.full(515, rank + 1.0, np.float64))
        sources = [_segments(a, "several") for a in arrays]
        dsts = [np.full(a.shape, np.nan, a.dtype) for a in arrays]
        out, acct = _growth(
            c, lambda: c.allreduce(dsts, ReduceOp.SUM, world, sources=sources).wait(WAIT)
        )
        return c.takes_sources(), [o.copy() for o in out], acct, [a.nbytes for a in arrays]

    for takes, out, acct, nbytes in _run_world(
        store, world, fn, f"fill{case}", **kwargs.get(case, {})
    ):
        assert takes == (case == "f64-beside-f32")  # asked of the plane, not of an array
        for a in out:
            np.testing.assert_array_equal(a, np.full(a.shape, (world + 1) / 2, a.dtype))
        if case == "f64-beside-f32":  # the f32 array rode the native ring (cma)
            assert acct["from_source"] == 1 and acct["copy_bytes"] == nbytes[1]
        else:
            assert acct["from_source"] == 0 and acct["copy_bytes"] == sum(nbytes)


def test_a_source_that_does_not_cover_the_destination_is_refused(store):
    def fn(c, rank):
        short = [np.ones(100, np.float32)]
        with pytest.raises(Exception, match="100 elements, the destination 128"):
            c.allreduce([np.zeros(128, np.float32)], sources=[short]).wait(WAIT)
        # what the ring would read at a bare address is checked before it is one
        for bad in (np.ones(256, np.float32)[::2], np.ones(128, np.float64)):
            with pytest.raises(ValueError, match="C-contiguous arrays of its array's dtype"):
                c.allreduce([np.zeros(128, np.float32)], sources=[[bad]])
        return True

    assert all(_run_world(store, 2, fn, "srcshort"))


@pytest.mark.parametrize("cma", ["1", "0"])
def test_a_timed_out_hop_with_a_source_fails_the_op_and_leaves_the_source(
    store, monkeypatch, cma
):
    """A neighbour that never enters the ring: the op fails at its deadline
    (a retryable timeout, naming no peer) and the source reads as before."""
    import threading

    monkeypatch.setenv("TORCHFT_DP_CMA", cma)
    gone = threading.Event()

    def fn(c, rank):
        if rank == 1:
            gone.wait(10)
            return None
        a = np.arange(100003, dtype=np.float32)
        segs = _segments(a, "several")
        dst = np.zeros(a.size, np.float32)
        try:
            with pytest.raises(TimeoutError):
                c.allreduce([dst], ReduceOp.SUM, 2, sources=[segs]).wait(WAIT)
        finally:
            gone.set()
        return np.array_equal(np.concatenate(segs), a), c.ring_account()["from_source"]

    outs = _run_world(store, 2, fn, f"srcto{cma}", timeout=timedelta(seconds=1))
    assert outs[0] == (True, 1)


_TORN = """
import sys
from datetime import timedelta
import numpy as np
from torchft_tpu.collectives import ReduceOp
from torchft_tpu.store import StoreServer
from tests.test_collectives import _run_world

def fn(c, rank):
    a = np.full(1 << 16, rank + 1.0, np.float32)
    src = [a[: 1 << 15].copy(), a[1 << 15 :].copy()]
    for s in src:
        s.flags.writeable = False
    try:
        c.allreduce([np.zeros(a.size, np.float32)], ReduceOp.SUM, 2, sources=[src]).wait(
            timedelta(seconds=20))
        how = "completed"
    except Exception as e:
        how = f"{type(e).__name__}: {e}"
    return c.plane_info(), how, bool(np.array_equal(np.concatenate(src), a))

s = StoreServer()
try:
    for out in _run_world(s, 2, fn, "torn", timeout=timedelta(seconds=3)):
        print("RANK", *out, sep="|")
finally:
    s.shutdown()
"""


def test_a_torn_pull_with_a_source_fails_the_op_on_both_ranks(tmp_path):
    """``TORCHFT_FI_CMA_TORN`` is read once per process, so the ring runs in
    one of its own: the hop it tears fails that rank's op, the neighbour's
    with it, and neither source is written."""
    env = dict(os.environ, TORCHFT_FI_CMA_TORN="2:0.5", JAX_PLATFORMS="cpu",
               TORCHFT_FI_EVIDENCE_DIR=str(tmp_path))
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.dirname(os.path.dirname(__file__)), env.get("PYTHONPATH", "")]
    )
    proc = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(_TORN)],
        env=env, capture_output=True, text=True, timeout=120,
    )
    ranks = [l.split("|")[1:] for l in proc.stdout.splitlines() if l.startswith("RANK")]
    assert len(ranks) == 2, proc.stdout + proc.stderr
    assert [r[0] for r in ranks] == ["cma", "cma"]
    assert all(r[1] != "completed" and r[2] == "True" for r in ranks), ranks
    assert any("torn CMA pull" in r[1] for r in ranks), ranks


def test_fill_from_sources_is_the_plain_pack():
    a = np.arange(10, dtype=np.float32)
    dst = [np.zeros(10, np.float32).reshape(2, 5), np.full(3, 7.0, np.float32)]
    fill_from_sources(dst, [[a[:4], a[4:4], a[4:].reshape(2, 3)], None])
    np.testing.assert_array_equal(dst[0].reshape(-1), a)
    np.testing.assert_array_equal(dst[1], 7.0)  # no source: as it was
    fill_from_sources(dst, None)
    np.testing.assert_array_equal(dst[0].reshape(-1), a)


def test_wrappers_delegate_the_capability_and_the_sources(store):
    assert not CollectivesDummy().takes_sources()
    inner = CollectivesTcp(timeout=timedelta(seconds=5), hostname="localhost")
    wrap = ErrorSwallowingCollectives(inner)
    wrap.configure(f"{store.address()}/eswsrc", 0, 1)
    assert not wrap.takes_sources()  # a world of one has no native plane
    dst = np.zeros(2, np.float32)
    src = [np.array([2.0, 4.0], np.float32)]
    assert wrap.allreduce([dst], ReduceOp.SUM, 2, sources=[src]).wait()[0] is dst
    np.testing.assert_array_equal(dst, [1.0, 2.0])
    inner.takes_sources = lambda: True
    assert wrap.takes_sources()
    wrap.shutdown()


# -- the Manager: the pass-through, and where it fills in itself ---------------


def test_a_plane_that_takes_no_sources_is_handed_none(harness):
    """The Manager asks the data plane; one that says no (here the dummy)
    gets the buffers already filled and no ``sources`` argument at all."""
    h = harness()
    m = h.manager
    h.client._quorum.return_value = quorum_result(max_rank=1)
    m.start_quorum()
    seen = []

    def recording(arrays, op=ReduceOp.SUM, divisor=1):
        seen.append([a.copy() for a in arrays])
        return Work.completed(arrays)

    h.collectives.allreduce = recording
    assert not m.takes_sources()
    dst = np.zeros(4, np.float32)
    src = [np.array([1.0, 2.0], np.float32), np.array([3.0, 4.0], np.float32)]
    assert m.allreduce_many([dst], sources=[src]).wait()[0] is dst
    np.testing.assert_array_equal(seen[0][0], [1.0, 2.0, 3.0, 4.0])


def test_a_lost_step_still_hands_back_the_callers_own_gradients(harness):
    """With the error latched no ring runs: the buffers come back holding
    the sources, as the packed buffers did."""
    h = harness()
    m = h.manager
    h.client._quorum.return_value = quorum_result(max_rank=1)
    m.start_quorum()
    m.report_error(RuntimeError("peer gone"))
    dst = np.zeros(3, np.float32)
    m.allreduce_many([dst], sources=[[np.array([5.0, 6.0, 7.0], np.float32)]]).wait()
    np.testing.assert_array_equal(dst, [5.0, 6.0, 7.0])


@pytest.mark.parametrize("cma", ["1", "0"])
def test_a_spare_passes_no_source_and_its_zeros_add_nothing(
    store_server, harness, monkeypatch, cma
):
    """Three groups on the native ring, two participants and a spare, every
    one called with a source: the participants' rings read theirs, the spare
    zeroes its destination and hands the ring no source, and all three hold
    np.divide(a0 + a1, 2) bit for bit."""
    monkeypatch.setenv("TORCHFT_DP_CMA", cma)
    hs = [
        harness(
            collectives=CollectivesTcp(hostname="localhost", timeout=WAIT),
            world_size_mode=WorldSizeMode.FIXED_WITH_SPARES,
        )
        for _ in range(3)
    ]
    data = [
        (np.random.default_rng(40 + r).standard_normal(100003) * 5).astype(np.float32)
        for r in range(3)
    ]

    def run(r):
        h = hs[r]
        q = quorum_result(max_rank=r, max_world_size=3, replica_rank=r, replica_world_size=3)
        q.store_address = f"{store_server.address()}/srcspare{cma}"
        h.client._quorum.return_value = q
        h.manager.start_quorum()
        h.manager.wait_quorum()
        assert h.manager.takes_sources()
        before = h.manager.ring_account()["from_source"]
        dst = np.full(data[r].size, np.nan, np.float32)
        out = h.manager.allreduce_many([dst], sources=[_segments(data[r], "several")]).wait()[0]
        assert h.manager.errored() is None
        return out, h.manager.ring_account()["from_source"] - before

    with ThreadPoolExecutor(max_workers=3) as ex:
        outs = list(ex.map(run, range(3)))
    expect = data[0] + data[1]
    np.divide(expect, 2, out=expect)
    for out, _ in outs:
        np.testing.assert_array_equal(_bits(out), _bits(expect))
    assert [n for _, n in outs] == [1, 1, 0]
