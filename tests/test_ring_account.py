"""The ring's account of an allreduce (``collectives.RING_ACCOUNT``;
native/dataplane.h's ``DpAccount``): what ``tft.exchange.ring.account``
carries for one op and ``ring_account()`` totals, on ``test_collectives``'s
rank threads sharing one store. Bytes are held exactly; seconds to a
direction and a margin of hundreds of milliseconds, never to a rate.
"""

import threading
import time
from datetime import timedelta

import numpy as np
import pytest

from torchft_tpu.collectives import (
    RING_ACCOUNT,
    Collectives,
    CollectivesDummy,
    CollectivesTcp,
    ErrorSwallowingCollectives,
    ReduceOp,
)
from torchft_tpu.telemetry import tracing
from tests.test_collectives import _run_world, store  # noqa: F401 — the fixture

SECONDS = [k for k in RING_ACCOUNT if k.endswith("_s") and k != "slowest_stripe_s"]
WAIT = timedelta(seconds=20)


def _growth(c, op):
    """(what ``op()`` returned, the growth of ``c.ring_account()`` over it,
    its wall seconds)."""
    before = c.ring_account()
    t0 = time.perf_counter()
    out = op()
    wall = time.perf_counter() - t0
    after = c.ring_account()
    return out, {k: after[k] - before[k] for k in after}, wall


def _evenly(world):
    """Elements that every stripe and every chunk divides evenly: 4 stripes x
    16-element alignment x world chunks."""
    return 4 * 16 * world * 257


@pytest.mark.parametrize("cma", ["1", "0"])
@pytest.mark.parametrize("world", [2, 4])
def test_bytes_are_exact_and_seconds_fit_inside_the_op(store, monkeypatch, cma, world):
    monkeypatch.setenv("TORCHFT_DP_CMA", cma)
    n = _evenly(world)

    def fn(c, rank):
        a = np.full(n, rank + 1.0, np.float32)
        out, acct, wall = _growth(c, lambda: c.allreduce([a], ReduceOp.SUM, world).wait(WAIT)[0])
        small, acct1, _ = _growth(c, lambda: c.allreduce([np.ones(67, np.float32)]).wait(WAIT)[0])
        return c.plane_info(), out, acct, wall, acct1

    for plane, out, acct, wall, acct1 in _run_world(store, world, fn, f"rab{cma}{world}"):
        assert plane == ("cma" if cma == "1" else "tcp-striped")
        np.testing.assert_array_equal(out, np.full(n, (world + 1) / 2, np.float32))
        assert set(acct) == set(RING_ACCOUNT)
        moved = 2 * (world - 1) * (4 * n) // world
        if plane == "cma":
            assert acct["pull_bytes"] == moved and acct["pump_bytes"] == 0
            assert acct["pump_s"] == 0 and acct["pull_s"] > 0
        else:
            assert acct["pump_bytes"] == moved and acct["pull_bytes"] == 0
            assert acct["pump_s"] > 0
            assert acct["desc_wait_s"] == acct["pull_s"] == acct["ack_wait_s"] == 0
        assert acct["reduce_bytes"] == (world - 1) * (4 * n) // world
        assert acct["codec_s"] == 0  # f32 on the wire
        assert acct["stripes"] == 4 and acct1["stripes"] == 1  # 67 < 4 x 64 elements
        assert all(v >= 0 for v in acct.values())
        # the mean over parallel stripes, and the slowest of them, fit inside
        # the op's wall time
        assert sum(acct[k] for k in SECONDS) <= acct["slowest_stripe_s"] + 1e-9
        assert acct["slowest_stripe_s"] <= wall


@pytest.mark.parametrize("cma", ["1", "0"])
def test_totals_are_monotonic_and_the_sum_of_the_ops_accounts(store, monkeypatch, cma):
    """Each op's account goes out as a zero-length ``exchange.ring.account``
    on the op thread; the totals are their sum, field by field."""
    monkeypatch.setenv("TORCHFT_DP_CMA", cma)
    by_thread = {}
    real = tracing.annotate

    def spy(name, **stats):
        if name == "exchange.ring.account":
            by_thread.setdefault(threading.get_ident(), []).append(stats)
        return real(name, **stats)

    monkeypatch.setattr(tracing, "annotate", spy)
    world, sizes = 2, (100003, 67, _evenly(2))

    def fn(c, rank):
        seen = [c.ring_account()]
        for size in sizes:
            c.allreduce([np.ones(size, np.float32)], ReduceOp.SUM, 2).wait(WAIT)
            seen.append(c.ring_account())
        return seen

    outs = _run_world(store, world, fn, f"rat{cma}")
    assert len(by_thread) == world and threading.get_ident() not in by_thread
    sums = []
    for accounts in by_thread.values():
        assert [a["bytes"] for a in accounts] == [4 * s for s in sizes]
        assert {a["plane"] for a in accounts} == {"cma" if cma == "1" else "tcp-striped"}
        sums.append({k: sum(a[k] for a in accounts) for k in RING_ACCOUNT})
    for seen in outs:
        assert all(v == 0 for v in seen[0].values())
        for a, b in zip(seen, seen[1:]):
            assert all(b[k] >= a[k] for k in RING_ACCOUNT)
        (mine,) = [s for s in sums if s["reduce_s"] == pytest.approx(seen[-1]["reduce_s"], abs=1e-12)]
        assert {k: seen[-1][k] for k in RING_ACCOUNT} == pytest.approx(mine, abs=1e-9)
        sums.remove(mine)


@pytest.mark.parametrize("cma", ["1", "0"])
@pytest.mark.parametrize("world", [2, 4])
def test_a_late_rank_makes_its_neighbours_wait_not_itself(store, monkeypatch, cma, world):
    monkeypatch.setenv("TORCHFT_DP_CMA", cma)
    late, n = world - 1, _evenly(world)

    def fn(c, rank):
        c.allreduce([np.ones(n, np.float32)]).wait(WAIT)  # everyone is here
        if rank == late:
            time.sleep(0.5)
        _, acct, _ = _growth(c, lambda: c.allreduce([np.ones(n, np.float32)]).wait(WAIT))
        # the pump cannot tell waiting from moving: its one number holds both
        return acct["desc_wait_s"] + acct["ack_wait_s"] + acct["pump_s"]

    waited = _run_world(store, world, fn, f"ral{cma}{world}")
    others = [w for rank, w in enumerate(waited) if rank != late]
    assert min(others) >= 0.3, waited
    assert waited[late] < min(others), waited


@pytest.mark.parametrize("cma", ["1", "0"])
def test_an_op_that_met_its_deadline_still_has_an_account(store, monkeypatch, cma):
    monkeypatch.setenv("TORCHFT_DP_CMA", cma)
    gave_up = threading.Event()

    def fn(c, rank):
        if rank == 1:  # never joins the op
            assert gave_up.wait(30)
            return None
        try:
            _, acct, _ = _growth(
                c, lambda: pytest.raises(TimeoutError, c.allreduce([np.ones(4096, np.float32)]).wait, WAIT)
            )
        finally:
            gave_up.set()
        return acct

    acct, _ = _run_world(store, 2, fn, f"rad{cma}", timeout=timedelta(seconds=2))
    # the two seconds went where the hop stood: waiting for the neighbour's
    # descriptor, or in the pump
    assert acct["desc_wait_s" if cma == "1" else "pump_s"] >= 1.5, acct
    assert acct["stripes"] == 4 and acct["reduce_bytes"] == 0


@pytest.mark.parametrize("codec", ["f32", "bfloat16"])
def test_the_python_ring_books_what_it_measures_anyway(store, codec):
    n = 4 * 4099

    def fn(c, rank):
        a = np.full(n, rank + 1.0, np.float32)
        _, acct, wall = _growth(c, lambda: c.allreduce([a], ReduceOp.SUM, 2).wait(WAIT))
        return c.plane_info(), acct, wall

    for plane, acct, wall in _run_world(store, 2, fn, f"rap{codec}", native_plane=False, wire_dtype=codec):
        assert plane == "python-ring"
        assert 0 < acct["pump_s"] <= wall
        assert (acct["codec_s"] > 0) == (codec != "f32")
        if codec == "f32":
            assert acct["pump_bytes"] == 4 * n  # 2 x (w - 1) / w of the bytes
        assert acct["pull_s"] == acct["desc_wait_s"] == acct["stripes"] == 0


def test_wrappers_forward_and_a_backend_without_an_account_says_nothing(store):
    inner = CollectivesTcp(timeout=timedelta(seconds=5), hostname="localhost")
    wrap = ErrorSwallowingCollectives(inner)
    wrap.configure(f"{store.address()}/raw", 0, 1)
    try:
        wrap.allreduce([np.ones(8, np.float32)], ReduceOp.SUM, 2).wait()
        assert wrap.ring_account() == dict.fromkeys(RING_ACCOUNT, 0)  # a world of one: no hop
        inner._ring_account["pull_s"] = 1.5
        assert wrap.ring_account()["pull_s"] == 1.5
        wrap.ring_account()["pull_s"] = 9.0  # a copy: the totals are the op thread's
        assert inner.ring_account()["pull_s"] == 1.5
    finally:
        wrap.shutdown()
    assert CollectivesDummy(rank=0, world_size=1).ring_account() == {}
    assert Collectives.ring_account(inner) == {}


def test_the_native_plane_hands_out_its_last_account_by_name():
    from torchft_tpu._native import NativeDataPlane

    dp = NativeDataPlane(0, 1, 4)
    try:
        a = np.full(64, 6.0, np.float32)
        dp.allreduce(a.ctypes.data, a.size, "sum", divisor=3)
        np.testing.assert_array_equal(a, np.full(64, 2.0, np.float32))
        assert dp.last_account() == dict.fromkeys(NativeDataPlane.ACCOUNT, 0)
        assert len(NativeDataPlane.ACCOUNT) == len(RING_ACCOUNT)
    finally:
        dp.close()
    assert dp.last_account() == {}  # closed: nothing, and no error
