"""Subprocess-isolated collectives tests (Baby PG tests analogue:
process_group_test.py:346-397, multiprocessing_test.py)."""

import multiprocessing as mp
import time
from concurrent.futures import ThreadPoolExecutor
from datetime import timedelta

import numpy as np
import pytest

from torchft_tpu.collectives import CollectivesTcp, ReduceOp
from torchft_tpu.multiprocessing import MonitoredQueue
from torchft_tpu.proxy import CollectivesProxy
from torchft_tpu.store import StoreServer


def make_tcp_backend():
    return CollectivesTcp(timeout=timedelta(seconds=10))


class TestMonitoredQueue:
    def test_dead_process_detection(self):
        ctx = mp.get_context("spawn")
        q = ctx.Queue()
        proc = ctx.Process(target=time.sleep, args=(0.2,))
        proc.start()
        proc.join()
        mq = MonitoredQueue(q)
        with pytest.raises(RuntimeError, match="dead"):
            mq.get(proc, timeout=5.0)

    def test_exception_reraise(self):
        ctx = mp.get_context("spawn")
        q = ctx.Queue()
        proc = ctx.Process(target=time.sleep, args=(5,))
        proc.start()
        try:
            q.put(ValueError("boom"))
            mq = MonitoredQueue(q)
            with pytest.raises(ValueError, match="boom"):
                mq.get(proc, timeout=5.0)
        finally:
            proc.terminate()
            proc.join()


@pytest.fixture
def proxy_pair():
    store = StoreServer()
    proxies = [
        CollectivesProxy(make_tcp_backend, timeout=timedelta(seconds=20))
        for _ in range(2)
    ]
    with ThreadPoolExecutor(max_workers=2) as pool:
        list(pool.map(lambda i: proxies[i].configure(store.address(), i, 2), range(2)))
    yield proxies
    for p in proxies:
        p.shutdown()
    store.shutdown()


class TestCollectivesProxy:
    def test_plane_info_reports_inner_backend(self, proxy_pair):
        """The proxy labels the child's LIVE transport (proxy:<inner>), so
        a silent CMA->TCP fallback stays visible on the dashboard even
        under the kill-safe deployment (ADVICE r5 #2)."""
        for p in proxy_pair:
            info = p.plane_info()
            assert info.startswith("proxy:") and len(info) > len("proxy:"), info
            # the inner label is the TCP backend's routing, not a class name
            assert "CollectivesTcp" not in info

    def test_allreduce_shm_path(self, proxy_pair):
        """Buckets above the shm threshold ride shared memory (one copy
        each way, no pickle) and still land in-place in caller buffers —
        the reference's _maybe_share_tensors (process_group.py:775-786)."""
        import glob

        n = 1 << 16  # 256 KB of f32 — well over _SHM_MIN_BYTES
        a = np.full(n, 1.0, dtype=np.float32)
        b = np.full(n, 2.0, dtype=np.float32)
        # only python shm segments (psm_*) count; other processes' /dev/shm
        # churn (semaphores etc.) must not flake this
        before = set(glob.glob("/dev/shm/psm_*"))
        w0 = proxy_pair[0].allreduce([a], ReduceOp.SUM)
        w1 = proxy_pair[1].allreduce([b], ReduceOp.SUM)
        w0.wait(timeout=timedelta(seconds=20))
        w1.wait(timeout=timedelta(seconds=20))
        np.testing.assert_array_equal(a, np.full(n, 3.0, np.float32))
        np.testing.assert_array_equal(b, np.full(n, 3.0, np.float32))
        # segments are unlinked after copy-back (no /dev/shm leak); poll
        # briefly in case another local test's segment is mid-flight
        deadline = time.monotonic() + 2.0
        while time.monotonic() < deadline:
            leaked = set(glob.glob("/dev/shm/psm_*")) - before
            if not leaked:
                break
            time.sleep(0.05)
        assert not leaked, leaked

    def test_allreduce_shm_bfloat16(self, proxy_pair):
        import ml_dtypes

        n = 1 << 16
        a = np.full(n, 1.0, dtype=ml_dtypes.bfloat16)
        b = np.full(n, 2.0, dtype=ml_dtypes.bfloat16)
        w0 = proxy_pair[0].allreduce([a], ReduceOp.AVG)
        w1 = proxy_pair[1].allreduce([b], ReduceOp.AVG)
        w0.wait(timeout=timedelta(seconds=20))
        w1.wait(timeout=timedelta(seconds=20))
        np.testing.assert_array_equal(a.astype(np.float32), 1.5)
        np.testing.assert_array_equal(b.astype(np.float32), 1.5)

    @pytest.mark.parametrize("n", [3, 1 << 16], ids=["pickle", "shm"])
    def test_allreduce_forwards_the_divisor(self, proxy_pair, n):
        """The divisor goes to the child with the op, on the pickle path
        and the shared-memory path: its backend takes the average."""
        a = np.full(n, 1.0, dtype=np.float32)
        b = np.full(n, 4.0, dtype=np.float32)
        w0 = proxy_pair[0].allreduce([a], ReduceOp.SUM, 2)
        w1 = proxy_pair[1].allreduce([b], ReduceOp.SUM, divisor=2)
        w0.wait(timeout=timedelta(seconds=20))
        w1.wait(timeout=timedelta(seconds=20))
        np.testing.assert_array_equal(a, np.full(n, 2.5, np.float32))
        np.testing.assert_array_equal(b, np.full(n, 2.5, np.float32))
        # the child's ring is out of the parent's sight
        assert proxy_pair[0].avg_in_ring_ops() == 0

    def test_allreduce_in_place(self, proxy_pair):
        a = np.array([1.0, 2.0], dtype=np.float32)
        b = np.array([3.0, 4.0], dtype=np.float32)
        w0 = proxy_pair[0].allreduce([a], ReduceOp.SUM)
        w1 = proxy_pair[1].allreduce([b], ReduceOp.SUM)
        w0.wait(timeout=timedelta(seconds=20))
        w1.wait(timeout=timedelta(seconds=20))
        np.testing.assert_allclose(a, [4.0, 6.0])  # caller buffer mutated
        np.testing.assert_allclose(b, [4.0, 6.0])

    def test_child_kill_surfaces_quickly(self, proxy_pair):
        proxy_pair[0].kill_child()
        t0 = time.monotonic()
        w = proxy_pair[0].allreduce(
            [np.ones(2, dtype=np.float32)], ReduceOp.SUM
        )
        with pytest.raises(Exception):
            w.wait(timeout=timedelta(seconds=10))
        assert time.monotonic() - t0 < 5.0

    def test_manager_over_proxy_kill_child_recovers_without_restart(self):
        """The Baby-PG story end to end (round-1 review 'what's weak' #2):
        Manager drives subprocess-isolated collectives; a SIGKILLed child
        mid-run latches an error, the failed commit requests a data-plane
        flush, the next quorum bumps quorum_id for BOTH groups, configure()
        respawns the child, and training recovers to identical states —
        no trainer process/thread restart involved."""
        from torchft_tpu.coordination import LighthouseServer
        from torchft_tpu.manager import Manager
        from torchft_tpu.optim import ManagedOptimizer

        from tests.test_integration import _init_params, _loss_fn

        import jax
        import optax

        lighthouse = LighthouseServer(bind="[::]:0", min_replicas=2)
        stores = [StoreServer() for _ in range(2)]
        kill_once = {"done": False}
        total_steps = 4

        def loop(gid):
            manager = Manager(
                collectives=CollectivesProxy(
                    make_tcp_backend, timeout=timedelta(seconds=20)
                ),
                load_state_dict=None,
                state_dict=None,
                min_replica_size=2,
                replica_id=str(gid),
                store_addr=stores[gid].address(),
                rank=0,
                world_size=1,
                lighthouse_addr=lighthouse.address(),
                timeout=timedelta(seconds=15),
                quorum_timeout=timedelta(seconds=30),
            )
            try:
                opt = ManagedOptimizer(manager, optax.sgd(0.05))
                opt.init(_init_params())
                grad_fn = jax.jit(jax.grad(_loss_fn))
                rng = np.random.default_rng(77 + gid)
                commits = []
                for _ in range(40):
                    opt.begin_step()
                    x = rng.standard_normal((8, 3)).astype(np.float32)
                    y = rng.standard_normal((8, 4)).astype(np.float32)
                    if (
                        gid == 1
                        and manager.current_step() == 2
                        and not kill_once["done"]
                    ):
                        kill_once["done"] = True
                        manager._collectives.kill_child()
                    grads = grad_fn(opt.params, x, y)
                    before = manager.current_step()
                    opt.step(grads)
                    commits.append(manager.current_step() > before)
                    if manager.current_step() >= total_steps:
                        break
                return {
                    "params": jax.tree_util.tree_map(np.asarray, opt.params),
                    "commits": commits,
                    "step": manager.current_step(),
                }
            finally:
                manager.shutdown(wait=False)

        try:
            with ThreadPoolExecutor(max_workers=2) as ex:
                a, b = list(ex.map(loop, range(2)))
        finally:
            for s in stores:
                s.shutdown()
            lighthouse.shutdown()

        assert a["step"] >= total_steps and b["step"] >= total_steps
        # the killed-child step must NOT have committed on either group...
        assert False in a["commits"] and False in b["commits"]
        # ...and both groups converge to bit-identical params afterwards
        for key in a["params"]:
            np.testing.assert_array_equal(a["params"][key], b["params"][key])

    def test_reconfigure_respawns(self, proxy_pair):
        store2 = StoreServer()
        try:
            proxy_pair[0].kill_child()
            old_pids = [p._proc.pid for p in proxy_pair]
            with ThreadPoolExecutor(max_workers=2) as pool:
                list(
                    pool.map(
                        lambda i: proxy_pair[i].configure(store2.address(), i, 2),
                        range(2),
                    )
                )
            assert [p._proc.pid for p in proxy_pair] != old_pids
            a = np.ones(4, dtype=np.float32)
            b = np.ones(4, dtype=np.float32)
            w0 = proxy_pair[0].allreduce([a], ReduceOp.AVG)
            w1 = proxy_pair[1].allreduce([b], ReduceOp.AVG)
            w0.wait(timeout=timedelta(seconds=20))
            w1.wait(timeout=timedelta(seconds=20))
            np.testing.assert_allclose(a, 1.0)
        finally:
            store2.shutdown()
