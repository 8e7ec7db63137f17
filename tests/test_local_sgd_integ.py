"""LocalSGD / DiLoCo integration tests (local_sgd_integ_test.py analogue).

Same Runner harness as test_integration.py: real lighthouse + managers,
replica groups as threads, recovery via HTTP transport. Asserts model (and
DiLoCo outer-optimizer) state equality across groups after syncs.
"""

from concurrent.futures import ThreadPoolExecutor
from datetime import timedelta
from typing import Any, Dict

import numpy as np
import optax
import pytest

import jax

from tests.test_integration import FailureInjector, Runner, _init_params, _loss_fn
from torchft_tpu.collectives import CollectivesTcp
from torchft_tpu.coordination import LighthouseServer
from torchft_tpu.local_sgd import DiLoCo, LocalSGD
from torchft_tpu.manager import Manager


def local_sgd_train_loop(
    rank: int, store_addr: str, runner: Runner, total_syncs: int = 2
) -> Dict[str, Any]:
    import optax

    mode = runner.train_loop_args.get("mode", "local_sgd")
    sync_every = 3

    holder = {}

    def load_state(sd):
        holder["params"] = sd["params"]
        holder["opt_state"] = sd["opt_state"]

    def save_state():
        return {"params": holder["params"], "opt_state": holder["opt_state"]}

    manager = Manager(
        collectives=CollectivesTcp(timeout=timedelta(seconds=10)),
        load_state_dict=load_state,
        state_dict=save_state,
        min_replica_size=2,
        replica_id=str(runner.replica_id),
        store_addr=store_addr,
        rank=rank,
        world_size=runner.world_size,
        lighthouse_addr=runner.lighthouse_address,
        timeout=timedelta(seconds=10),
        use_async_quorum=False,  # DiLoCo requires sync quorum
    )
    try:
        tx = optax.sgd(0.05)
        holder["params"] = _init_params()
        holder["opt_state"] = tx.init(holder["params"])
        grad_fn = jax.jit(jax.grad(_loss_fn))
        apply_fn = jax.jit(
            lambda p, o, g: (
                lambda u: (optax.apply_updates(p, u[0]), u[1])
            )(tx.update(g, o, p))
        )

        if mode == "local_sgd":
            wrapper = LocalSGD(manager, sync_every=sync_every)
        else:
            wrapper = DiLoCo(
                manager,
                outer_tx=optax.sgd(0.7, momentum=0.9, nesterov=True),
                sync_every=sync_every,
            )
        wrapper.save(holder["params"])

        # live recovery must carry the wrapper's backup/outer state along
        # with the raw params, or a rejoiner syncs from a stale snapshot
        def load_state_full(sd):
            load_state(sd)
            wrapper.load_state_dict(sd["wrapper"])

        def save_state_full():
            sd = save_state()
            sd["wrapper"] = wrapper.state_dict()
            return sd

        manager.set_state_dict_fns(load_state_full, save_state_full)

        data_rng = np.random.default_rng(2000 + runner.replica_id * 31 + rank)
        while manager.current_step() < total_syncs:
            x = data_rng.standard_normal((8, 3)).astype(np.float32)
            y = data_rng.standard_normal((8, 4)).astype(np.float32)
            grads = grad_fn(holder["params"], x, y)
            holder["params"], holder["opt_state"] = apply_fn(
                holder["params"], holder["opt_state"], grads
            )
            holder["params"] = wrapper.step(holder["params"])
            runner.failure_injector.check(rank, manager.current_step())

        out = {
            "params": jax.tree_util.tree_map(np.asarray, holder["params"]),
            "step": manager.current_step(),
        }
        if mode == "diloco":
            out["outer"] = jax.tree_util.tree_map(
                np.asarray, wrapper.outer_state()
            )
        return out
    finally:
        manager.shutdown(wait=False)


class _StubManager:
    """Single-group manager stand-in: allreduce is identity (average of
    one), commit outcome is scripted."""

    _use_async_quorum = False

    def __init__(self, commits):
        self._commits = list(commits)

    def start_quorum(self):
        pass

    def num_participants(self):
        return 1

    def errored(self):
        return None

    def allreduce(self, arr):
        # match the real Manager.allreduce: unwrap to the single array
        return self.allreduce_many([arr]).then(lambda f: f.value()[0])

    def allreduce_many(self, arrays):
        from torchft_tpu.futures import Future

        for arr in arrays:
            np.divide(arr, self.num_participants(), out=arr)
        return Future.completed(arrays)

    def should_commit(self):
        return self._commits.pop(0)


def test_diloco_outer_step_descends_toward_inner_progress():
    """Locks in the paper-sign pseudogradient (backup − local): with plain
    SGD at lr=1 the outer step must land exactly on the averaged inner
    params; a flipped sign would move *away* from the inner progress."""
    start = {"w": np.zeros(4, dtype=np.float32)}
    inner = {"w": np.full(4, 2.0, dtype=np.float32)}

    diloco = DiLoCo(_StubManager([True]), optax.sgd(1.0), sync_every=1)
    diloco.save(start)
    out = diloco.step(inner)
    np.testing.assert_allclose(out["w"], inner["w"], atol=1e-6)

    # lr=0.5 moves exactly halfway from the backup toward the inner params
    diloco = DiLoCo(_StubManager([True]), optax.sgd(0.5), sync_every=1)
    diloco.save(start)
    out = diloco.step(inner)
    np.testing.assert_allclose(out["w"], np.full(4, 1.0), atol=1e-6)


class _CountingManager(_StubManager):
    """Counts the bytes that cross the replica axis."""

    def __init__(self, commits):
        super().__init__(commits)
        self.bytes = 0

    def allreduce_many(self, arrays):
        self.bytes += sum(int(a.nbytes) for a in arrays)
        return super().allreduce_many(arrays)


@pytest.mark.parametrize("mode", ["local_sgd", "diloco"])
def test_one_exchange_of_the_tree_per_sync_every_steps(mode):
    """The communication these wrappers exist to save: over K * H local
    steps the groups exchange K times, each time the parameter tree's
    bytes once (f32), and vote K times — nothing crosses in between."""
    every, syncs = 4, 3
    manager = _CountingManager([True] * syncs)
    if mode == "local_sgd":
        wrapper = LocalSGD(manager, sync_every=every)
    else:
        wrapper = DiLoCo(manager, optax.sgd(0.7), sync_every=every)
    params = {
        "w": np.ones((8, 16), dtype=np.float32),
        "b": np.ones(16, dtype=np.float32),
    }
    tree_bytes = sum(v.nbytes for v in params.values())
    wrapper.save(params)
    for i in range(every * syncs):
        params = wrapper.step(params)
        done = (i + 1) // every
        assert manager.bytes == done * tree_bytes
        assert len(manager._commits) == syncs - done


def test_local_sgd_backup_does_not_alias_live_params():
    """Rollback safety: after a committed sync the caller keeps training
    (possibly in place) on the returned params; a later failed commit must
    restore the synced snapshot, not the mutated buffer."""
    lsgd = LocalSGD(_StubManager([True, False, False]), sync_every=1)
    params = {"w": np.full(4, 3.0, dtype=np.float32)}
    lsgd.save(params)
    params["w"][...] = 5.0  # in-place update before the first sync
    synced = lsgd.step(params)  # commit=True: backup snapshots 5.0
    np.testing.assert_array_equal(synced["w"], np.full(4, 5.0))
    synced["w"][...] = 9.0  # in-place inner steps after the sync
    restored = lsgd.step(synced)  # commit=False: roll back to the snapshot
    np.testing.assert_array_equal(restored["w"], np.full(4, 5.0))
    # the restored tree must not alias the snapshot either: mutate it and
    # fail another sync — the snapshot still restores cleanly
    restored["w"][...] = 9.0
    again = lsgd.step(restored)
    np.testing.assert_array_equal(again["w"], np.full(4, 5.0))


@pytest.mark.parametrize("mode", ["local_sgd", "diloco"])
def test_local_sgd_modes(mode):
    _run_modes(mode, [FailureInjector(), FailureInjector()])


@pytest.mark.parametrize("mode", ["local_sgd", "diloco"])
def test_local_sgd_modes_recovery(mode):
    """Kill group 0 after its first committed sync: the restart heals the
    wrapper's backup (and DiLoCo outer state) from the survivor, and its
    stale local params are replaced by the received backup at the next
    sync (LocalSGD._just_healed) — final states must still be identical
    (the reference's local_sgd_integ recovery bar)."""
    _run_modes(mode, [FailureInjector().fail_at(0, 1), FailureInjector()])


def _run_modes(mode, injectors):
    lighthouse = LighthouseServer(bind="[::]:0", min_replicas=2)
    try:
        with ThreadPoolExecutor(max_workers=2) as ex:
            futs = [
                ex.submit(
                    Runner(
                        replica_id=i,
                        lighthouse_address=lighthouse.address(),
                        failure_injector=inj,
                        train_loop=local_sgd_train_loop,
                        train_loop_args={"mode": mode},
                    ).run_replica
                )
                for i, inj in enumerate(injectors)
            ]
            results = [f.result(timeout=120) for f in futs]
    finally:
        lighthouse.shutdown()

    a, b = results[0][0], results[1][0]
    for key in a["params"]:
        np.testing.assert_array_equal(a["params"][key], b["params"][key])
    if mode == "diloco":
        la = jax.tree_util.tree_leaves(a["outer"])
        lb = jax.tree_util.tree_leaves(b["outer"])
        for x, y in zip(la, lb):
            np.testing.assert_array_equal(x, y)
