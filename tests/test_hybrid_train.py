"""The hybrid through ``TrainStep``, a dp x fsdp mesh and the Manager (the layer
kinds, the reference and the share are ``tests/test_hybrid.py``'s): a file of
its own, so that these three compile-heavy tests are handed to a worker of
their own late in a run and do not keep ``test_hybrid.py``'s worker for
minutes beside the timing-sensitive tiers."""

from concurrent.futures import ThreadPoolExecutor
from datetime import timedelta

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from tests.test_hybrid import REPEATING, RTOL, grad_errors, make
from torchft_tpu.models.transformer import TransformerConfig, _held_row_bound, loss_and_stats, loss_fn
from torchft_tpu.parallel.mesh import MeshConfig, make_mesh
from torchft_tpu.parallel.train_step import TrainStep


def test_a_dp_x_fsdp_mesh_gives_the_unsharded_loss():
    cfg, params, tokens, _ = make("repeating", seq=32)
    tokens = jnp.concatenate([tokens, tokens[::-1]], axis=0)  # 4 sequences over dp x fsdp = 4
    want, stats_want = jax.jit(loss_and_stats, static_argnums=2)(params, tokens, cfg)
    mesh = make_mesh(MeshConfig(dp=2, fsdp=2), devices=jax.devices()[:4])
    ts = TrainStep(cfg, optax.adamw(1e-2), mesh)
    placed = jax.device_put(params, ts._param_shardings)
    loss, grads = ts.grads(placed, ts.shard_batch(tokens))
    assert float(loss) == pytest.approx(float(want), rel=1e-5)
    np.testing.assert_array_equal(ts.last_stats["tokens_per_expert"], stats_want["tokens_per_expert"])
    np.testing.assert_array_equal(ts.last_stats["rows_held"], stats_want["rows_held"])
    g_want = jax.jit(jax.grad(loss_fn), static_argnums=2)(params, tokens, cfg)
    assert max(jax.tree_util.tree_leaves(grad_errors(grads, g_want))) < RTOL


def test_the_fused_step_learns_and_keeps_the_rows_held():
    cfg, _, tokens, _ = make("repeating", seq=32)
    mesh = make_mesh(MeshConfig(), devices=jax.devices()[:1])
    ts = TrainStep(cfg, optax.adamw(1e-2), mesh)
    params = ts.init_params(jax.random.PRNGKey(0))
    opt = ts.init_opt(params)
    losses = []
    for _ in range(6):
        loss, params, opt = ts.step(params, opt, ts.shard_batch(tokens))
        losses.append(float(loss))
    assert losses[-1] < losses[0] - 0.3
    stats = ts.last_stats
    assert set(stats) == {"tokens_per_expert", "balance_loss", "rows_held", "window_live_share"}
    load = np.asarray(stats["tokens_per_expert"])
    assert load.shape == (4, 16) and (load.sum(axis=1) == 2 * 32 * 4).all()  # the four expert layers, all 16 experts
    np.testing.assert_array_equal(stats["rows_held"], load[:, :4].sum(axis=1))  # share 0 holds experts 0..3
    # ... over the window's slots, a layer: here the window is every slot (a tile of 512 holds all 256), so about a quarter
    bound = _held_row_bound(cfg, 2 * 32 * 4)
    assert bound == 2 * 32 * 4
    np.testing.assert_allclose(stats["window_live_share"], np.asarray(stats["rows_held"]) / bound, rtol=1e-6)


def hybrid_train_loop(rank, store_addr, runner, total_steps=4):
    from torchft_tpu.collectives import CollectivesTcp
    from torchft_tpu.manager import Manager
    from torchft_tpu.parallel.ft import FTTrainer

    cfg = TransformerConfig(dtype=jnp.float32, **REPEATING)
    mesh = make_mesh(MeshConfig(), devices=jax.devices()[runner.replica_id : runner.replica_id + 1])
    ts = TrainStep(cfg, optax.sgd(0.05), mesh)
    manager = Manager(
        collectives=CollectivesTcp(timeout=timedelta(seconds=10)),
        load_state_dict=None, state_dict=None, min_replica_size=2, replica_id=str(runner.replica_id),
        store_addr=store_addr, rank=rank, world_size=runner.world_size,
        lighthouse_addr=runner.lighthouse_address, timeout=timedelta(seconds=10),
    )
    try:
        trainer = FTTrainer(manager, ts)
        trainer.init(jax.random.PRNGKey(0))
        data = np.random.default_rng(3000 + runner.replica_id * 13)
        committed = []
        while manager.current_step() < total_steps:
            tokens = jnp.asarray(data.integers(0, cfg.vocab_size, (2, 32)), jnp.int32)
            committed.append(trainer.step(tokens)[1])
            runner.failure_injector.check(rank, manager.current_step())
        return {
            "params": jax.tree_util.tree_map(np.asarray, trainer.params),
            "step": manager.current_step(), "committed": committed,
        }
    finally:
        manager.shutdown(wait=False)


def test_two_groups_train_the_hybrid_through_the_manager_and_a_third_heals_from_them():
    """Two replica groups average the hybrid's gradients (a tree of groups by
    kind of layer) over the Manager; one is killed after its second step and
    the trainer started in its place — the third — heals the whole tree from
    the survivor. Both end on the same step with the same parameters, bit
    for bit."""
    from tests.test_integration import FailureInjector, Runner
    from torchft_tpu.coordination import LighthouseServer

    lighthouse = LighthouseServer(bind="[::]:0", min_replicas=2)
    injectors = [FailureInjector(), FailureInjector().fail_at(0, 2)]
    try:
        with ThreadPoolExecutor(max_workers=2) as ex:
            futs = [
                ex.submit(Runner(
                    replica_id=i, lighthouse_address=lighthouse.address(), failure_injector=inj,
                    train_loop=hybrid_train_loop,
                ).run_replica)
                for i, inj in enumerate(injectors)
            ]
            results = [f.result(timeout=240) for f in futs]
    finally:
        lighthouse.shutdown()
    assert injectors[1].count == 1  # the kill happened, and a third trainer took the group's place
    a, b = results[0][0], results[1][0]
    assert a["step"] == b["step"] == 4
    la, ta = jax.tree_util.tree_flatten(a["params"])
    lb, tb = jax.tree_util.tree_flatten(b["params"])
    assert ta == tb and a["params"]["lead"] and a["params"]["periods"]  # groups by kind, leading and scanned
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(x, y)
