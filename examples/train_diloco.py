"""DiLoCo training example — four replica groups, one outer step per H.

Communication-reduced fault-tolerant training (arxiv 2311.08105): each
replica group runs ``SYNC_EVERY`` purely-local AdamW steps, then the
groups average *pseudogradients* through the quorum and apply an outer
Nesterov-SGD step. Crossing the elastic axis once per H inner steps is
what makes cross-datacenter (DCN-connected) replica groups practical.

Env (same launcher contract as train_ddp.py):

    TORCHFT_LIGHTHOUSE=host:port   lighthouse address
    REPLICA_GROUP_ID / NUM_REPLICA_GROUPS (default 4)
    OUTER_STEPS=4                  outer (sync) steps to run
    SYNC_EVERY=8                   inner steps between syncs

Run 4 groups under the launcher (``--min-replicas 2`` mirrors the
Manager's ``min_replica_size`` so survivors keep committing while a
killed group is down — the launcher's default lighthouse would otherwise
require all 4 to participate)::

    python -m torchft_tpu.launcher --groups 4 --min-replicas 2 -- \\
        python examples/train_diloco.py

Kill any group mid-run: the survivors' next sync commits without it (down
to min_replica_size), and a restarted group rejoins at the next quorum —
the failed group's inner steps are the only work lost.

Reference workflow: torchft/local_sgd.py:177-239 + train_ddp.py loop.
"""

import logging
import os
import sys
from datetime import timedelta

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from torchft_tpu.utils.compile_cache import place_compile_cache

place_compile_cache()  # before first use of jax; children inherit it
import jax
import optax

from torchft_tpu.collectives import CollectivesTcp
from torchft_tpu.data import DistributedSampler
from torchft_tpu.local_sgd import DiLoCo
from torchft_tpu.manager import Manager
from torchft_tpu.store import StoreServer

logging.basicConfig(level=logging.INFO, format="%(asctime)s %(name)s: %(message)s")
logger = logging.getLogger("train_diloco")


def make_dataset(n=4096, d=32, classes=10, seed=7):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((n, d)).astype(np.float32)
    w_true = rng.standard_normal((d, classes)).astype(np.float32)
    y = np.argmax(x @ w_true + 0.1 * rng.standard_normal((n, classes)), axis=1)
    return x, y.astype(np.int32)


def init_params(d=32, hidden=64, classes=10, seed=42):
    rng = np.random.default_rng(seed)
    scale = 1.0 / np.sqrt(d)
    return {
        "w1": (scale * rng.standard_normal((d, hidden))).astype(np.float32),
        "b1": np.zeros(hidden, np.float32),
        "w2": (scale * rng.standard_normal((hidden, classes))).astype(np.float32),
        "b2": np.zeros(classes, np.float32),
    }


def loss_fn(params, x, y):
    h = jax.nn.relu(x @ params["w1"] + params["b1"])
    logits = h @ params["w2"] + params["b2"]
    return optax.softmax_cross_entropy_with_integer_labels(logits, y).mean()


def main() -> None:
    replica_group = int(os.environ.get("REPLICA_GROUP_ID", 0))
    num_groups = int(os.environ.get("NUM_REPLICA_GROUPS", 4))
    outer_steps = int(os.environ.get("OUTER_STEPS", 4))
    sync_every = int(os.environ.get("SYNC_EVERY", 8))
    batch = int(os.environ.get("BATCH", 64))

    store_addr = os.environ.get("TORCHFT_STORE_ADDR")
    store = None
    if store_addr is None:
        store = StoreServer()
        store_addr = store.address()

    manager = Manager(
        collectives=CollectivesTcp(timeout=timedelta(seconds=30)),
        load_state_dict=None,
        state_dict=None,
        min_replica_size=min(2, num_groups),
        # DiLoCo's outer step must start from a fully-healed state
        # (local_sgd.py:195-199) — sync quorum heals before the sync math
        use_async_quorum=False,
        replica_id=f"diloco_{replica_group}",
        store_addr=store_addr,
        rank=int(os.environ.get("RANK", 0)),
        world_size=int(os.environ.get("WORLD_SIZE", 1)),
        timeout=timedelta(seconds=30),
        # the quorum interval spans a whole inner loop (manager.py
        # docstring guidance: quorum_timeout must cover it)
        quorum_timeout=timedelta(seconds=120),
    )

    x, y = make_dataset()
    inner_tx = optax.adamw(1e-3)
    outer_tx = optax.sgd(0.7, momentum=0.9, nesterov=True)
    state = {"params": init_params()}
    state["inner"] = inner_tx.init(state["params"])
    diloco = DiLoCo(manager, outer_tx, sync_every=sync_every)
    diloco.save(state["params"])

    # live recovery: a rejoining group receives params + the DiLoCo
    # backup/outer-optimizer state from a survivor at its next sync quorum
    def user_state_dict():
        return {"params": state["params"], "diloco": diloco.state_dict()}

    def user_load_state_dict(s):
        state["params"] = s["params"]
        state["inner"] = inner_tx.init(s["params"])
        diloco.load_state_dict(s["diloco"])

    manager.set_state_dict_fns(user_load_state_dict, user_state_dict)

    @jax.jit
    def inner_step(params, opt_state, xb, yb):
        loss, grads = jax.value_and_grad(loss_fn)(params, xb, yb)
        updates, opt_state = inner_tx.update(grads, opt_state, params)
        return loss, optax.apply_updates(params, updates), opt_state

    data_rng_step = 0
    try:
        while manager.current_step() < outer_steps:
            sampler = DistributedSampler(
                len(x),
                replica_group=replica_group,
                num_replica_groups=num_groups,
                shuffle=True,
                seed=0,
            )
            sampler.set_epoch(data_rng_step)
            idx = np.fromiter(iter(sampler), dtype=np.int64)[:batch]
            data_rng_step += 1

            loss, params, inner = inner_step(
                state["params"], state["inner"], x[idx], y[idx]
            )
            state["params"], state["inner"] = params, inner
            synced = diloco.step(params)
            if synced is not params:  # a sync ran (commit or rollback)
                state["params"] = synced
                # inner optimizer restarts from the outer point each round
                # (paper setup: fresh inner state per outer step)
                state["inner"] = inner_tx.init(synced)
                logger.info(
                    "outer step=%d participants=%d inner_loss=%.4f",
                    manager.current_step(),
                    manager.num_participants(),
                    float(loss),
                )
        final = sum(
            float(np.asarray(v).sum())
            for v in jax.tree_util.tree_leaves(state["params"])
        )
        logger.info(
            "done: outer_step=%d param_checksum=%.6f",
            manager.current_step(),
            final,
        )
    finally:
        manager.shutdown(wait=False)
        if store is not None:
            store.shutdown()


if __name__ == "__main__":
    main()
