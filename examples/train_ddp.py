"""Fault-tolerant data-parallel training example — the reference
train_ddp.py analogue (/root/reference/train_ddp.py:34-152), jax-native.

One process per replica group (within a group, TPU chips are an inner jax
Mesh — see torchft_tpu.parallel). Configure via env:

    TORCHFT_LIGHTHOUSE=host:port   lighthouse address
    REPLICA_GROUP_ID=0             this group's id
    NUM_REPLICA_GROUPS=2           total groups (min replicas = 2 here)
    STEPS=20                       steps to train
    CKPT_DIR=/path                 enable periodic disk checkpoints there
    CKPT_EVERY=5                   checkpoint cadence (committed steps)
    DATA_PLANE=tcp|device-dist     cross-group backend (device-dist needs
                                   launcher --shared-runtime: one
                                   multi-controller runtime, psum on ICI)

Run a 2-group session (3 terminals)::

    python -m torchft_tpu.lighthouse --bind "[::]:29510" --min_replicas 2
    REPLICA_GROUP_ID=0 TORCHFT_LIGHTHOUSE=$(hostname):29510 python examples/train_ddp.py
    REPLICA_GROUP_ID=1 TORCHFT_LIGHTHOUSE=$(hostname):29510 python examples/train_ddp.py

Kill either trainer mid-run and restart it: it rejoins the quorum and
live-heals from the survivor, costing the cohort at most one step.

Two complementary recovery mechanisms, as in the reference: the live
quorum heal above covers *partial* failures (a peer survives to serve
state), and the periodic disk checkpoint covers *total* failures — with
CKPT_DIR set, every CKPT_EVERY committed steps the group writes
{manager state, params+optimizer, sampler position} atomically
(reference workflow: train_ddp.py:141-148, manager.py:83-85 docs) and a
restarted process resumes from it automatically, continuing bit-exactly.
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
from torchft_tpu.manager import Manager
from torchft_tpu.optim import ManagedOptimizer
from torchft_tpu.store import StoreServer

logging.basicConfig(
    level=logging.INFO, format="%(asctime)s %(name)s: %(message)s"
)
logger = logging.getLogger("train_ddp")


def make_dataset(n=4096, d=32, classes=10, seed=7):
    """Synthetic classification set (CIFAR stand-in), identical everywhere."""
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
    num_groups = int(os.environ.get("NUM_REPLICA_GROUPS", 2))
    steps = int(os.environ.get("STEPS", 20))
    batch = int(os.environ.get("BATCH", 64))
    ckpt_dir = os.environ.get("CKPT_DIR")
    ckpt_every = int(os.environ.get("CKPT_EVERY", 5))
    # launcher env contract (torchelastic analogue): a launcher-provided
    # store + RANK/WORLD_SIZE means this process is one rank of a
    # multi-process group; standalone runs make their own 1-rank group
    rank = int(os.environ.get("RANK", 0))
    world_size = int(os.environ.get("WORLD_SIZE", 1))
    store_addr = os.environ.get("TORCHFT_STORE_ADDR")
    store = None
    if store_addr is None:
        store = StoreServer()
        store_addr = store.address()
    # multi-host group: join the group-wide jax runtime (no-op without
    # TORCHFT_JAX_COORDINATOR); this example keeps compute replicated per
    # rank — a sharded inner mesh is what torchft_tpu.parallel is for
    from torchft_tpu.parallel.multihost import initialize_group

    initialize_group()

    # DATA_PLANE=tcp (default): host ring with the native striped/CMA
    # fast path. DATA_PLANE=device-dist: all groups share ONE
    # multi-controller jax runtime (launcher --shared-runtime) and the
    # averaging psum rides ICI — see README's plane-selection table.
    if os.environ.get("DATA_PLANE", "tcp") == "device-dist":
        from torchft_tpu.collectives_device_dist import (
            CollectivesDeviceDist,
            init_from_env,
        )

        if not init_from_env():
            raise SystemExit(
                "DATA_PLANE=device-dist requires the shared-runtime cohort "
                "env (run under `python -m torchft_tpu.launcher "
                "--shared-runtime`); without it every group would form its "
                "own 1-process runtime and quorum configure() would reject "
                "the cohort mismatch on every epoch"
            )
        collectives = CollectivesDeviceDist(timeout=timedelta(seconds=30))
    else:
        collectives = CollectivesTcp(timeout=timedelta(seconds=30))

    manager = Manager(
        collectives=collectives,
        load_state_dict=None,  # wired by ManagedOptimizer.init
        state_dict=None,
        min_replica_size=min(2, num_groups),
        replica_id=f"train_ddp_{replica_group}",
        store_addr=store_addr,
        rank=rank,
        world_size=world_size,
        timeout=timedelta(seconds=30),
    )

    x, y = make_dataset()
    opt = ManagedOptimizer(manager, optax.adam(1e-3))
    opt.init(init_params())
    sampler = DistributedSampler(
        len(x),
        replica_group=replica_group,
        num_replica_groups=num_groups,
        shuffle=True,
        seed=0,
    )
    value_and_grad = jax.jit(jax.value_and_grad(loss_fn))

    # periodic disk checkpoints (total-failure recovery; live quorum
    # healing covers partial failures): one writer per group, every rank
    # restores from the shared snapshot, restore happens BEFORE the first
    # quorum so a resumed group reports its true step and heals forward
    ckpt = None
    if ckpt_dir:
        from torchft_tpu.checkpointing.disk import DiskCheckpointer

        ckpt = DiskCheckpointer(
            ckpt_dir,
            manager,
            state_dict=lambda: {
                "opt": opt.state_dict(),
                "sampler": sampler.state_dict(),
            },
            load_state_dict=lambda s: (
                opt.load_state_dict(s["opt"]),
                sampler.load_state_dict(s["sampler"]),
            ),
            every=ckpt_every,
            tag=f"group{replica_group}",
            is_writer=(rank == 0),
        )
        ckpt.restore()

    import time

    try:
        prev_step = manager.current_step()
        while manager.current_step() < steps:
            sampler.set_epoch(manager.current_step())
            idx = np.fromiter(iter(sampler), dtype=np.int64)[:batch]

            opt.begin_step()  # async quorum overlaps the forward pass
            loss, grads = value_and_grad(opt.params, x[idx], y[idx])
            opt.step(grads)
            if manager.current_step() == prev_step:
                # failed commit (e.g. waiting for enough replicas): back
                # off instead of hammering the quorum in a busy loop
                time.sleep(0.2)
            prev_step = manager.current_step()
            logger.info(
                "step=%d batches_committed=%d participants=%d loss=%.4f",
                manager.current_step(),
                manager.batches_committed(),
                manager.num_participants(),
                float(loss),
            )
            if ckpt is not None:
                ckpt.maybe_save()
        final = jax.tree_util.tree_map(lambda a: np.asarray(a).sum(), opt.params)
        logger.info("done: step=%d param_checksum=%.6f",
                    manager.current_step(),
                    float(sum(float(v) for v in jax.tree_util.tree_leaves(final))))
    finally:
        manager.shutdown(wait=False)
        if store is not None:
            store.shutdown()


if __name__ == "__main__":
    main()
