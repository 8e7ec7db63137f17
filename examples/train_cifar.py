"""Fault-tolerant ResNet-18 CIFAR-10 DDP — the reference's flagship
real-data config ("ResNet-18 CIFAR-10 DDP with kill/rejoin": reference
train_ddp.py:34-80).

TPU-native differences from the torch original: the model is the pure-JAX
NHWC ResNet (models/resnet.py) with functional batch norm — running stats
are explicit state that rides the heal/disk-checkpoint state dict (torch
DDP likewise keeps BN stats local per replica); the dataloader position
derives from the committed step count (torchft_tpu.data.step_indices), so
kill/rejoin can never skip or double-train a sample.

The dataset is a CIFAR-10-shaped on-disk .npz: real CIFAR-10 when a copy
exists at DATA_PATH (zero-egress environments can't download it), else a
deterministic learnable stand-in with the same shapes/dtypes generated
once and shared by every group — either way the input pipeline (disk →
sampler shards → augment → device) is the real one.

Env: TORCHFT_LIGHTHOUSE, REPLICA_GROUP_ID, NUM_REPLICA_GROUPS, STEPS,
BATCH, DATA_PATH, TRACE_PATH, CKPT_DIR, CKPT_EVERY (as train_bytes.py).

Run::

    python -m torchft_tpu.launcher --groups 2 -- python examples/train_cifar.py
"""

import json
import logging
import os
import sys
from datetime import timedelta

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from torchft_tpu.utils.compile_cache import place_compile_cache

place_compile_cache()  # before first use of jax; children inherit it
import jax
import jax.numpy as jnp
import optax

from torchft_tpu.collectives import CollectivesTcp
from torchft_tpu.data import DistributedSampler, step_indices
from torchft_tpu.ddp import allreduce_gradients
from torchft_tpu.manager import Manager
from torchft_tpu.store import StoreServer

logging.basicConfig(
    level=logging.INFO, format="%(asctime)s %(name)s: %(message)s"
)
logger = logging.getLogger("train_cifar")


def ensure_dataset(path: str, n: int = 2048):
    """Load (or deterministically create) a CIFAR-10-shaped dataset:
    images uint8 [N,32,32,3], labels uint8 [N]."""
    if not os.path.exists(path):
        rng = np.random.default_rng(0)
        labels = rng.integers(0, 10, n).astype(np.uint8)
        # class-dependent structure (a colored gradient per class) + noise:
        # learnable, so training loss demonstrably falls
        yy, xx = np.mgrid[0:32, 0:32].astype(np.float32) / 31.0
        base = np.stack([xx, yy, 1.0 - xx], axis=-1)  # [32,32,3]
        phase = (labels.astype(np.float32) / 10.0)[:, None, None, None]
        imgs = 127.5 * (1.0 + np.sin(6.28 * (base[None] + phase)))
        imgs = imgs + rng.normal(0, 16.0, imgs.shape)
        imgs = np.clip(imgs, 0, 255).astype(np.uint8)
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        tmp = path + f".tmp{os.getpid()}"
        np.savez(tmp, images=imgs, labels=labels)
        os.replace(tmp + ".npz", path)  # np.savez appends .npz
    with np.load(path) as z:
        return z["images"], z["labels"]


def augment(imgs: np.ndarray, rng: np.random.Generator) -> np.ndarray:
    """Standard CIFAR augmentation on host: pad-4 random crop + hflip."""
    n = len(imgs)
    padded = np.pad(imgs, ((0, 0), (4, 4), (4, 4), (0, 0)), mode="reflect")
    out = np.empty_like(imgs)
    offs = rng.integers(0, 9, (n, 2))
    flips = rng.random(n) < 0.5
    for i in range(n):
        dy, dx = offs[i]
        crop = padded[i, dy : dy + 32, dx : dx + 32]
        out[i] = crop[:, ::-1] if flips[i] else crop
    return out


def main() -> None:
    replica_group = int(os.environ.get("REPLICA_GROUP_ID", 0))
    num_groups = int(os.environ.get("NUM_REPLICA_GROUPS", 2))
    steps = int(os.environ.get("STEPS", 20))
    batch = int(os.environ.get("BATCH", 32))
    data_path = os.environ.get("DATA_PATH", "/tmp/torchft_tpu_cifar.npz")
    trace_path = os.environ.get("TRACE_PATH")
    ckpt_dir = os.environ.get("CKPT_DIR")
    ckpt_every = int(os.environ.get("CKPT_EVERY", 5))

    store_addr = os.environ.get("TORCHFT_STORE_ADDR")
    store = None
    if store_addr is None:
        store = StoreServer()
        store_addr = store.address()

    images, labels = ensure_dataset(data_path)
    logger.info("dataset: %d images %s", len(images), images.shape[1:])

    from torchft_tpu.models import resnet

    on_tpu = jax.devices()[0].platform not in ("cpu",)
    cfg = resnet.ResNetConfig(dtype=jnp.bfloat16 if on_tpu else jnp.float32)

    manager = Manager(
        collectives=CollectivesTcp(timeout=timedelta(seconds=30)),
        load_state_dict=None,  # wired below (params + opt + bn stats)
        state_dict=None,
        min_replica_size=min(2, num_groups),
        replica_id=f"train_cifar_{replica_group}",
        store_addr=store_addr,
        rank=0,
        world_size=1,
        timeout=timedelta(seconds=30),
    )

    params, bn_stats = resnet.init(jax.random.PRNGKey(0), cfg)
    tx = optax.sgd(0.05, momentum=0.9)
    opt_state = tx.init(params)

    # heal state: params + optimizer + BN running stats, all together
    state = {"params": params, "opt_state": opt_state, "bn": bn_stats}

    def load_state(s):
        state.update(s)

    manager.set_state_dict_fns(load_state, lambda: dict(state))

    sampler = DistributedSampler(
        len(images),
        replica_group=replica_group,
        num_replica_groups=num_groups,
        shuffle=True,
        seed=0,
    )

    @jax.jit
    def grads_fn(params, bn, x, y):
        (loss, new_bn), grads = jax.value_and_grad(
            lambda p: resnet.loss_fn(p, bn, x, y, cfg), has_aux=True
        )(params)
        return loss, grads, new_bn

    @jax.jit
    def apply_fn(params, opt_state, grads):
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state

    ckpt = None
    if ckpt_dir:
        from torchft_tpu.checkpointing.disk import DiskCheckpointer

        ckpt = DiskCheckpointer(
            ckpt_dir,
            manager,
            state_dict=lambda: dict(state),
            load_state_dict=load_state,
            every=ckpt_every,
            tag=f"group{replica_group}",
        )
        ckpt.restore()

    trace = open(trace_path, "a", buffering=1) if trace_path else None
    aug_rng = np.random.default_rng(1000 + replica_group)
    import time

    try:
        while manager.current_step() < steps:
            step = manager.current_step()
            ids = step_indices(sampler, step, batch)
            x = augment(images[ids], aug_rng).astype(np.float32) / 255.0
            y = jnp.asarray(labels[ids], jnp.int32)

            manager.start_quorum()
            loss, grads, new_bn = grads_fn(
                state["params"], state["bn"], jnp.asarray(x), y
            )
            grads = allreduce_gradients(manager, grads)
            if manager.should_commit():
                state["params"], state["opt_state"] = apply_fn(
                    state["params"], state["opt_state"], grads
                )
                if manager.is_participating():
                    # participants only: on a heal step should_commit just
                    # restored the peer's accumulated BN stats into
                    # state["bn"] — new_bn here came from the PRE-heal
                    # forward and would clobber them
                    state["bn"] = new_bn
                    if trace is not None:
                        trace.write(
                            json.dumps({"step": step, "ids": ids.tolist()})
                            + "\n"
                        )
            else:
                time.sleep(0.2)  # same batch retries: step didn't advance
            logger.info(
                "step=%d participants=%d loss=%.4f",
                manager.current_step(),
                manager.num_participants(),
                float(loss),
            )
            if ckpt is not None:
                ckpt.maybe_save()
        checksum = float(
            sum(
                float(np.asarray(l, dtype=np.float64).sum())
                for l in jax.tree_util.tree_leaves(state["params"])
            )
        )
        logger.info(
            "done: step=%d param_checksum=%.6f", manager.current_step(), checksum
        )
    finally:
        if trace is not None:
            trace.close()
        manager.shutdown(wait=False)
        if store is not None:
            store.shutdown()


if __name__ == "__main__":
    main()
