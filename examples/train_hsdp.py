"""HSDP training example — replica groups of sharded (fsdp x tp) meshes.

The flagship composition: each replica group owns a fixed inner
``jax.sharding.Mesh`` (fsdp x tp [x sp x pp] — XLA's ICI collectives,
compiled once), while the Manager runs the elastic replica axis across
groups. One group is one process with its own chips, so gradients cross
the replica axis through ``allreduce_gradients`` on the host plane
(``CollectivesTcp``). Group membership changes never
recompile the train step; a killed group live-heals its *sharded* params
shard-by-shard from a survivor (serialization.py "shards" transfer).

Env:

    TORCHFT_LIGHTHOUSE=host:port
    REPLICA_GROUP_ID / NUM_REPLICA_GROUPS (default 2)
    MODEL=tiny|scale_647M|olmoe-1b-7b
                                   models.transformer.PRESETS (default
                                   tiny; scale_647M fills one v5e chip,
                                   olmoe-1b-7b needs a sharded group of
                                   >= 8 real chips)
    FSDP/TP/SP/PP                  inner mesh axis sizes (default 2/2/1/1)
                                   over the devices THIS process sees: a
                                   chip belongs to one process, so the
                                   launcher hands each group its own chips
    STEPS=3  BATCH=8  SEQ=16       training shape

Run 2 tiny groups, each on its own 4-device virtual CPU platform::

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \\
    python -m torchft_tpu.launcher --groups 2 -- python examples/train_hsdp.py

Reference parity: fsdp_test.py:40-64 (fully_shard over ft_init_device_mesh)
re-designed TPU-first — the inner mesh is GSPMD shardings, not FSDP2.
"""

import logging
import os
import sys
import time
from datetime import timedelta

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from torchft_tpu.utils.compile_cache import place_compile_cache

place_compile_cache()  # before first use of jax; children inherit it
import jax
import jax.numpy as jnp
import optax

from torchft_tpu.collectives import CollectivesTcp
from torchft_tpu.manager import Manager
from torchft_tpu.models.transformer import PRESETS, TransformerConfig, init_params
from torchft_tpu.parallel.ft import FTTrainer
from torchft_tpu.parallel.mesh import MeshConfig, make_mesh
from torchft_tpu.parallel.multihost import initialize_group
from torchft_tpu.parallel.train_step import TrainStep
from torchft_tpu.store import StoreServer

# group/pid on every line: the launcher interleaves its groups' output,
# and a respawned group is a new pid
logging.basicConfig(
    level=logging.INFO,
    format=f"%(asctime)s g{os.environ.get('REPLICA_GROUP_ID', 0)}/%(process)d "
    "%(name)s: %(message)s",
)
logger = logging.getLogger("train_hsdp")


def main() -> None:
    replica_group = int(os.environ.get("REPLICA_GROUP_ID", 0))
    num_groups = int(os.environ.get("NUM_REPLICA_GROUPS", 2))
    steps = int(os.environ.get("STEPS", 3))
    batch = int(os.environ.get("BATCH", 8))
    seq = int(os.environ.get("SEQ", 16))
    preset = os.environ.get("MODEL", "tiny")

    logger.info("start: group %d of %d, model=%s", replica_group, num_groups, preset)

    store_addr = os.environ.get("TORCHFT_STORE_ADDR")
    store = None
    if store_addr is None:
        store = StoreServer()
        store_addr = store.address()

    initialize_group()  # multi-host group: join its jax runtime (no-op else)

    mesh_cfg = MeshConfig(
        fsdp=int(os.environ.get("FSDP", 2)),
        tp=int(os.environ.get("TP", 2)),
        sp=int(os.environ.get("SP", 1)),
        pp=int(os.environ.get("PP", 1)),
    )
    devices = jax.devices()
    logger.info(
        "devices: %d x %s (%s), jax %s",
        len(devices), devices[0].device_kind, devices[0].platform,
        jax.__version__,
    )
    mesh = make_mesh(mesh_cfg, devices=devices)

    cfg = TransformerConfig(pp=mesh_cfg.pp, **PRESETS[preset])
    ts = TrainStep(cfg, optax.adamw(3e-4), mesh)
    n_params = sum(
        int(np.prod(l.shape))
        for l in jax.tree_util.tree_leaves(
            jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
        )
    )
    # one deadline covers every collective, commit vote and heal transfer,
    # and a heal moves the whole replica state (f32 params + two Adam
    # moments): budget it at 50 MB/s on top of the 30 s floor
    timeout = timedelta(seconds=30 + 12 * n_params / 50e6)

    collectives = CollectivesTcp(timeout=timeout)
    manager = Manager(
        collectives=collectives,
        load_state_dict=None,  # wired by FTTrainer.init
        state_dict=None,
        min_replica_size=min(2, num_groups),
        replica_id=f"hsdp_{replica_group}",
        store_addr=store_addr,
        rank=int(os.environ.get("RANK", 0)),
        world_size=int(os.environ.get("WORLD_SIZE", 1)),
        timeout=timeout,
    )
    try:
        trainer = FTTrainer(manager, ts)
        trainer.init(jax.random.PRNGKey(0))
        logger.info(
            "model=%s params=%.1fM mesh=%s timeout=%.0fs",
            preset, n_params / 1e6, mesh_cfg.sizes, timeout.total_seconds(),
        )
        # where the state landed: a sharded leaf must really be split over
        # the mesh's devices, and each device must hold its part
        sharded = [
            l for l in jax.tree_util.tree_leaves(trainer.params)
            if not l.sharding.is_fully_replicated
        ]
        logger.info(
            "placement: sharded_leaves=%d min_shard_devices=%d bytes_in_use=%s",
            len(sharded),
            min(
                (len({s.device for s in l.addressable_shards}) for l in sharded),
                default=0,
            ),
            [
                (d.memory_stats() or {}).get("bytes_in_use")
                for d in mesh.devices.flat
                if d.process_index == jax.process_index()  # this process's
            ],
        )

        data_rng = np.random.default_rng(1000 + replica_group)
        while manager.current_step() < steps:
            tokens = jnp.asarray(
                data_rng.integers(0, cfg.vocab_size, (batch, seq)), jnp.int32
            )
            loss, committed = trainer.step(tokens)
            if not committed:
                time.sleep(0.2)  # back off while the quorum is short
            logger.info(
                "step=%d committed=%s participants=%d loss=%.4f",
                manager.current_step(),
                committed,
                manager.num_participants(),
                loss,
            )
        checksum = sum(
            float(jnp.sum(l.astype(jnp.float32)))
            for l in jax.tree_util.tree_leaves(trainer.params)
        )
        logger.info(
            "done: step=%d param_checksum=%.6f plane=%s",
            manager.current_step(), checksum, collectives.plane_info(),
        )
    finally:
        manager.shutdown(wait=False)
        if store is not None:
            store.shutdown()


if __name__ == "__main__":
    main()
