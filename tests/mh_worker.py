"""Worker process for tests/test_multihost.py: one rank of a replica group
whose inner mesh spans 2 processes (multi-controller JAX on CPU).

argv: gid rank world coordinator store_addr lighthouse_addr out_path
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    gid, rank, world = int(sys.argv[1]), int(sys.argv[2]), int(sys.argv[3])
    coordinator, store_addr, lighthouse_addr, out_path = sys.argv[4:8]

    from torchft_tpu.parallel.multihost import global_mesh, initialize_group

    # before any backend use: joins the group's jax runtime
    initialize_group(coordinator, world, rank)
    assert len(jax.devices()) == 2 * world, jax.devices()

    from datetime import timedelta

    import jax.numpy as jnp
    import numpy as np
    import optax

    from torchft_tpu.collectives import CollectivesTcp
    from torchft_tpu.manager import Manager
    from torchft_tpu.models.transformer import TransformerConfig
    from torchft_tpu.parallel.ft import FTTrainer
    from torchft_tpu.parallel.mesh import MeshConfig
    from torchft_tpu.parallel.train_step import TrainStep

    cfg = TransformerConfig(
        vocab_size=64,
        d_model=16,
        n_layers=2,
        n_heads=2,
        head_dim=8,
        d_ff=32,
        dtype=jnp.float32,
    )
    # dp spans the two processes, tp is intra-process: the jitted step's
    # collectives cross the process boundary
    mesh = global_mesh(MeshConfig(dp=2, tp=2))
    ts = TrainStep(cfg, optax.sgd(0.05), mesh)

    # no fault is injected here, so a deadline only has to end a hang:
    # on a busy host one group can be tens of seconds behind the other
    # (its compile, its gloo set-up), and an op that gives up on a peer
    # that is merely late fails the run on the neighbours' load
    op_timeout = timedelta(seconds=120)
    manager = Manager(
        collectives=CollectivesTcp(timeout=op_timeout),
        load_state_dict=None,  # wired by FTTrainer.init
        state_dict=None,
        min_replica_size=2,
        replica_id=f"mh{gid}",
        store_addr=store_addr,
        rank=rank,
        world_size=world,
        lighthouse_addr=lighthouse_addr,
        timeout=op_timeout,
    )
    try:
        trainer = FTTrainer(manager, ts)
        trainer.init(jax.random.PRNGKey(0))

        data_rng = np.random.default_rng(500 + gid)
        while manager.current_step() < 3:
            tokens = jnp.asarray(
                data_rng.integers(0, cfg.vocab_size, (4, 16)), jnp.int32
            )
            trainer.step(tokens)

        total = jax.jit(
            lambda p: sum(
                jnp.sum(l.astype(jnp.float64))
                for l in jax.tree_util.tree_leaves(p)
            )
        )(trainer.params)
        checksum = float(total)
        if rank == 0:
            with open(out_path, "w") as f:
                f.write(f"{manager.current_step()} {checksum:.10f}\n")
    finally:
        manager.shutdown(wait=False)


if __name__ == "__main__":
    main()
