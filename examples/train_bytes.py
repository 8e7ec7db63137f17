"""Fault-tolerant byte-level LM on a real on-disk dataset.

The reference's flagship example trains CIFAR-10 from disk with a stateful
dataloader whose position survives restarts (train_ddp.py:34-80 + its
torchdata StatefulDataLoader use at :57-61). The TPU-native analogue: a
byte-level transformer LM over a real corpus file, with the
DistributedSampler's (epoch, position) derived from the *committed step
count* — the one clock every replica group provably agrees on — so

* a killed + restarted group resumes exactly where its last committed
  step left off (no sample double-trained, none skipped),
* groups can never desync epochs (the round-robin partition across
  groups stays disjoint through kill/heal/resume),
* a failed commit retries the SAME batch (the step didn't advance).

Each group appends one JSONL line per committed step to TRACE_PATH
recording the exact sample indices it trained on — the resume-correctness
proof harness (tests/test_data_example.py) kills a group mid-epoch,
restarts it, and replays the trace against an oracle sampler.

Env (launcher contract, see torchft_tpu/launcher.py):

    TORCHFT_LIGHTHOUSE  REPLICA_GROUP_ID  NUM_REPLICA_GROUPS  STEPS
    DATA_PATH    corpus file (built from this repo's own sources if absent)
    TRACE_PATH   committed-step JSONL (optional)
    CKPT_DIR / CKPT_EVERY   periodic disk checkpoints (optional)

Run::

    python -m torchft_tpu.launcher --groups 2 -- python examples/train_bytes.py
"""

import glob
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
from torchft_tpu.data import DistributedSampler, step_indices as batch_indices
from torchft_tpu.manager import Manager
from torchft_tpu.optim import ManagedOptimizer
from torchft_tpu.store import StoreServer

logging.basicConfig(
    level=logging.INFO, format="%(asctime)s %(name)s: %(message)s"
)
logger = logging.getLogger("train_bytes")

SEQ = 128


def ensure_corpus(path: str) -> bytes:
    """Real bytes from disk: the framework's own sources, deterministic
    for every group of the same checkout (the CIFAR-download analogue)."""
    if not os.path.exists(path):
        root = os.path.join(os.path.dirname(__file__), "..", "torchft_tpu")
        files = sorted(glob.glob(os.path.join(root, "**", "*.py"), recursive=True))
        blob = b"".join(open(f, "rb").read() for f in files)
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        tmp = path + f".tmp{os.getpid()}"
        with open(tmp, "wb") as f:
            f.write(blob)
        os.replace(tmp, path)  # atomic: concurrent groups race safely
    with open(path, "rb") as f:
        return f.read()


def main() -> None:
    replica_group = int(os.environ.get("REPLICA_GROUP_ID", 0))
    num_groups = int(os.environ.get("NUM_REPLICA_GROUPS", 2))
    steps = int(os.environ.get("STEPS", 20))
    batch = int(os.environ.get("BATCH", 8))
    data_path = os.environ.get("DATA_PATH", "/tmp/torchft_tpu_corpus.bin")
    trace_path = os.environ.get("TRACE_PATH")
    ckpt_dir = os.environ.get("CKPT_DIR")
    ckpt_every = int(os.environ.get("CKPT_EVERY", 5))

    store_addr = os.environ.get("TORCHFT_STORE_ADDR")
    store = None
    if store_addr is None:
        store = StoreServer()
        store_addr = store.address()

    corpus = np.frombuffer(ensure_corpus(data_path), dtype=np.uint8)
    n_windows = (len(corpus) - 1) // SEQ
    windows = corpus[: n_windows * SEQ].reshape(n_windows, SEQ)
    logger.info("corpus: %d bytes, %d windows of %d", len(corpus), n_windows, SEQ)

    from torchft_tpu.models.transformer import TransformerConfig, loss_fn

    on_tpu = jax.devices()[0].platform not in ("cpu",)
    cfg = TransformerConfig(
        vocab_size=256,
        d_model=128,
        n_layers=2,
        n_heads=4,
        head_dim=32,
        d_ff=352,
        dtype=jnp.bfloat16 if on_tpu else jnp.float32,
    )

    manager = Manager(
        collectives=CollectivesTcp(timeout=timedelta(seconds=30)),
        load_state_dict=None,  # wired by ManagedOptimizer.init
        state_dict=None,
        min_replica_size=min(2, num_groups),
        replica_id=f"train_bytes_{replica_group}",
        store_addr=store_addr,
        rank=0,
        world_size=1,
        timeout=timedelta(seconds=30),
    )

    from torchft_tpu.models.transformer import init_params

    opt = ManagedOptimizer(manager, optax.adam(1e-3))
    opt.init(init_params(jax.random.PRNGKey(0), cfg))
    sampler = DistributedSampler(
        n_windows,
        replica_group=replica_group,
        num_replica_groups=num_groups,
        shuffle=True,
        seed=0,
    )

    value_and_grad = jax.jit(
        jax.value_and_grad(lambda p, toks: loss_fn(p, toks, cfg, None))
    )

    ckpt = None
    if ckpt_dir:
        from torchft_tpu.checkpointing.disk import DiskCheckpointer

        ckpt = DiskCheckpointer(
            ckpt_dir,
            manager,
            state_dict=lambda: {"opt": opt.state_dict(), "sampler": sampler.state_dict()},
            load_state_dict=lambda s: (
                opt.load_state_dict(s["opt"]),
                sampler.load_state_dict(s["sampler"]),
            ),
            every=ckpt_every,
            tag=f"group{replica_group}",
        )
        ckpt.restore()

    trace = open(trace_path, "a", buffering=1) if trace_path else None
    import time

    try:
        prev_step = manager.current_step()
        while manager.current_step() < steps:
            step = manager.current_step()
            ids = batch_indices(sampler, step, batch)
            tokens = jnp.asarray(windows[ids], jnp.int32)

            opt.begin_step()
            loss, grads = value_and_grad(opt.params, tokens)
            opt.step(grads)

            committed = manager.current_step() > prev_step
            if committed and manager.is_participating() and trace is not None:
                trace.write(
                    json.dumps({"step": step, "ids": ids.tolist()}) + "\n"
                )
            if not committed:
                time.sleep(0.2)  # same batch retries: step didn't advance
            prev_step = manager.current_step()
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
                for l in jax.tree_util.tree_leaves(opt.params)
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
