"""Real 2-group 'ft'-axis averaging overhead on a virtual CPU mesh.

The round-2 review called out that the headline bench's "averaging" is a
world-size-1 no-op on a single chip (`CollectivesDevice.allreduce` short-
circuits at world==1), so the reported overhead measured nothing. One chip
can't host two device-path groups — but a virtual 8-device CPU mesh can:
this worker runs TWO replica groups (threads sharing one JAX runtime,
4 devices each, the in-process registry path), each through a full Manager
(C++ lighthouse, per-step quorum + commit), and measures steps/s with the
REAL cross-group 'ft'-axis psum vs. without any averaging on identical
configs. The relative overhead is the honest number for what device-path
averaging costs; absolute CPU steps/s is meaningless and not reported
upstream.

Run standalone (must be a fresh process — the flags must precede jax
import)::

    python -m torchft_tpu.benchmarks.cpu_mesh_2group
"""

from __future__ import annotations

import json
import os
import sys


def _ensure_cpu_mesh() -> None:
    """Re-exec with the virtual-mesh flags if jax could already be live.

    Importing this module via ``-m`` runs the package ``__init__`` (which
    pulls in jax) before any code here, so mutating ``os.environ`` in-
    process is too late — a child process with the flags set is the only
    reliable way to get 8 virtual CPU devices."""
    if os.environ.get("_TFT_CPU2G") == "1":
        return
    import subprocess

    env = dict(os.environ)
    env["_TFT_CPU2G"] = "1"
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
    ).strip()
    sys.exit(
        subprocess.call(
            [sys.executable, "-m", "torchft_tpu.benchmarks.cpu_mesh_2group"],
            env=env,
        )
    )


def _measure(averaging: bool, steps: int, warmup: int) -> float:
    """Mean steps/s across 2 concurrent replica groups."""
    import time
    from concurrent.futures import ThreadPoolExecutor
    from datetime import timedelta

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from torchft_tpu.collectives_device import CollectivesDevice
    from torchft_tpu.coordination import LighthouseServer
    from torchft_tpu.ddp import allreduce_gradients
    from torchft_tpu.manager import Manager
    from torchft_tpu.models.transformer import TransformerConfig
    from torchft_tpu.parallel.mesh import MeshConfig, make_mesh
    from torchft_tpu.parallel.train_step import TrainStep
    from torchft_tpu.store import StoreServer

    # this bench must NEVER run on (or occupy) a real accelerator
    jax.config.update("jax_platforms", "cpu")
    devs = jax.devices()
    assert len(devs) >= 8, "needs xla_force_host_platform_device_count=8"

    cfg = TransformerConfig(
        vocab_size=1024,
        d_model=256,
        n_layers=4,
        n_heads=4,
        head_dim=64,
        d_ff=704,
        dtype=jnp.float32,
    )
    batch, seq = 4, 128

    lighthouse = LighthouseServer(bind="[::]:0", min_replicas=2)

    def one_group(gid: int) -> float:
        mesh = make_mesh(MeshConfig(dp=4), devices=devs[gid * 4 : (gid + 1) * 4])
        ts = TrainStep(cfg, optax.adamw(3e-4), mesh)
        params = ts.init_params(jax.random.PRNGKey(0))
        opt_state = ts.init_opt(params)
        store = StoreServer()
        manager = Manager(
            collectives=CollectivesDevice(timeout=timedelta(seconds=60)),
            load_state_dict=lambda s: None,
            state_dict=lambda: {},
            min_replica_size=2,
            replica_id=f"cpu2g{gid}",
            store_addr=store.address(),
            rank=0,
            world_size=1,
            lighthouse_addr=lighthouse.address(),
            timeout=timedelta(seconds=60),
            use_async_quorum=False,
        )
        rng = np.random.default_rng(gid)
        tokens = ts.shard_batch(
            jnp.asarray(rng.integers(0, cfg.vocab_size, (batch, seq)), jnp.int32)
        )
        try:
            def ft_step(params, opt_state):
                manager.start_quorum()
                loss, grads = ts.grads(params, tokens)
                if averaging:
                    grads = allreduce_gradients(manager, grads)
                if manager.should_commit():
                    params, opt_state = ts.apply(params, opt_state, grads)
                return loss, params, opt_state

            for _ in range(warmup):
                loss, params, opt_state = ft_step(params, opt_state)
            if warmup:
                float(loss)  # fence warmup work out of the timed window
            t0 = time.perf_counter()
            for _ in range(steps):
                loss, params, opt_state = ft_step(params, opt_state)
            float(loss)
            return steps / (time.perf_counter() - t0)
        finally:
            manager.shutdown(wait=False)
            store.shutdown()

    try:
        with ThreadPoolExecutor(max_workers=2) as ex:
            rates = list(ex.map(one_group, range(2)))
    finally:
        lighthouse.shutdown()
    return sum(rates) / len(rates)


def main() -> None:
    _ensure_cpu_mesh()
    steps, warmup = 5, 2
    # Interleave the variants and keep best-of-4 per variant: on a
    # 1-core host the run-to-run noise otherwise dwarfs the psum cost
    # (the first cut measured the overhead at -80%, and round 4's
    # best-of-2 still drifted 17% between rounds — review weak #7). The
    # MAX is the right statistic here: contention only ever subtracts,
    # so the fastest run is the closest view of the machine-independent
    # cost, and 4 samples make it stable across rounds.
    avg_runs, noavg_runs = [], []
    for _ in range(4):
        avg_runs.append(_measure(True, steps, warmup))
        noavg_runs.append(_measure(False, steps, warmup))
    with_avg, without = max(avg_runs), max(noavg_runs)
    overhead = (without - with_avg) / without * 100.0 if without else 0.0
    print(
        json.dumps(
            {
                "steps_per_sec_2group_avg": round(with_avg, 4),
                "steps_per_sec_2group_noavg": round(without, 4),
                "averaging_overhead_pct": round(overhead, 2),
                "avg_runs": [round(r, 4) for r in avg_runs],
                "noavg_runs": [round(r, 4) for r in noavg_runs],
                "config": "2 groups × dp=4 virtual CPU devices, d256 L4 "
                "b4 s128 f32, device-path 'ft' psum, sync quorum; "
                "best-of-4 per variant, runs recorded",
                "limitation": "CPU-mesh proxy metric: compute here is "
                "unrealistically cheap relative to the psum, so the "
                "overhead_pct OVERSTATES the on-chip cost; a single-chip "
                "box cannot isolate the multi-chip 'ft'-psum cost at "
                "realistic model sizes (on the chip: not measured)",
            }
        ),
        flush=True,
    )


if __name__ == "__main__":
    main()
