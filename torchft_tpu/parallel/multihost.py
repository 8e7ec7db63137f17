"""Multi-host replica groups — jax.distributed wiring.

The reference's replica groups span hosts through torchrun: each group is
``torchrun --nnodes=1 --nproc_per_node=M`` and torch.distributed carries
the intra-group collectives (/root/reference/torchft/torchx.py:11-76). The
TPU-native equivalent is multi-controller JAX: every process of a group
calls ``jax.distributed.initialize`` against the group's coordinator, after
which ``jax.devices()`` is the group's *global* device list, the inner
``jax.sharding.Mesh`` spans hosts, and XLA runs the intra-group collectives
over ICI/DCN. The elastic cross-group axis stays outside (Manager +
CollectivesTcp per rank, same-rank peers across groups), so group
membership changes still never touch the compiled step.

Env contract (set by the launcher, torchelastic-style):

    TORCHFT_JAX_COORDINATOR   host:port of the group's jax coordinator
    RANK / WORLD_SIZE         this process's index / process count in group

Per-process accelerator visibility (e.g. 4 chips of a v5e host) comes from
the platform; on CPU tests ``--xla_force_host_platform_device_count``
gives each process N virtual devices.
"""

from __future__ import annotations

import os
from typing import Optional

__all__ = ["initialize_group", "is_initialized", "global_mesh"]

JAX_COORDINATOR_ENV = "TORCHFT_JAX_COORDINATOR"

_initialized = False


def is_initialized() -> bool:
    return _initialized


def initialize_group(
    coordinator: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
) -> None:
    """Join this replica group's JAX runtime. Reads the launcher env
    (TORCHFT_JAX_COORDINATOR / RANK / WORLD_SIZE) unless given explicitly;
    a no-op for single-process groups (no coordinator set) and when
    already initialized (idempotent, so library code may call it freely).

    Must run before any other jax API touches the backend."""
    global _initialized
    if _initialized:
        return
    coordinator = coordinator or os.environ.get(JAX_COORDINATOR_ENV)
    if coordinator is None:
        return  # single-process group
    num_processes = (
        num_processes
        if num_processes is not None
        else int(os.environ["WORLD_SIZE"])
    )
    process_id = (
        process_id if process_id is not None else int(os.environ["RANK"])
    )
    if num_processes <= 1:
        return
    import jax

    jax.distributed.initialize(
        coordinator_address=coordinator,
        num_processes=num_processes,
        process_id=process_id,
    )
    _initialized = True


def global_mesh(config):
    """The group-wide mesh: :func:`make_mesh` over the global device list
    (which spans every process of the group after :func:`initialize_group`).
    All processes must call with the same config."""
    import jax

    from torchft_tpu.parallel.mesh import make_mesh

    return make_mesh(config, devices=jax.devices())
