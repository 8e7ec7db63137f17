"""Device-mesh construction for the intra-replica-group axes.

The FT replicate axis is deliberately NOT part of this mesh (contrast with
the reference's ManagedDeviceMesh which splices the managed PG *into* the
torch DeviceMesh, process_group.py:1361-1606): a jitted step function bakes
the mesh shape into the compiled executable, so putting the elastic axis in
the mesh would force a recompile on every membership change. Keeping it
host-side (Manager + Collectives) is the TPU-native answer to the same
composition problem.
"""

from __future__ import annotations

import logging
from dataclasses import dataclass
from typing import Dict, Optional, Sequence

import numpy as np

logger = logging.getLogger(__name__)

__all__ = ["MeshConfig", "make_mesh", "AXES"]

# canonical axis order: outermost (slowest, DCN-adjacent) first so that
# tp/sp land on the innermost ICI links where their collectives are hottest
AXES: Sequence[str] = ("dp", "fsdp", "pp", "ep", "sp", "tp")


@dataclass(frozen=True)
class MeshConfig:
    """Sizes for each named axis; 1 means the axis is inert (size-1 axes
    still exist in the mesh so one step function serves every layout)."""

    dp: int = 1
    fsdp: int = 1
    pp: int = 1
    ep: int = 1
    sp: int = 1
    tp: int = 1

    @property
    def sizes(self) -> Dict[str, int]:
        return {a: getattr(self, a) for a in AXES}

    @property
    def total(self) -> int:
        return int(np.prod(list(self.sizes.values())))


def make_mesh(config: MeshConfig, devices: Optional[Sequence] = None):
    """Build a ``jax.sharding.Mesh`` with the canonical axis order."""
    import jax

    devices = list(devices if devices is not None else jax.devices())
    if len(devices) < config.total:
        raise ValueError(
            f"mesh needs {config.total} devices, have {len(devices)}"
        )
    if len(devices) > config.total and devices[0].platform != "cpu":
        # e.g. FSDP=1 TP=1 on a four-chip host: everything lands on chip 0
        logger.warning(
            "mesh %s uses %d of the %d visible devices; %s stay idle",
            {a: n for a, n in config.sizes.items() if n > 1} or "(1 device)",
            config.total, len(devices), devices[config.total :],
        )
    shape = tuple(config.sizes[a] for a in AXES)
    dev = np.array(devices[: config.total]).reshape(shape)
    return jax.sharding.Mesh(dev, AXES)
