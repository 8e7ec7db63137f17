"""Cross-group device plane for separate-PROCESS groups sharing a slice.

``CollectivesDevice`` (collectives_device.py) averages gradients over an
elastic ``'ft'`` mesh axis, but its rendezvous is an in-process registry —
it requires every replica group to live in ONE Python process. The
builder's own launcher and k8s manifests put each group in its own
process, where averaging previously fell back to the host TCP/CMA plane
(round-3 review missing #1/#6).

``CollectivesDeviceDist`` closes that gap for the one-slice topology: all
replica-group processes join a single multi-controller JAX runtime
(``jax.distributed``), and cross-group averaging is ONE jitted
``shard_map``/``psum`` over a global ``'ft'`` axis spanning the
processes — the cross-process reduction rides ICI, the role
NCCL-over-NVLink plays for the reference's same-host process groups
(process_group.py:431-447). The current API takes host numpy buffers
(one D2H/H2D hop each side of the psum, like the host plane's bucket
path); a device-array fast path (``device_arrays=True``) is the natural
next step once a multi-chip box exists to measure it on.

The price of the shared runtime is STATIC membership: multi-controller
JAX cannot lose a member and live. ``configure`` therefore validates the
quorum cohort == the runtime cohort and raises on any mismatch — the
supervisor then restarts the whole cohort (the k8s Indexed-Job pattern,
launcher.py), or the caller falls back to the host plane, which is what
the elastic path is for. Plane selection table: README "Choosing a
cross-group data plane".

Op surface (round-4 review missing #2 closed): the symmetric
collectives — allreduce, allgather, broadcast, reduce_scatter,
alltoall, barrier — ride the device mesh (psum / all_gather /
psum_scatter / all_to_all over the global ``'ft'`` axis). Point-to-point
``send``/``recv`` cannot ride a multi-controller runtime (a compiled
collective needs every process in the same program; p2p involves two),
so they ride a host TCP side-channel — an embedded
:class:`~torchft_tpu.collectives.CollectivesTcp` configured on the same
epoch store — which is also what makes
:class:`~torchft_tpu.checkpointing.collectives_transport.CollectivesTransport`
(live heals) work on this plane. This mirrors how NCCL separates
collective rings from p2p channels. Non-uniform input lists for
reduce_scatter/alltoall (per-slot shapes/dtypes) take the side-channel
too; the device path requires a stackable list.

Runtime bootstrap: call ``jax.distributed.initialize`` before first jax
use (the launcher's ``--jax-coordinator`` wiring or
``init_distributed`` below), one process per replica group.
"""

from __future__ import annotations

from datetime import timedelta
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from torchft_tpu.collectives import (
    Collectives,
    ReduceOp,
    Work,
    average_in_place,
    resolve_divisor,
)
from torchft_tpu.futures import Future

__all__ = ["CollectivesDeviceDist", "init_distributed", "init_from_env"]


def init_distributed(
    coordinator: str, num_processes: int, process_id: int
) -> None:
    """Join the shared runtime (idempotent). Must run before first jax
    use in the process; the launcher can do this for you."""
    import jax

    jax.distributed.initialize(
        coordinator_address=coordinator,
        num_processes=num_processes,
        process_id=process_id,
    )


def init_from_env() -> bool:
    """Join the shared runtime from the launcher's cohort env contract
    (``torchft_tpu.launcher --shared-runtime`` exports
    TORCHFT_COHORT_COORDINATOR / _SIZE / _ID). Returns whether a cohort
    was configured; call before first jax use."""
    import os

    coordinator = os.environ.get("TORCHFT_COHORT_COORDINATOR")
    if not coordinator:
        return False
    init_distributed(
        coordinator,
        int(os.environ["TORCHFT_COHORT_SIZE"]),
        int(os.environ["TORCHFT_COHORT_ID"]),
    )
    return True


class CollectivesDeviceDist(Collectives):
    def __init__(self, timeout: timedelta = timedelta(seconds=60)) -> None:
        # Per-op deadlines cannot interrupt a compiled collective; on this
        # plane LIVENESS is the shared runtime's own job (jax.distributed
        # heartbeats kill the cohort when a member wedges, and the
        # launcher's cohort supervision respawns it). The timeout arg is
        # kept for Collectives-API symmetry only.
        self._timeout = timeout
        self._rank = -1
        self._world = 0
        self._mesh = None
        self._jit_cache: Dict[Tuple, Callable] = {}
        # host TCP side-channel for p2p (and ragged reduce_scatter/
        # alltoall): created at first configure, reconfigured per epoch
        self._p2p: Optional[Any] = None

    # -- lifecycle --

    def configure(self, store_addr: str, rank: int, world_size: int) -> None:
        import jax
        from jax.sharding import Mesh

        # the cohort check applies to world_size==1 too: a quorum shrunk
        # to one on a 2-process runtime must RAISE (silently no-op
        # allreducing alone — or two partitioned singletons diverging —
        # is exactly what the contract forbids)
        if jax.process_count() != world_size or jax.process_index() != rank:
            raise RuntimeError(
                "CollectivesDeviceDist needs quorum cohort == runtime "
                f"cohort: quorum says rank {rank}/{world_size}, the shared "
                f"jax runtime says {jax.process_index()}/"
                f"{jax.process_count()}. A shrunken quorum cannot ride a "
                "multi-controller runtime — restart the cohort (launcher/"
                "k8s Job) or fall back to the host plane."
            )
        # one device per process carries the cross-group payload; the
        # group's inner mesh (if any) keeps using all local devices
        devs = np.empty(world_size, dtype=object)
        for d in jax.devices():
            if d.process_index < world_size and devs[d.process_index] is None:
                devs[d.process_index] = d
        if any(d is None for d in devs):
            raise RuntimeError("some process contributes no devices")
        self._mesh = Mesh(devs, ("ft",))
        self._rank = rank
        self._world = world_size
        self._jit_cache.clear()
        # p2p side-channel: every cohort member reaches configure (the
        # Manager reconfigures all members on a quorum change), so the
        # full-mesh TCP dial inside is a safe per-epoch barrier. Plain
        # sockets only (native_plane=False): bulk traffic rides ICI; this
        # channel exists for heals and ragged ops. A store is required
        # for its rendezvous — standalone use with store_addr="" keeps
        # the symmetric device collectives and loses only p2p.
        if store_addr:
            from torchft_tpu.collectives import CollectivesTcp

            if self._p2p is None:
                self._p2p = CollectivesTcp(
                    timeout=self._timeout, native_plane=False
                )
            self._p2p.configure(store_addr, rank, world_size)
        elif self._p2p is not None:
            self._p2p.shutdown()
            self._p2p = None

    def shutdown(self) -> None:
        if self._p2p is not None:
            self._p2p.shutdown()
            self._p2p = None
        self._mesh = None

    def size(self) -> int:
        return self._world

    def rank(self) -> int:
        return self._rank

    def plane_info(self) -> str:
        """Dashboard label: ICI psum plane (+TCP p2p side-channel)."""
        return "device-dist"

    # -- plumbing --

    def _cached_jit(self, key: Tuple, body, replicated_out: bool = False,
                    **shard_map_kwargs) -> Callable:
        """Build-or-fetch the jitted shard_map for ``body`` over 'ft'."""
        fn = self._jit_cache.get(key)
        if fn is not None:
            return fn
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        out_spec = P() if replicated_out else P("ft")
        fn = jax.jit(
            jax.shard_map(
                body,
                mesh=self._mesh,
                in_specs=P("ft"),
                out_specs=out_spec,
                **shard_map_kwargs,
            ),
            out_shardings=NamedSharding(self._mesh, out_spec),
        )
        self._jit_cache[key] = fn
        return fn

    def _stage(self, host_block: np.ndarray):
        """Place this process's ``[1, ...]`` host block as its shard of
        the 'ft'-sharded global array."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        return jax.make_array_from_process_local_data(
            NamedSharding(self._mesh, P("ft")),
            host_block,
            (self._world, *host_block.shape[1:]),
        )

    def _reduce_jit(self, shape, dtype, op: ReduceOp, divisor: int) -> Callable:
        import jax

        def block(x):  # x: local [1, *shape] block
            if op == ReduceOp.SUM:
                r = jax.lax.psum(x, "ft")
                if divisor != 1:
                    r = r / divisor
            elif op == ReduceOp.MAX:
                r = jax.lax.pmax(x, "ft")
            else:
                r = jax.lax.pmin(x, "ft")
            return r

        return self._cached_jit((tuple(shape), str(dtype), op, divisor), block)

    def _gather_jit(self, shape, dtype) -> Callable:
        import jax

        return self._cached_jit(
            (tuple(shape), str(dtype), "allgather"),
            lambda x: jax.lax.all_gather(x, "ft", axis=0, tiled=True),
            replicated_out=True,
            # all_gather(tiled) IS replicated over 'ft'; the VMA
            # checker just can't infer it through the tiled form
            check_vma=False,
        )

    @staticmethod
    def _check_avg_dtype(averaged: bool, dtype: np.dtype) -> None:
        """An average (AVG, or SUM with a divisor) of integer inputs would
        silently truncate on the host-copy assignment here, while the
        host TCP plane's in-place np.divide raises a casting error — keep
        the planes' failure semantics identical (round-4 advisor low)."""
        if averaged and not np.issubdtype(dtype, np.inexact):
            raise TypeError(
                f"ReduceOp.AVG on dtype {np.dtype(dtype)} would truncate; "
                "cast to a float dtype first (matches the host plane's "
                "np.divide casting error)"
            )

    def _allreduce_one(self, arr: np.ndarray, op: ReduceOp, divisor: int) -> None:
        self._check_avg_dtype(divisor != 1, arr.dtype)
        garr = self._stage(np.ascontiguousarray(arr)[None, ...])
        out = self._reduce_jit(arr.shape, arr.dtype, op, divisor)(garr)
        arr[...] = np.asarray(out.addressable_shards[0].data)[0]

    # -- collectives --

    def allreduce(
        self,
        arrays: List[np.ndarray],
        op: ReduceOp = ReduceOp.SUM,
        divisor: int = 1,
    ) -> Work:
        try:
            op, divisor = resolve_divisor(op, divisor, self._world)
            if self._world > 1:
                for arr in arrays:
                    self._allreduce_one(arr, op, divisor)  # divides on the device
            else:
                average_in_place(arrays, divisor)  # 1 for AVG at world 1
            return Work.completed(arrays)
        except Exception as e:  # noqa: BLE001 — surface through the future
            return Work.failed(e)

    def allgather(self, arr: np.ndarray) -> Work:
        try:
            if self._world == 1:
                return Work.completed([arr.copy()])
            garr = self._stage(np.ascontiguousarray(arr)[None, ...])
            gathered = self._gather_jit(arr.shape, arr.dtype)(garr)
            local = np.asarray(gathered.addressable_shards[0].data)
            return Work.completed(
                [local[i].copy() for i in range(self._world)]
            )
        except Exception as e:  # noqa: BLE001 — surface through the future
            return Work.failed(e)

    def broadcast(self, arr: np.ndarray, root: int = 0) -> Work:
        out = self.allgather(arr)

        def pick(f: Future):
            arr[...] = f.value()[root]
            return arr

        return Work(out.get_future().then(pick))

    @staticmethod
    def _uniform(arrays: List[np.ndarray]) -> bool:
        first = arrays[0]
        return all(
            a.shape == first.shape and a.dtype == first.dtype
            for a in arrays[1:]
        )

    def _rs_jit(self, shape, dtype) -> Callable:
        import jax

        # global [world, world, *shape], dim 0 sharded on 'ft' (the
        # contributing rank), dim 1 the destination slot; psum_scatter
        # over slots leaves rank r holding sum_contributors(slot r)
        return self._cached_jit(
            (tuple(shape), str(dtype), "reduce_scatter"),
            lambda x: jax.lax.psum_scatter(
                x, "ft", scatter_dimension=1, tiled=False
            ),
        )

    def _a2a_jit(self, shape, dtype) -> Callable:
        import jax

        # local block [1, world, *shape]: split the slot dim across 'ft',
        # concatenate along the (sharded) leading dim — rank r ends with
        # [world, 1, *shape] where entry j is rank j's slot r
        return self._cached_jit(
            (tuple(shape), str(dtype), "alltoall"),
            lambda x: jax.lax.all_to_all(
                x, "ft", split_axis=1, concat_axis=0, tiled=True
            ),
        )

    def reduce_scatter(
        self, arrays: List[np.ndarray], op: ReduceOp = ReduceOp.SUM
    ) -> Work:
        try:
            if len(arrays) != self._world:
                raise ValueError(
                    f"reduce_scatter needs {self._world} inputs, "
                    f"got {len(arrays)}"
                )
            # dtype check BEFORE the world==1 return: the host plane's
            # np.divide raises for AVG-on-int even at world 1
            self._check_avg_dtype(op == ReduceOp.AVG, arrays[0].dtype)
            if self._world == 1:
                return Work.completed(arrays[0].copy())
            if not self._uniform(arrays):
                # ragged slots can't stack into one device array
                return self._p2p_or_raise().reduce_scatter(arrays, op)
            if op not in (ReduceOp.SUM, ReduceOp.AVG):
                # psum_scatter is sum-only; max/min scatter is a host op
                return self._p2p_or_raise().reduce_scatter(arrays, op)
            shape, dtype = arrays[0].shape, arrays[0].dtype
            garr = self._stage(np.ascontiguousarray(np.stack(arrays))[None])
            out_g = self._rs_jit(shape, dtype)(garr)
            # np.asarray of a jax shard is a READ-ONLY view; the host
            # plane returns writable arrays, so copy (alltoall below
            # and allgather do the same)
            out = np.array(np.asarray(out_g.addressable_shards[0].data)[0])
            if op == ReduceOp.AVG:
                out = out / self._world
            return Work.completed(out.astype(dtype, copy=False))
        except Exception as e:  # noqa: BLE001 — surface through the future
            return Work.failed(e)

    def alltoall(self, arrays: List[np.ndarray]) -> Work:
        try:
            if len(arrays) != self._world:
                raise ValueError(
                    f"alltoall needs {self._world} inputs, got {len(arrays)}"
                )
            if self._world == 1:
                return Work.completed([arrays[0].copy()])
            if not self._uniform(arrays):
                return self._p2p_or_raise().alltoall(arrays)
            shape, dtype = arrays[0].shape, arrays[0].dtype
            garr = self._stage(np.ascontiguousarray(np.stack(arrays))[None])
            out_g = self._a2a_jit(shape, dtype)(garr)
            local = np.asarray(out_g.addressable_shards[0].data)
            # local: [world, 1, *shape] — entry j is rank j's slot for us
            return Work.completed(
                [local[j, 0].copy() for j in range(self._world)]
            )
        except Exception as e:  # noqa: BLE001 — surface through the future
            return Work.failed(e)

    def _p2p_or_raise(self):
        if self._p2p is None:
            raise RuntimeError(
                "the p2p side-channel needs a store rendezvous: "
                "configure() with a non-empty store_addr (the Manager "
                "always does)"
            )
        return self._p2p

    def send(self, arr: np.ndarray, dst: int, tag: int = 0) -> Work:
        return self._p2p_or_raise().send(arr, dst, tag)

    def recv(self, arr: np.ndarray, src: int, tag: int = 0) -> Work:
        return self._p2p_or_raise().recv(arr, src, tag)

    def barrier(self) -> Work:
        one = np.ones(1, dtype=np.float32)
        return Work(
            self.allreduce([one], ReduceOp.SUM).get_future().then(lambda f: None)
        )
