"""Device-path cross-replica-group collectives — the ICI data plane.

The reference's data plane between replica groups is NCCL over RDMA
(/root/reference/torchft/process_group.py:431-447): gradients never touch
the host. ``CollectivesTcp`` (the Gloo analogue) covers groups in separate
processes, but every byte it moves pays device→host→TCP→host→device. On
TPU the analogous fast path is XLA collectives over ICI: when replica
groups share one JAX runtime — one controller process driving a slice,
e.g. 4 groups × 8 chips on a v5e-32 — cross-group averaging can stay in
HBM end to end. ``CollectivesDevice`` is that backend (survey §7 item 3b).

Design:

* **arrays stay on device.** ``allreduce`` stacks each leaf across the
  participating groups into one global ``jax.Array`` over a mesh with a
  leading elastic ``'ft'`` axis (built from the groups' own inner meshes,
  which must be congruent), then runs a single jitted ``shard_map`` psum —
  XLA emits the ICI collectives. Results are handed back re-assembled on
  each group's original devices with its original sharding.
* **reconfiguration is cheap by construction.** Membership changes change
  only the tiny 'ft'-axis reduction kernel (re-jitted per (mesh, specs,
  world), cached); the model's compiled train step never recompiles —
  the same split the host backend guarantees, without the host.
* **the rendezvous is the same epoch namespace** the TCP backend uses
  (``{store}/torchft/{quorum_id}/{rank}`` — manager.py configure path),
  resolved through an in-process registry instead of sockets. Ops match
  across groups by an SPMD sequence number exactly like the TCP backend's
  frame tags; a kind mismatch at the same sequence raises the same
  "collective desync" error.

A group whose peer dies mid-op is protected by deadlines: every returned
``Work`` future fails with ``TimeoutError`` after the configured timeout,
and ``configure``/``shutdown`` fail all pending ops of the abandoned epoch
(the socket-shutdown analogue), so the Manager's latch → flush-reconfigure
path works identically over this backend.
"""

from __future__ import annotations

import threading
from collections import deque
from datetime import timedelta
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from torchft_tpu.collectives import Collectives, ReduceOp, Work, resolve_divisor
from torchft_tpu.futures import Future, future_timeout

__all__ = ["CollectivesDevice"]


def _epoch_key(store_addr: str) -> str:
    # `{store}/torchft/{quorum_id}/{rank}` → drop the rank: all members of
    # one epoch share the prefix (manager.py reconfigure path)
    return store_addr.rsplit("/", 1)[0]


class _Op:
    def __init__(self, kind: str, world: int, meta: Tuple) -> None:
        self.kind = kind
        self.world = world
        self.meta = meta
        self.inputs: Dict[int, Any] = {}
        self.futures: Dict[int, Future] = {}


class _Epoch:
    """One quorum epoch's in-process rendezvous state."""

    def __init__(self, key: str, world: int) -> None:
        self.key = key
        self.world = world
        self.lock = threading.Lock()
        self.joined: set = set()
        self.left: set = set()
        self.dead: Optional[Exception] = None
        self.ops: Dict[int, _Op] = {}  # seq tag → op
        self.sends: Dict[Tuple[int, int, int], deque] = {}
        self.recvs: Dict[Tuple[int, int, int], deque] = {}

    def fail_pending(self, exc: Exception) -> List[Future]:
        """Called under self.lock — detach every waiter and return the
        doomed futures for the CALLER to resolve AFTER releasing the
        lock. Resolving them in here ran arbitrary continuation callbacks
        (timeout-chain copies, flight-recorder completions, user ``then``
        chains) inside the epoch lock, so a continuation that re-entered
        the collectives deadlocked [found by the analysis gate:
        callback-under-lock]."""
        self.dead = exc
        doomed: List[Future] = []
        for op in self.ops.values():
            doomed.extend(op.futures.values())
        self.ops.clear()
        for waiters in self.recvs.values():
            doomed.extend(fut for fut, _arr in waiters)
        self.recvs.clear()
        self.sends.clear()
        return doomed


_REGISTRY: Dict[str, _Epoch] = {}
_REGISTRY_LOCK = threading.Lock()

# sentinel distinguishing "no buffered send matched" from a buffered None
_NOTHING = object()


def _devices_and_spec(arr) -> Tuple[np.ndarray, Tuple[str, ...], Any]:
    """Normalize an array's sharding to (device_matrix, axis_names, spec)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

    s = arr.sharding
    if isinstance(s, NamedSharding):
        return s.mesh.devices, tuple(s.mesh.axis_names), s.spec
    if isinstance(s, SingleDeviceSharding):
        devs = np.empty((), dtype=object)
        devs[()] = list(arr.devices())[0]
        return devs, (), PartitionSpec()
    raise TypeError(
        f"CollectivesDevice requires NamedSharding or SingleDeviceSharding "
        f"arrays, got {type(s).__name__}"
    )


def _congruent(ranks_arrays: Dict[int, Any], i: int) -> None:
    """All groups' i-th arrays must agree on shape/dtype/mesh-shape/spec."""
    base = None
    for rank in sorted(ranks_arrays):
        arr = ranks_arrays[rank][i]
        devs, names, spec = _devices_and_spec(arr)
        sig = (arr.shape, str(arr.dtype), devs.shape, names, spec)
        if base is None:
            base = sig
        elif sig != base:
            raise RuntimeError(
                f"collective desync: group meshes/shardings not congruent "
                f"for array {i}: {sig} vs {base}"
            )


_PSUM_CACHE: Dict[Tuple, Callable] = {}
_PSUM_CACHE_LOCK = threading.Lock()


def _reduction_fn(mesh, specs: Tuple, op: ReduceOp, divisor: int) -> Callable:
    """Jitted shard_map reduction over the 'ft' axis, divided by
    ``divisor`` where it is not 1 (AVG arrives as SUM with the divisor
    ``world``: ``collectives.resolve_divisor``); cached per
    (mesh, specs, op, divisor) so steady-state steps never recompile."""
    import jax

    key = (mesh, specs, op, divisor)
    with _PSUM_CACHE_LOCK:
        fn = _PSUM_CACHE.get(key)
    if fn is not None:
        return fn

    red = {
        ReduceOp.SUM: jax.lax.psum,
        ReduceOp.MAX: jax.lax.pmax,
        ReduceOp.MIN: jax.lax.pmin,
    }[op]

    def block_fn(*blocks):
        outs = tuple(red(b, "ft") for b in blocks)
        if divisor != 1:
            outs = tuple((o / divisor).astype(o.dtype) for o in outs)
        return outs

    fn = jax.jit(
        jax.shard_map(block_fn, mesh=mesh, in_specs=specs, out_specs=specs)
    )
    with _PSUM_CACHE_LOCK:
        _PSUM_CACHE[key] = fn
    return fn


class CollectivesDevice(Collectives):
    """XLA-collective data plane for replica groups sharing one JAX runtime.

    Ops take and return ``jax.Array``s (``device_arrays = True``); numpy
    inputs are accepted and placed on the default device. All groups must
    issue the same ops in the same order (SPMD), as with every backend.
    """

    device_arrays = True

    def __init__(self, timeout: timedelta = timedelta(seconds=60)) -> None:
        self._timeout = timeout
        self._rank = -1
        self._world = 0
        self._epoch: Optional[_Epoch] = None
        self._op_seq = 0

    # -- lifecycle --

    def configure(self, store_addr: str, rank: int, world_size: int) -> None:
        self._leave()
        key = _epoch_key(store_addr)
        with _REGISTRY_LOCK:
            ep = _REGISTRY.get(key)
            if ep is None:
                ep = _Epoch(key, world_size)
                _REGISTRY[key] = ep
        with ep.lock:
            if ep.dead is not None:
                raise RuntimeError(f"epoch {key} already failed: {ep.dead}")
            if ep.world != world_size:
                raise RuntimeError(
                    f"epoch {key}: world_size mismatch "
                    f"({world_size} vs {ep.world})"
                )
            ep.joined.add(rank)
        self._rank = rank
        self._world = world_size
        self._epoch = ep
        self._op_seq = 0
        # rendezvous barrier: surface missing members at configure() time,
        # like the TCP backend's eager mesh dial
        import time

        deadline = time.monotonic() + self._timeout.total_seconds()
        while True:
            with ep.lock:
                if ep.dead is not None:
                    raise RuntimeError(f"epoch {key} failed: {ep.dead}")
                missing = set(range(world_size)) - ep.joined
            if not missing:
                return
            if time.monotonic() > deadline:
                raise TimeoutError(f"groups never joined epoch: {sorted(missing)}")
            time.sleep(0.005)

    def _leave(self) -> None:
        ep, self._epoch = self._epoch, None
        if ep is None:
            return
        exc = RuntimeError("collectives reconfigured before op completed")
        with ep.lock:
            ep.left.add(self._rank)
            # a departing member strands every in-flight op of the epoch —
            # detach the waiters now (the socket-shutdown analogue)
            doomed = ep.fail_pending(exc)
            # delete once every member that ever joined has left — members
            # that never joined (peer crashed before configure) must not
            # pin the epoch in the registry forever
            all_gone = ep.left >= ep.joined
        # resolve outside the lock: continuations run inline on this
        # thread and may re-enter the collectives
        for fut in doomed:
            fut.set_exception(exc)
        if all_gone:
            with _REGISTRY_LOCK:
                if _REGISTRY.get(ep.key) is ep:
                    del _REGISTRY[ep.key]

    def shutdown(self) -> None:
        self._leave()

    def size(self) -> int:
        return self._world

    def rank(self) -> int:
        return self._rank

    def plane_info(self) -> str:
        """Dashboard label: in-process device mesh ('ft' psum over ICI)."""
        return "device"

    def wire_codec(self) -> str:
        """The ICI psum moves exact device bytes — no wire codec applies,
        so error feedback is a no-op on this plane (docs/wire_plane.md)."""
        return "f32"

    # -- rendezvous plumbing --

    def _next_tag(self) -> int:
        self._op_seq += 1
        return self._op_seq

    def _rendezvous(self, kind: str, payload: Any, meta: Tuple = ()) -> Work:
        """Deposit this group's input for the next SPMD op slot; the last
        group to arrive computes and resolves everyone's future."""
        from torchft_tpu import telemetry
        from torchft_tpu.faultinject.core import fault_point

        fault_point(
            "collective.issue", match=f"device.{kind}", rank=self._rank
        )
        ep = self._epoch
        assert ep is not None, "configure() must be called first"
        if kind != "allreduce":  # allreduce accounts bytes+latency itself
            telemetry.COLLECTIVE_OPS.labels(op=kind, plane="device").inc()
        tag = self._next_tag()
        nbytes = 0
        try:
            leaves = payload if isinstance(payload, list) else [payload]
            nbytes = sum(int(getattr(a, "nbytes", 0) or 0) for a in leaves)
        except TypeError:
            pass
        fid = telemetry.FLIGHT.record_issue(
            kind, "device", nbytes, tag=tag, rank=self._rank
        )
        fut: Future = Future()
        run_op: Optional[_Op] = None
        dead: Optional[Exception] = None
        desync: Optional[RuntimeError] = None
        doomed: List[Future] = []
        with ep.lock:
            if ep.dead is not None:
                dead = ep.dead
            else:
                op = ep.ops.get(tag)
                if op is None:
                    op = _Op(kind, ep.world, meta)
                    ep.ops[tag] = op
                if op.kind != kind or op.meta != meta:
                    desync = RuntimeError(
                        f"collective desync: op {tag} is {op.kind}{op.meta}, "
                        f"this group issued {kind}{meta}"
                    )
                    # a desynced epoch can never make progress — fail
                    # everyone instead of stranding the other groups'
                    # waiters (futures resolved below, outside the lock)
                    doomed = ep.fail_pending(desync)
                else:
                    op.inputs[self._rank] = payload
                    op.futures[self._rank] = fut
                    if len(op.inputs) == op.world:
                        del ep.ops[tag]
                        run_op = op
        if dead is not None:
            fut.set_exception(dead)
            telemetry.FLIGHT.record_complete(fid, error=dead)
            return Work(future_timeout(fut, self._timeout))
        if desync is not None:
            for f in doomed:
                f.set_exception(desync)
            telemetry.FLIGHT.record_complete(fid, error=desync)
            raise desync
        if run_op is not None:
            self._compute(run_op)
        out = future_timeout(fut, self._timeout)

        def complete(f: Future) -> Any:
            telemetry.FLIGHT.record_complete(fid, error=f.exception())
            value = f.value()  # re-raises the op's failure, if any
            # completion-side injection (parity with the host plane's
            # site in CollectivesTcp._submit): `corrupt` silently
            # perturbs THIS group's finished output — the divergence-
            # sentinel adversary on the device plane
            inj = fault_point(
                "collective.complete", match=f"device.{kind}",
                rank=self._rank, wire=True,
            )
            if inj is not None:
                if inj.action == "corrupt":
                    value = _corrupt_device_result(value, inj.frac)
                elif inj.action in ("drop", "torn"):
                    # no wire semantics here: degrade to error — never a
                    # silent no-op (delay/kill already applied inline)
                    raise inj.make_exception()
            return value

        return Work(out.then(complete))

    def _compute(self, op: _Op) -> None:
        try:
            results = _COMPUTE[op.kind](op.inputs, op.meta)
        except BaseException as e:  # noqa: BLE001 — propagate via futures
            for fut in op.futures.values():
                fut.set_exception(e)
            return
        for rank, fut in op.futures.items():
            fut.set_result(results[rank])

    # -- collectives --

    def allreduce(
        self, arrays: List[Any], op: ReduceOp = ReduceOp.SUM, divisor: int = 1
    ) -> Work:
        import time

        from torchft_tpu import telemetry

        op, divisor = resolve_divisor(op, divisor, self._world)
        arrays = [_as_device(a) for a in arrays]
        nbytes = sum(int(a.nbytes) for a in arrays)
        if self._world == 1:
            if divisor != 1:
                arrays = [(a / divisor).astype(a.dtype) for a in arrays]
            # sum/avg/max/min of one input is itself; no timer registration.
            # Count the op + bytes but record NO latency observation — a
            # hard-coded 0.0 for the no-op path would drown the histogram's
            # real cross-group latencies
            telemetry.COLLECTIVE_OPS.labels(op="allreduce", plane="device").inc()
            telemetry.ALLREDUCE_BYTES.labels(plane="device").inc(nbytes)
            return Work(Future.completed(arrays))
        telemetry.COLLECTIVE_OPS.labels(op="allreduce", plane="device").inc()
        t0 = time.perf_counter()
        work = self._rendezvous("allreduce", arrays, (op, divisor))

        def observe(f: Future) -> None:
            # dispatch latency of the cross-group rendezvous + psum launch
            # (device work is async; completion is fenced by the caller)
            if f.exception() is None:
                telemetry.record_collective(
                    "allreduce", nbytes, time.perf_counter() - t0, "device",
                    count_op=False,
                )

        work.get_future().then(observe)
        return work

    def allgather(self, arr: Any) -> Work:
        return self._rendezvous("allgather", _as_device(arr))

    def broadcast(self, arr: Any, root: int = 0) -> Work:
        return self._rendezvous("broadcast", _as_device(arr), (root,))

    def reduce_scatter(self, arrays: List[Any], op: ReduceOp = ReduceOp.SUM) -> Work:
        if len(arrays) != self._world:
            raise ValueError(
                f"reduce_scatter needs {self._world} inputs, got {len(arrays)}"
            )
        return self._rendezvous("reduce_scatter", [_as_device(a) for a in arrays], (op,))

    def alltoall(self, arrays: List[Any]) -> Work:
        if len(arrays) != self._world:
            raise ValueError(f"alltoall needs {self._world} inputs, got {len(arrays)}")
        return self._rendezvous("alltoall", [_as_device(a) for a in arrays])

    def barrier(self) -> Work:
        if self._world == 1:
            return Work.completed(None)
        return self._rendezvous("barrier", None)

    def send(self, arr: Any, dst: int, tag: int = 0) -> Work:
        ep = self._epoch
        assert ep is not None, "configure() must be called first"
        key = (self._rank, dst, tag)
        arr = _as_device(arr)
        matched: Optional[Future] = None
        with ep.lock:
            if ep.dead is not None:
                dead = ep.dead
            else:
                dead = None
                waiters = ep.recvs.get(key)
                if waiters:
                    matched, _target = waiters.popleft()
                else:
                    ep.sends.setdefault(key, deque()).append(arr)
        if dead is not None:
            return Work(Future.failed(dead))
        if matched is not None:
            # resolve outside the lock: the receiver's `place` continuation
            # (and any user `then`) runs inline on this thread
            matched.set_result(arr)
        return Work.completed(None)  # buffered send, like TCP's sendall

    def recv(self, arr: Any, src: int, tag: int = 0) -> Work:
        ep = self._epoch
        assert ep is not None, "configure() must be called first"
        key = (src, self._rank, tag)
        fut: Future = Future()
        got = _NOTHING
        with ep.lock:
            if ep.dead is not None:
                dead = ep.dead
            else:
                dead = None
                buffered = ep.sends.get(key)
                if buffered:
                    got = buffered.popleft()
                else:
                    ep.recvs.setdefault(key, deque()).append((fut, arr))
        if dead is not None:
            fut.set_exception(dead)
            return Work(future_timeout(fut, self._timeout))
        if got is not _NOTHING:
            fut.set_result(got)  # outside the lock — continuations inline

        def place(f: Future) -> Any:
            # received payload keeps its device placement; in-place numpy
            # semantics only apply when the caller handed us numpy
            got = f.value()
            if isinstance(arr, np.ndarray):
                arr[...] = np.asarray(got).reshape(arr.shape)
                return arr
            return got

        return Work(future_timeout(fut, self._timeout).then(place))


def _as_device(arr: Any):
    import jax
    import jax.numpy as jnp

    if isinstance(arr, jax.Array):
        return arr
    return jnp.asarray(arr)


def _corrupt_device_result(value: Any, frac: float) -> Any:
    """``corrupt(frac)`` injection semantics on the device plane: +1 on
    the leading ``frac`` of the first output's elements, THIS group only
    (see collectives._corrupt_buffers — same adversary, immutable-array
    edition: the perturbed copy replaces the result)."""
    import jax.numpy as jnp

    arrays = value if isinstance(value, (list, tuple)) else [value]
    out = list(arrays)
    for i, arr in enumerate(out):
        size = int(getattr(arr, "size", 0) or 0)
        if not size:
            continue
        host = np.array(arr)
        n = max(1, int(size * frac))
        host.reshape(-1)[:n] += host.dtype.type(1)
        out[i] = jnp.asarray(host)
        break
    if isinstance(value, (list, tuple)):
        return type(value)(out)
    return out[0]


# ---------------------------------------------------------------------------
# op implementations (run once per rendezvous, on the last-arriving thread;
# data never leaves the devices — transfers are D2D)
# ---------------------------------------------------------------------------


def _stack_over_ft(per_rank: Dict[int, Any], idx: int, big_mesh=None):
    """Build (global_array, big_mesh, global_spec, per-rank shardings) for
    the idx-th array of each rank, stacked on a leading 'ft' mesh axis.
    Pass a previously-built ``big_mesh`` to reuse it across leaves (every
    leaf of one op spans the same devices)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    _congruent(per_rank, idx)
    ranks = sorted(per_rank)
    arrs = [per_rank[r][idx] for r in ranks]
    _devs0, names0, spec0 = _devices_and_spec(arrs[0])
    if big_mesh is None:
        big_devs = np.stack([_devices_and_spec(a)[0] for a in arrs])
        big_mesh = Mesh(big_devs, ("ft", *names0))
    elif big_mesh.axis_names != ("ft", *names0):
        raise RuntimeError(
            "collective desync: arrays within one allreduce span "
            "different meshes"
        )
    gspec = PartitionSpec("ft", *spec0)
    shards = []
    for a in arrs:
        for s in a.addressable_shards:
            shards.append(jnp.expand_dims(s.data, 0))
    garr = jax.make_array_from_single_device_arrays(
        (len(ranks), *arrs[0].shape), NamedSharding(big_mesh, gspec), shards
    )
    return garr, big_mesh, gspec, [a.sharding for a in arrs]


def _unstack_over_ft(out, shardings, per_rank_devices) -> List[Any]:
    """Split a reduced global array back into per-rank arrays on their
    original devices/shardings (a squeeze per shard — metadata-cheap)."""
    import jax
    import jax.numpy as jnp

    by_dev = {s.device: s.data for s in out.addressable_shards}
    results = []
    for sharding, devices in zip(shardings, per_rank_devices):
        datas = [jnp.squeeze(by_dev[d], axis=0) for d in devices]
        results.append(
            jax.make_array_from_single_device_arrays(
                out.shape[1:], sharding, datas
            )
        )
    return results


def _compute_allreduce(inputs: Dict[int, List[Any]], meta: Tuple) -> Dict[int, Any]:
    op, divisor = meta
    ranks = sorted(inputs)
    n_arrays = {len(v) for v in inputs.values()}
    if len(n_arrays) != 1:
        raise RuntimeError(f"collective desync: array counts differ: {n_arrays}")
    (n,) = n_arrays

    garrs, specs, all_shardings, all_devices = [], [], [], []
    big_mesh = None
    for i in range(n):
        g, big_mesh, spec, shardings = _stack_over_ft(inputs, i, big_mesh)
        garrs.append(g)
        specs.append(spec)
        all_shardings.append(shardings)
        all_devices.append(
            [[s.device for s in inputs[r][i].addressable_shards] for r in ranks]
        )

    fn = _reduction_fn(big_mesh, tuple(specs), op, divisor)
    outs = fn(*garrs)
    per_rank: Dict[int, List[Any]] = {r: [] for r in ranks}
    for i, out in enumerate(outs):
        rank_arrays = _unstack_over_ft(out, all_shardings[i], all_devices[i])
        for r, a in zip(ranks, rank_arrays):
            per_rank[r].append(a)
    return per_rank


def _compute_allgather(inputs: Dict[int, Any], meta: Tuple) -> Dict[int, Any]:
    import jax

    ranks = sorted(inputs)
    return {
        r: [
            jax.device_put(inputs[j], inputs[r].sharding)
            for j in ranks
        ]
        for r in ranks
    }


def _compute_broadcast(inputs: Dict[int, Any], meta: Tuple) -> Dict[int, Any]:
    import jax

    (root,) = meta
    src = inputs[root]
    return {
        r: (src if r == root else jax.device_put(src, inputs[r].sharding))
        for r in sorted(inputs)
    }


def _compute_reduce_scatter(
    inputs: Dict[int, List[Any]], meta: Tuple
) -> Dict[int, Any]:
    import jax
    import jax.numpy as jnp

    (op,) = meta
    ranks = sorted(inputs)
    world = len(ranks)
    out: Dict[int, Any] = {}
    for r in ranks:
        target_sharding = inputs[r][r].sharding
        parts = [jax.device_put(inputs[j][r], target_sharding) for j in ranks]
        if op in (ReduceOp.SUM, ReduceOp.AVG):
            acc = parts[0]
            for p in parts[1:]:
                acc = acc + p
            if op == ReduceOp.AVG:
                acc = (acc / world).astype(acc.dtype)
        elif op == ReduceOp.MAX:
            acc = parts[0]
            for p in parts[1:]:
                acc = jnp.maximum(acc, p)
        else:
            acc = parts[0]
            for p in parts[1:]:
                acc = jnp.minimum(acc, p)
        out[r] = acc
    return out


def _compute_alltoall(inputs: Dict[int, List[Any]], meta: Tuple) -> Dict[int, Any]:
    import jax

    ranks = sorted(inputs)
    return {
        r: [
            jax.device_put(inputs[j][r], inputs[r][r].sharding)
            for j in ranks
        ]
        for r in ranks
    }


def _compute_barrier(inputs: Dict[int, Any], meta: Tuple) -> Dict[int, Any]:
    return {r: None for r in inputs}


_COMPUTE: Dict[str, Callable[[Dict[int, Any], Tuple], Dict[int, Any]]] = {
    "allreduce": _compute_allreduce,
    "allgather": _compute_allgather,
    "broadcast": _compute_broadcast,
    "reduce_scatter": _compute_reduce_scatter,
    "alltoall": _compute_alltoall,
    "barrier": _compute_barrier,
}
