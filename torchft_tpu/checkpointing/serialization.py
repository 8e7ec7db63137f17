"""Streaming pytree (de)serialization for checkpoint transfer.

The reference streams torch state dicts with
``torch.distributed._serialization`` after a pytree flatten
(torchft/checkpointing/http_transport.py:219-241, _serialization.py:8-33).
The JAX equivalent: ``jax.tree_util`` flattens the state into leaves; array
leaves (``jax.Array`` / ``np.ndarray`` / scalars) travel as raw host
buffers described by a small pickled header, everything else is pickled
whole. Device arrays are pulled to host at flatten time — on multi-host
deployments each process serializes its addressable shards, and placement
back onto the mesh is the loader's job (the ``NamedSharding`` analogue of
the reference's DTensor-spec handling, pg_transport.py:104-114).

Wire layout::

    u64 header_len | pickle((treedef, leaf_infos)) | raw buffers...

where ``leaf_infos[i]`` is one of

* ``("arr", dtype_str, shape, nbytes)`` — dense array leaf (one buffer);
* ``("shards", dtype_str, global_shape, mesh_desc, spec_entries,
  [(index_desc, nbytes), ...])`` — a sharded ``jax.Array`` leaf shipped
  **per shard** (one buffer per distinct shard): the NamedSharding
  analogue of the reference's DTensor-spec transfer
  (pg_transport.py:104-114, 217-247). Only this process's addressable
  shards travel, deduplicated by shard index (replicated copies ship
  once), so a sharded group never gathers the full model onto one host
  and multi-host groups each contribute their own shards. The receiver
  gets a :class:`ShardedArray` placeholder and rebuilds the device array
  on its own congruent mesh via :func:`from_transfer_tree`;
* ``("obj", pickled_bytes)`` — non-array leaf (inline, no buffer).
"""

from __future__ import annotations

import io
import pickle
import struct
import time
from typing import Any, BinaryIO, List, Tuple

import numpy as np

_LEN = struct.Struct("<Q")

__all__ = [
    "Flattening",
    "flatten_state",
    "unflatten_state",
    "save_state",
    "load_state",
    "buffer_sizes",
    "ShardedArray",
    "from_transfer_tree",
    "ArraySpec",
    "spec_tree_from_header",
]


def _tree_util():
    # Imported lazily so the coordination/data-plane layers stay importable
    # on hosts without jax (e.g. a CPU-only lighthouse box).
    import jax

    return jax.tree_util


def _is_array(leaf: Any) -> bool:
    if isinstance(leaf, np.ndarray):
        return True
    try:
        import jax

        return isinstance(leaf, jax.Array)
    except Exception:
        return False


def _to_host(leaf: Any, copy: bool = False) -> np.ndarray:
    arr = np.ascontiguousarray(np.asarray(leaf))
    if copy and (arr is leaf or not arr.flags.owndata):
        # ascontiguousarray returns the SAME object for already-contiguous
        # numpy inputs, and np.asarray of a CPU jax.Array can be a zero-copy
        # view over the XLA buffer — either way the "snapshot" would alias
        # live storage. Callers that need a true backup (LocalSGD/DiLoCo
        # rollback) pass copy=True to force ownership.
        arr = arr.copy()
    return arr


def to_host_tree(tree: Any, copy: bool = False) -> Any:
    """Pull every array leaf of a pytree to a contiguous host buffer (the
    shared device→host step used by gradient averaging, LocalSGD backups and
    checkpoint staging). With ``copy=True`` every leaf is guaranteed to own
    its buffer (no aliasing of the input), which backup/rollback paths
    require."""
    return _tree_util().tree_map(lambda l: _to_host(l, copy=copy), tree)


def as_bytes(arr: np.ndarray) -> memoryview:
    """Byte view that also works for ml_dtypes arrays (bfloat16 etc.), whose
    buffers plain ``memoryview(...)`` rejects."""
    return memoryview(np.ascontiguousarray(arr).reshape(-1).view(np.uint8))


def _dtype_name(dtype: np.dtype) -> str:
    # dtype.name (not .str): ml_dtypes report '<V2'-style .str which does not
    # round-trip through np.dtype(); names like 'bfloat16' do once ml_dtypes
    # is imported (jax always imports it).
    return dtype.name


def _resolve_dtype(name: str) -> np.dtype:
    try:
        return np.dtype(name)
    except TypeError:
        import ml_dtypes  # noqa: F401 — registers bfloat16/fp8 names

        return np.dtype(name)


# ---------------------------------------------------------------------------
# sharded leaves (NamedSharding descriptor — the DTensor-spec analogue)
# ---------------------------------------------------------------------------


def _index_desc(index: Tuple, shape: Tuple[int, ...]) -> Tuple:
    """Canonicalize a shard's index (tuple of slices) into nested
    ``(start, stop)`` pairs that pickle cleanly and compare by value."""
    out = []
    for sl, n in zip(index, shape):
        start = 0 if sl.start is None else int(sl.start)
        stop = n if sl.stop is None else int(sl.stop)
        out.append((start, stop))
    return tuple(out)


def _sharding_desc(arr) -> Any:
    """``(axis_names, mesh_shape, spec_entries)`` for a NamedSharding-ed
    jax.Array spanning >1 device, else None (dense path)."""
    from jax.sharding import NamedSharding

    s = getattr(arr, "sharding", None)
    if not isinstance(s, NamedSharding):
        return None
    if len(s.mesh.devices.flat) <= 1:
        return None
    return (
        tuple(s.mesh.axis_names),
        tuple(s.mesh.devices.shape),
        tuple(s.spec),
    )


class ShardedArray:
    """Host-side carrier for a sharded ``jax.Array`` in transit: global
    shape/dtype, the sender's mesh/spec descriptor, and its (deduplicated)
    addressable shards. Rebuild on the receiver with :meth:`to_jax` against
    a congruent local mesh, or assemble densely with :meth:`full`."""

    def __init__(
        self,
        dtype: np.dtype,
        shape: Tuple[int, ...],
        mesh_desc: Tuple,
        spec_entries: Tuple,
        shards: List[Tuple[Tuple, np.ndarray]],
    ) -> None:
        self.dtype = dtype
        self.shape = tuple(shape)
        self.mesh_desc = mesh_desc
        self.spec_entries = spec_entries
        self.shards = shards  # [(index_desc, host_array), ...]

    @property
    def nbytes(self) -> int:
        return sum(s.nbytes for _, s in self.shards)

    def to_jax(self, mesh):
        """Place the shards onto ``mesh`` with the sender's PartitionSpec.
        The mesh must be congruent (same axis names/sizes for the sharded
        axes); each local device receives exactly its shard — no dense
        intermediate, no cross-device gather."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec

        sharding = NamedSharding(mesh, PartitionSpec(*self.spec_entries))
        by_index = {idx: data for idx, data in self.shards}
        arrays = []
        for dev, index in sharding.addressable_devices_indices_map(
            self.shape
        ).items():
            data = by_index.get(_index_desc(index, self.shape))
            if data is None:
                raise ValueError(
                    f"missing shard {index} for leaf {self.shape}; sender "
                    f"mesh {self.mesh_desc} is not congruent with the local mesh"
                )
            arrays.append(jax.device_put(data, dev))
        return jax.make_array_from_single_device_arrays(
            self.shape, sharding, arrays
        )

    def full(self) -> np.ndarray:
        """Assemble a dense host array (fallback when no mesh is at hand —
        requires the sender's shards to cover the global array)."""
        out = np.empty(self.shape, dtype=self.dtype)
        # Boolean coverage mask, not a summed element count: overlapping
        # shards would double-count and mask uninitialized gaps (round-2
        # advisor finding).
        covered = np.zeros(self.shape, dtype=bool)
        for idx, data in self.shards:
            sl = tuple(slice(a, b) for a, b in idx)
            out[sl] = data
            covered[sl] = True
        if not covered.all():
            raise ValueError(
                "shards do not cover the array (multi-host sender); "
                "rebuild with to_jax(mesh) instead"
            )
        return out


def from_transfer_tree(tree: Any, mesh) -> Any:
    """Convert every :class:`ShardedArray` leaf back into a ``jax.Array``
    on ``mesh`` (the receiver-side half of the sharded transfer)."""
    tu = _tree_util()
    return tu.tree_map(
        lambda l: l.to_jax(mesh) if isinstance(l, ShardedArray) else l,
        tree,
        is_leaf=lambda l: isinstance(l, ShardedArray),
    )


# device-to-host copies kept in flight beyond the one being read. On a
# v5e the blocking read of one leaf after another lands 7.3 GB of f32 in
# 2.2 s, with one or two copies ahead in 1.8 s, and with EVERY leaf's copy
# issued before the first read — what suits ddp's exchange, whose copies
# run under the step's compute — in 5.6-8.9 s (PERF.md §6, PR 49): the
# runtime fills the copies it has been handed side by side, each slowly
_COPIES_AHEAD = 2


class Flattening:
    """A state tree on its way to the host, leaf by leaf.
    :meth:`buffers` yields the host buffers in stream order as each lands,
    with the next ``_COPIES_AHEAD`` device-to-host copies already issued,
    so a consumer works on leaf *i* under the landing of leaf *i + 1*;
    :attr:`landed` holds what has arrived so far and :attr:`header` is
    complete once the stream is exhausted; :attr:`wait_s` is how long
    the reads blocked. ``nbytes`` / ``nbuffers`` are known at once (from
    shapes), for callers that size a pool before the bytes exist."""

    def __init__(self, state: Any) -> None:
        leaves, self._treedef = _tree_util().tree_flatten(state)
        self._infos: List[Tuple] = []
        # per leaf: ("obj", leaf) | ("arr", leaf) | ("shards", leaf, desc,
        # [(index_desc, single-device array), ...])
        self._pending: List[Tuple] = []
        # every buffer's source in stream order, and how many of them have
        # had their copy issued
        self._parts: List[Any] = []
        self._issued = 0
        self.landed: List[np.ndarray] = []
        # seconds the reading thread stood waiting for bytes to land
        self.wait_s = 0.0
        for leaf in leaves:
            if not _is_array(leaf):
                self._pending.append(("obj", leaf))
                continue
            desc = _sharding_desc(leaf)
            if desc is None:
                self._pending.append(("arr", leaf))
                self._parts.append(leaf)
            else:
                seen = {}
                for s in leaf.addressable_shards:
                    idx = _index_desc(s.index, leaf.shape)
                    if idx not in seen:  # replicas ship once
                        seen[idx] = s.data
                self._pending.append(("shards", leaf, desc, list(seen.items())))
                self._parts.extend(seen.values())
        self.nbuffers = len(self._parts)
        self.nbytes = sum(
            int(p.size) * np.dtype(p.dtype).itemsize for p in self._parts
        )

    def _land(self, part: Any) -> np.ndarray:
        """The next buffer of the stream (``part`` is its source), read
        once the copies up to ``_COPIES_AHEAD`` past it are issued."""
        upto = min(len(self._parts), len(self.landed) + 1 + _COPIES_AHEAD)
        for ahead in self._parts[self._issued : upto]:
            if not isinstance(ahead, np.ndarray):
                ahead.copy_to_host_async()
        self._issued = max(self._issued, upto)
        t0 = time.perf_counter()
        host = _to_host(part)
        self.wait_s += time.perf_counter() - t0
        self.landed.append(host)
        return host

    def buffers(self):
        """Yield each host buffer in stream order (blocking until it has
        landed); one pass."""
        for item in self._pending:
            if item[0] == "obj":
                self._infos.append(("obj", pickle.dumps(item[1])))
            elif item[0] == "arr":
                host = self._land(item[1])
                self._infos.append(
                    ("arr", _dtype_name(host.dtype), host.shape, host.nbytes)
                )
                yield host
            else:
                _, leaf, (axis_names, mesh_shape, spec_entries), shards = item
                shard_meta = []
                for idx, data in shards:
                    host = self._land(data)
                    shard_meta.append((idx, host.nbytes))
                    yield host
                self._infos.append(
                    (
                        "shards",
                        _dtype_name(np.dtype(leaf.dtype)),
                        tuple(leaf.shape),
                        (axis_names, mesh_shape),
                        spec_entries,
                        shard_meta,
                    )
                )
        self._pending = []

    @property
    def header(self) -> bytes:
        assert len(self._infos) == self._treedef.num_leaves, (
            "the header is complete once buffers() is exhausted"
        )
        return pickle.dumps((self._treedef, self._infos))


def flatten_state(state: Any) -> Tuple[bytes, List[np.ndarray]]:
    """Flatten a pytree into ``(header_bytes, array_buffers)``."""
    flat = Flattening(state)
    for _ in flat.buffers():
        pass
    return flat.header, flat.landed


def buffer_sizes(infos: List[Tuple]) -> List[int]:
    """Byte size of every raw buffer that follows the header, in stream
    order (the transports' manifest for chunked / per-buffer transfer)."""
    sizes: List[int] = []
    for info in infos:
        if info[0] == "arr":
            sizes.append(info[3])
        elif info[0] == "shards":
            sizes.extend(n for _, n in info[5])
    return sizes


def unflatten_state(header: bytes, buffers: List[np.ndarray]) -> Any:
    """Inverse of :func:`flatten_state`. Sharded leaves come back as
    :class:`ShardedArray` placeholders — pass the tree through
    :func:`from_transfer_tree` (or call ``.full()``) to materialize."""
    treedef, infos = pickle.loads(header)
    leaves: List[Any] = []
    it = iter(buffers)
    for info in infos:
        if info[0] == "arr":
            _, dtype, shape, _ = info
            buf = next(it)
            leaves.append(np.frombuffer(buf, dtype=_resolve_dtype(dtype)).reshape(shape))
        elif info[0] == "shards":
            _, dtype, shape, mesh_desc, spec_entries, shard_meta = info
            np_dtype = _resolve_dtype(dtype)
            shards = []
            for idx, _nbytes in shard_meta:
                shard_shape = tuple(b - a for a, b in idx)
                shards.append(
                    (
                        idx,
                        np.frombuffer(next(it), dtype=np_dtype).reshape(shard_shape),
                    )
                )
            leaves.append(
                ShardedArray(np_dtype, shape, mesh_desc, spec_entries, shards)
            )
        else:
            leaves.append(pickle.loads(info[1]))
    return _tree_util().tree_unflatten(treedef, leaves)


class ArraySpec:
    """jax-free shape/dtype spec leaf (the ``jax.ShapeDtypeStruct``
    stand-in :func:`spec_tree_from_header` falls back to on hosts
    without jax)."""

    def __init__(self, shape: Tuple[int, ...], dtype: np.dtype) -> None:
        self.shape = tuple(shape)
        self.dtype = dtype

    def __repr__(self) -> str:  # pragma: no cover — debug aid
        return f"ArraySpec(shape={self.shape}, dtype={self.dtype})"


def spec_tree_from_header(header: bytes) -> Any:
    """Rebuild the transferred pytree's SHAPE — ``jax.ShapeDtypeStruct``
    leaves for arrays (global shape for sharded leaves), the actual
    objects for ``obj`` leaves — from a transfer header alone. This is
    what the heal/compile overlap consumes: the header arrives before any
    bulk bytes, so a healer can start jit compilation from these specs
    while the stripes stream (docs/heal_plane.md)."""
    treedef, infos = pickle.loads(header)
    try:
        import jax

        make = jax.ShapeDtypeStruct
    except Exception:  # noqa: BLE001 — jax-free hosts get the plain spec
        make = ArraySpec
    leaves: List[Any] = []
    for info in infos:
        if info[0] == "arr":
            _, dtype, shape, _ = info
            leaves.append(make(tuple(shape), _resolve_dtype(dtype)))
        elif info[0] == "shards":
            _, dtype, shape = info[0], info[1], info[2]
            leaves.append(make(tuple(shape), _resolve_dtype(dtype)))
        else:
            leaves.append(pickle.loads(info[1]))
    return _tree_util().tree_unflatten(treedef, leaves)


def save_state(state: Any, f: BinaryIO) -> None:
    """Stream a pytree to a file object."""
    header, buffers = flatten_state(state)
    f.write(_LEN.pack(len(header)))
    f.write(header)
    for buf in buffers:
        f.write(as_bytes(buf))


def load_state(f: BinaryIO) -> Any:
    """Inverse of :func:`save_state`."""
    (header_len,) = _LEN.unpack(f.read(_LEN.size))
    header = f.read(header_len)
    _, infos = pickle.loads(header)
    buffers: List[np.ndarray] = []
    for nbytes in buffer_sizes(infos):
        raw = f.read(nbytes)
        if len(raw) != nbytes:
            raise EOFError("truncated checkpoint stream")
        buffers.append(np.frombuffer(raw, dtype=np.uint8))
    return unflatten_state(header, buffers)


def dumps_state(state: Any) -> bytes:
    buf = io.BytesIO()
    save_state(state, buf)
    return buf.getvalue()


def loads_state(data: bytes) -> Any:
    return load_state(io.BytesIO(data))
