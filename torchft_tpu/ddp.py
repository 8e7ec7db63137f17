"""Cross-replica-group gradient averaging — the DDP comm-hook analogue.

The reference registers a DDP communication hook that routes each gradient
bucket through ``manager.allreduce`` (torchft/ddp.py:32-71). JAX has no
backward hooks: gradients arrive as one pytree from ``jax.grad``, already
reduced *within* the replica group by XLA's ICI collectives. This module
averages them *across* replica groups on host buffers (the managed axis
that can resize without recompiling the train step).

Bucketing mirrors DDP's reducer: leaves are packed into ~25 MB flat
buffers so each quorum-managed allreduce moves a large contiguous span
(fewer ring rounds, full-bandwidth frames) instead of one op per leaf.

The host path is a three-stage pipeline, the role NCCL's async stream
plays in the reference (process_group.py:431-447): while bucket k rides
the TCP ring on the collectives op thread, bucket k+1's device→host
transfers complete on the main thread and bucket k−1's averaged pieces
are already being device_put back — so wire time hides behind transfer
time instead of adding to it.

Pipelined-commit note (docs/commit_pipeline.md): callers must resolve
any in-flight commit vote (``manager.resolve_pending_commit()``) before
calling :func:`allreduce_gradients` for the next step — the Manager
raises otherwise, because gradients of a speculative (possibly about to
be rolled back) state must never enter a collective. The bucket buffers
here always own their memory (``np.empty``, packed with ``np.copyto``),
so the in-place ring reduction can never corrupt the caller's retained
gradient pytree across a rollback/replay.

The bucket buffers live as long as the bucket plan does. Mapping fresh
host pages costs ~4 us each where writing touched ones runs at memory
speed (PERF.md §5), so a manager's first exchange with a plan allocates
the buffers and every later one packs into them (:class:`_KeptBuckets`
holds the three rules that keep this as safe as a fresh buffer; the
``exchange`` span counts ``buckets_reused`` beside ``buckets``). The cost
is host memory: a tree's size per manager stays resident between
exchanges — for ``LocalSGD``, which exchanges every H steps, a model's
size held for one saved allocation per sync.

What the exchange spends its time on is visible from inside
(docs/observability.md "Spans in the profiler's trace"): one ``exchange``
span around the call, carrying the per-step sums and the process's
CPU-time deltas, and in a profiler trace one ``tft.exchange.*`` event per
piece of work per bucket, on the thread that did it.
"""

from __future__ import annotations

import os
import resource
import time
import weakref
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from torchft_tpu.telemetry import tracing

__all__ = ["flatten_buckets", "unflatten_buckets", "allreduce_gradients"]

_DEFAULT_BUCKET_BYTES = 25 * 1024 * 1024


def default_bucket_bytes() -> int:
    """Streamed-bucket size for the host wire plane — the
    ``TORCHFT_WIRE_BUCKET_BYTES`` env knob, default 25 MB
    (docs/wire_plane.md: smaller buckets start the wire earlier but pay
    more per-op overhead)."""
    raw = os.environ.get("TORCHFT_WIRE_BUCKET_BYTES")
    if raw:
        try:
            return max(1 << 16, int(raw))
        except ValueError:
            pass
    return _DEFAULT_BUCKET_BYTES


def _leaves(tree: Any) -> Tuple[List[Any], Any]:
    import jax

    return jax.tree_util.tree_flatten(tree)


def flatten_buckets(
    leaves: Sequence[np.ndarray], bucket_bytes: int = _DEFAULT_BUCKET_BYTES
) -> List[Tuple[np.ndarray, List[int]]]:
    """Pack host arrays into flat float buffers of ~``bucket_bytes``.

    Returns ``[(buffer, leaf_indices), ...]``; same-dtype leaves are packed
    together in input order (a dtype change forces a new bucket, as packing
    requires a uniform element type)."""
    buckets: List[Tuple[np.ndarray, List[int]]] = []
    for idxs in plan_buckets(
        [(l.dtype, l.nbytes) for l in leaves], bucket_bytes
    ):
        buf = np.concatenate([leaves[i].reshape(-1) for i in idxs])
        buckets.append((buf, idxs))
    return buckets


def plan_buckets(
    meta: Sequence[Tuple[np.dtype, int]], bucket_bytes: int = _DEFAULT_BUCKET_BYTES
) -> List[List[int]]:
    """Group item indices into ~``bucket_bytes`` same-dtype buckets from
    (dtype, nbytes) metadata alone — so the plan exists before any device
    buffer has been pulled to host (the pipeline needs it up front)."""
    plan: List[List[int]] = []
    cur: List[int] = []
    cur_bytes = 0
    cur_dtype = None
    for i, (dtype, nbytes) in enumerate(meta):
        if cur and (dtype != cur_dtype or cur_bytes + nbytes > bucket_bytes):
            plan.append(cur)
            cur, cur_bytes = [], 0
        cur.append(i)
        cur_bytes += nbytes
        cur_dtype = dtype
    if cur:
        plan.append(cur)
    return plan


def unflatten_buckets(
    buckets: Sequence[Tuple[np.ndarray, List[int]]],
    leaves: Sequence[np.ndarray],
) -> List[np.ndarray]:
    """Scatter reduced buffers back into leaf-shaped arrays."""
    out: List[np.ndarray] = list(leaves)
    for buf, idxs in buckets:
        offset = 0
        for i in idxs:
            n = leaves[i].size
            out[i] = buf[offset : offset + n].reshape(leaves[i].shape)
            offset += n
    return out


class _Item:
    """One host transfer unit: a dense leaf or a single shard of a
    process-spanning leaf. Metadata (dtype/size) is known before the
    device buffer is, which is what lets buckets be planned up front."""

    __slots__ = ("leaf_pos", "src", "dtype", "shape", "index")

    def __init__(self, leaf_pos, src, dtype, shape, index=None) -> None:
        self.leaf_pos = leaf_pos
        self.src = src  # jax.Array / shard data / numpy
        self.dtype = np.dtype(dtype)
        self.shape = tuple(shape)
        self.index = index  # shard index desc, or None for dense

    @property
    def size(self) -> int:
        return int(np.prod(self.shape, dtype=np.int64))

    @property
    def nbytes(self) -> int:
        return self.size * self.dtype.itemsize


class _KeptBuckets:
    """One bucket plan's staging buffers, from one exchange of a manager to
    its next. They belong to that manager (``_KEPT``), never to the module:
    several managers run in one process.

    A buffer is rewritten only when nothing can still read or write it:

    * what the last exchange placed from it is on the device. ``placed``
      holds the arrays whose ``device_put`` had not finished when that
      exchange returned, and :func:`_take_kept` blocks on them (a step the
      commit refused never ran the ``apply`` that would have);
    * the last ring that was handed it has left the op thread. The manager
      completes a timed-out or failed op with the very buffers while the
      op thread may still be inside the ring, always with its error
      latched: an exchange that ends with ``errored()`` set keeps nothing;
    * nothing handed out aliases it. A host leaf comes back as a slice of
      its bucket, and on the CPU backend ``device_put`` of an aligned NumPy
      array may share its memory (:func:`_put_copies`): ``bufs`` holds
      ``None`` for a bucket with such a piece, and that bucket is
      allocated anew every time.
    """

    __slots__ = ("key", "bufs", "placed")

    def __init__(self, key: Tuple[Tuple[np.dtype, int], ...]) -> None:
        self.key = key  # per bucket (dtype, element count)
        self.bufs: List[Optional[np.ndarray]] = [None] * len(key)
        self.placed: List[Any] = []


_KEPT: "weakref.WeakKeyDictionary[Any, _KeptBuckets]" = weakref.WeakKeyDictionary()


def _put_copies(src: Any) -> bool:
    """Whether an averaged piece goes back to ``src``'s devices as an array
    that owns its memory: a device array off the CPU backend."""
    import jax

    return (
        isinstance(src, jax.Array)
        and next(iter(src.sharding.device_set)).platform != "cpu"
    )


def _take_kept(manager, key: Tuple[Tuple[np.dtype, int], ...]) -> _KeptBuckets:
    """``manager``'s kept buffers if they are of this plan and safe to
    rewrite, else an empty set. Taken out of ``_KEPT`` for the length of
    the exchange: only one that ends clean puts its set back."""
    import jax

    kept = _KEPT.pop(manager, None)
    if (
        kept is None
        or kept.key != key
        # given away (donated) before its transfer was seen to end
        or any(a.is_deleted() for a in kept.placed)
    ):
        return _KeptBuckets(key)
    jax.block_until_ready(kept.placed)
    kept.placed = []
    return kept


def allreduce_gradients(
    manager,
    grads: Any,
    bucket_bytes: Optional[int] = None,
    error_feedback: Optional[Any] = None,
) -> Any:
    """Average a gradient pytree across replica groups through the Manager.

    Two paths, chosen by the Manager's configured data plane:

    * **device path** (``CollectivesDevice`` — groups sharing one JAX
      runtime): the ``jax.Array`` leaves go straight into
      ``manager.allreduce_many``; the averaging is one jitted psum over the
      'ft' mesh axis riding ICI and the gradients never touch the host.
    * **host path** (``CollectivesTcp`` — groups in separate processes,
      DCN): a per-bucket pipeline — D2H of bucket k+1 overlaps the TCP
      ring of bucket k overlaps the H2D of bucket k−1. Averaged leaves
      come back as device arrays (the H2D already happened), ready for
      the jitted optimizer update.

    Both scale by ``1/num_participants()`` and swallow errors into the
    Manager's latched state.

    ``error_feedback`` (a :class:`~torchft_tpu.wire_codec.ErrorFeedback`,
    host path only): each bucket is compensated with the committed
    residual, projected onto the wire codec's grid, and its fresh
    residual STAGED — the caller promotes or discards it with the step's
    fate (``commit()``/``rollback()``; ManagedOptimizer wires this
    automatically). ``bucket_bytes`` defaults to the
    ``TORCHFT_WIRE_BUCKET_BYTES`` knob.
    """
    import jax

    if bucket_bytes is None:
        bucket_bytes = default_bucket_bytes()
    leaves, treedef = _leaves(grads)
    # duck-typed managers (tests, benches) may have no step counter
    step = int(getattr(manager, "current_step", lambda: -1)())

    ru0 = resource.getrusage(resource.RUSAGE_SELF)
    with tracing.TRACER.span("exchange", step=step) as span:
        if getattr(manager, "device_data_plane", lambda: False)():
            out = manager.allreduce_many(leaves).wait()
            counters: Dict[str, Any] = {}
        else:
            out, counters = _host_exchange(
                manager, leaves, bucket_bytes, error_feedback, step
            )
        ru1 = resource.getrusage(resource.RUSAGE_SELF)
        # one syscall at each end, all threads of the process — the
        # runtime's own among them, whose work no span of ours covers
        counters.update(
            step=step,
            utime_s=round(ru1.ru_utime - ru0.ru_utime, 6),
            stime_s=round(ru1.ru_stime - ru0.ru_stime, 6),
        )
        span.set(**counters)
        # an annotation takes its stats at entry: a zero-length one at exit
        # carries the counters into the profiler's trace
        with tracing.annotate("exchange.counters", **counters):
            pass
    return jax.tree_util.tree_unflatten(treedef, out)


def _host_exchange(
    manager,
    leaves: List[Any],
    bucket_bytes: int,
    error_feedback: Optional[Any],
    step: int,
) -> Tuple[List[Any], Dict[str, Any]]:
    """The host path of :func:`allreduce_gradients`: averaged leaves, and
    the step's sums for the ``exchange`` span."""
    import jax

    from torchft_tpu.collectives import record_wire_stage
    from torchft_tpu.telemetry.anatomy import LEDGER as _ledger

    # host path. A leaf sharded across processes (multi-host group) cannot
    # be gathered: this process averages only its addressable shards —
    # correct because same-rank peers across groups hold the same shard
    # indices (congruent meshes), and replicas within the process are
    # averaged once and re-placed to every holder.
    from torchft_tpu.checkpointing.serialization import _index_desc

    sums = {"buckets_reused": 0, "d2h_wait_s": 0.0, "pack_s": 0.0}  # per step

    # stage 0: kick off D2H for every leaf/shard before anything blocks.
    # No guard: a runtime that rejects the prefetch would serialise every
    # bucket's D2H behind the ring, and that must be seen, not absorbed
    with tracing.annotate("exchange.d2h_issue", step=step, leaves=len(leaves)):
        for leaf in leaves:
            if not isinstance(leaf, jax.Array):
                continue
            if leaf.is_fully_addressable:
                leaf.copy_to_host_async()
            else:
                for s in leaf.addressable_shards:
                    s.data.copy_to_host_async()

    # item descriptors (metadata only; no blocking transfer yet)
    with tracing.annotate("exchange.plan", step=step):
        items: List[_Item] = []
        for li, leaf in enumerate(leaves):
            if isinstance(leaf, jax.Array) and not leaf.is_fully_addressable:
                seen: Dict[Tuple, Any] = {}
                for s in leaf.addressable_shards:
                    idx = _index_desc(s.index, leaf.shape)
                    if idx not in seen:  # replicated copies average once
                        seen[idx] = s.data
                for idx, data in seen.items():
                    items.append(_Item(li, data, data.dtype, data.shape, idx))
            else:
                dtype = getattr(leaf, "dtype", None) or np.asarray(leaf).dtype
                shape = getattr(leaf, "shape", None)
                if shape is None:
                    shape = np.asarray(leaf).shape
                items.append(_Item(li, leaf, dtype, shape))

        plan = plan_buckets(
            [(it.dtype, it.nbytes) for it in items], bucket_bytes
        )
        # the wait for what the last exchange was still placing is here:
        # microseconds, unless the step in between never ran its update
        kept = _take_kept(
            manager,
            tuple(
                (items[idxs[0]].dtype, sum(items[i].size for i in idxs))
                for idxs in plan
            ),
        )

    def _run_bucket(ordinal: int, idxs: List[int]):
        # what ties this bucket's events together across threads
        tags = {
            "step": step,
            "bucket": ordinal,
            "bytes": sum(items[i].nbytes for i in idxs),
        }

        # stage 1 (main thread): materialize this bucket's host buffers —
        # blocks only on *this* bucket's D2H while earlier buckets are
        # already riding the ring on the op thread
        t0 = time.perf_counter()
        with tracing.annotate("exchange.d2h_wait", **tags):
            host = [np.asarray(items[i].src) for i in idxs]
        t1 = time.perf_counter()
        with tracing.annotate("exchange.pack", **tags):
            # the bucket buffer always owns its memory: the ring reduces
            # (and non-participants zero) in place, which must never write
            # through a view of the caller's arrays or a read-only XLA
            # host buffer
            buf = kept.bufs[ordinal]
            if buf is None:
                dtype, count = kept.key[ordinal]
                buf = np.empty(count, dtype)
            else:
                sums["buckets_reused"] += 1
            off = 0
            for h in host:
                np.copyto(buf[off : off + h.size].reshape(h.shape), h)
                off += h.size
            # it outlives the call only if no piece of it does
            kept.bufs[ordinal] = (
                buf if all(_put_copies(items[i].src) for i in idxs) else None
            )
        t2 = time.perf_counter()
        record_wire_stage("host_copy", t2 - t0)
        sums["d2h_wait_s"] += t1 - t0
        sums["pack_s"] += t2 - t1

        if error_feedback is not None:
            # compensate with the committed residual and project onto the
            # codec's bucket-level grid BEFORE the collective (exact for
            # bf16; int8's per-chunk wire scales add a finer bounded
            # component EF doesn't track — see ErrorFeedback docstring);
            # the fresh residual stays PENDING until the step's fate
            # resolves. The key is stable across steps as long as the
            # bucket plan is (same tree -> same plan).
            t0 = time.perf_counter()
            with tracing.annotate("exchange.ef", **tags):
                error_feedback.apply(f"b{ordinal}_{buf.size}", buf)
            record_wire_stage("quantize", time.perf_counter() - t0)

        # stage 2 (op thread): quorum-managed ring allreduce of the bucket
        with tracing.annotate("exchange.submit", **tags):
            fut = manager.allreduce_many([buf])

        # dense jax leaves carry their sharding so stage 3 can start the
        # averaged piece's H2D without waiting for the whole tree
        put_shardings = []
        for i in idxs:
            it = items[i]
            s = (
                getattr(it.src, "sharding", None)
                if it.index is None and isinstance(it.src, jax.Array)
                else None
            )
            put_shardings.append(s)
        shapes = [items[i].shape for i in idxs]

        def scatter(f):
            # stage 3 (runs on the op thread as soon as this bucket's ring
            # finishes, while the next bucket's ring occupies the wire — or
            # inline on the main thread when the ring was done before the
            # continuation was attached): slice the averaged buffer and
            # dispatch H2D immediately
            res = f.value()[0]
            parts = []
            off = 0
            with tracing.annotate("exchange.h2d", **tags):
                for shp, sharding in zip(shapes, put_shardings):
                    n = int(np.prod(shp, dtype=np.int64))
                    piece = res[off : off + n].reshape(shp)
                    off += n
                    if sharding is not None:
                        piece = jax.device_put(piece, sharding)
                    parts.append(piece)
            return parts

        return fut.then(scatter)

    bucket_futs = [
        (idxs, _run_bucket(ordinal, idxs))
        for ordinal, idxs in enumerate(plan)
    ]

    # collect averaged pieces per item (in order; waits overlap the tail).
    # The blocked time is the step's main-thread cost of the cross-group
    # wire — recorded as the anatomy ledger's `wire` phase (NOT via
    # record_wire_stage: that would double it into the op-thread socket
    # totals the crossgroup bench attributes stages with). In a
    # synchronous fleet a slow peer inflates exactly this wait, which is
    # what lets the straggler detector's local-time signal exclude it.
    item_out: List[np.ndarray] = [None] * len(items)  # type: ignore[list-item]
    placed: List[Any] = []  # every device array this exchange put
    t_wait = time.perf_counter()
    with tracing.annotate("exchange.tail_wait", step=step):
        for idxs, fut in bucket_futs:
            parts = fut.wait()
            for i, piece in zip(idxs, parts):
                item_out[i] = piece
                if isinstance(piece, jax.Array):
                    placed.append(piece)
    tail_wait_s = time.perf_counter() - t_wait
    _ledger.record("wire", tail_wait_s)

    # reassemble leaves
    with tracing.annotate("exchange.reassemble", step=step):
        out: List[Any] = [None] * len(leaves)
        shard_acc: Dict[int, Dict[Tuple, np.ndarray]] = {}
        for it, averaged in zip(items, item_out):
            if it.index is None:
                out[it.leaf_pos] = averaged
            else:
                shard_acc.setdefault(it.leaf_pos, {})[it.index] = averaged
        for li, by_idx in shard_acc.items():
            template = leaves[li]
            arrays = [
                jax.device_put(by_idx[_index_desc(index, template.shape)], dev)
                for dev, index in template.sharding.addressable_devices_indices_map(
                    template.shape
                ).items()
            ]
            placed.extend(arrays)
            out[li] = jax.make_array_from_single_device_arrays(
                template.shape, template.sharding, arrays
            )
    if any(buf is not None for buf in kept.bufs) and not getattr(
        manager, "errored", lambda: None
    )():
        kept.placed = [a for a in placed if not a.is_ready()]
        _KEPT[manager] = kept
    return out, {
        "buckets": len(plan),
        "bytes_d2h": sum(it.nbytes for it in items),
        "tail_wait_s": tail_wait_s,
        **sums,
    }
