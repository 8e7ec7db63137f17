"""Reconfigurable collectives — the data plane across replica groups.

The reference's equivalent layer is torch.distributed ProcessGroups that can
be re-created with a new store/rank/world each quorum
(/root/reference/torchft/process_group.py). A TPU-native design splits the
data plane in two:

* **within** a replica group: a jax.sharding.Mesh + pjit/shard_map — XLA
  emits ICI collectives; nothing here to manage (see torchft_tpu.parallel).
* **across** replica groups: membership changes every quorum, so these
  collectives live *outside* jit on host buffers, keeping the compiled step
  function stable while the replica axis resizes. ``CollectivesTcp`` is that
  backend (the Gloo analogue, riding DCN); ops take/return numpy arrays and
  return ``Work`` handles like torch PGs do.

The ``configure(store_addr, rank, world_size)`` verb is the reconfiguration
point (process_group.py:224-239): it abandons the previous epoch's sockets
and re-rendezvouses through the epoch-prefixed store namespace
(``{store}/torchft/{quorum_id}/{rank}`` — manager.py:472).

Wrappers mirror the reference: ``CollectivesDummy`` (no-op backend used to
soak init ops and for tests, process_group.py:450-558),
``ErrorSwallowingCollectives`` (first error latches, later ops no-op until
reconfigure, process_group.py:561-654) and ``ManagedCollectives`` (routes
through a Manager, process_group.py:657-722).
"""

from __future__ import annotations

import logging
import socket
import struct
import threading
import time
from abc import ABC, abstractmethod
from concurrent.futures import ThreadPoolExecutor
from datetime import timedelta
from enum import Enum
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from torchft_tpu.faultinject.core import fault_point
from torchft_tpu.futures import Future
from torchft_tpu.store import create_store_client
from torchft_tpu.wire_codec import WireCodec, get_codec

logger = logging.getLogger(__name__)

__all__ = [
    "ReduceOp",
    "Work",
    "Collectives",
    "CollectivesTcp",
    "CollectivesDummy",
    "ErrorSwallowingCollectives",
    "ManagedCollectives",
    "PeerGoneError",
    "record_wire_stage",
    "wire_stage_snapshot",
    "average_in_place",
    "resolve_divisor",
]


# ---------------------------------------------------------------------------
# Per-stage wall-clock accounting for the cross-group wire plane
# (docs/wire_plane.md): host-copy / quantize / wire / dequantize-reduce.
# Both functions are thin shims over the step-anatomy ledger
# (telemetry/anatomy.py) — ONE source of truth for
# wire_stage_snapshot()'s callers (tests/test_anatomy.py) and a step's
# anatomy row. The ledger mirrors
# every record into tft_wire_stage_seconds_total as before.
# ---------------------------------------------------------------------------

from torchft_tpu.telemetry import tracing  # noqa: E402
from torchft_tpu.telemetry.anatomy import (  # noqa: E402
    LEDGER as _ANATOMY_LEDGER,
    WIRE_STAGES,
)


def record_wire_stage(stage: str, seconds: float) -> None:
    """Accumulate wall-clock into a wire-plane stage bucket — a shim over
    ``telemetry.anatomy.LEDGER.record(..., wire_total=True)``; main-thread
    records additionally join the current step-anatomy row."""
    _ANATOMY_LEDGER.record(stage, seconds, wire_total=True)


def wire_stage_snapshot(reset: bool = False) -> Dict[str, float]:
    """Process-cumulative seconds per wire-plane stage; ``reset`` moves
    the snapshot mark (the ledger's totals and the telemetry counters
    stay monotonic)."""
    return _ANATOMY_LEDGER.wire_stage_snapshot(reset)


class PeerGoneError(ConnectionError):
    """A socket-level failure talking to a specific peer rank.

    Carries ``peer_rank`` so the Manager can map the ring rank back to a
    replica_id and file an ``lh.evict`` report — active dead-peer
    detection that beats the passive heartbeat-lease floor the reference
    shares (src/lighthouse.rs:119-128)."""

    def __init__(self, peer_rank: int, msg: str = "") -> None:
        super().__init__(msg or f"connection to peer {peer_rank} failed")
        self.peer_rank = peer_rank

    def __reduce__(self):  # survive pickling through the proxy backend
        return (PeerGoneError, (self.peer_rank, str(self)))


class ReduceOp(Enum):
    SUM = "sum"
    AVG = "avg"
    MAX = "max"
    MIN = "min"


_REDUCE_FNS: Dict[ReduceOp, Callable[[np.ndarray, np.ndarray], np.ndarray]] = {
    ReduceOp.SUM: lambda a, b: np.add(a, b, out=a),
    ReduceOp.AVG: lambda a, b: np.add(a, b, out=a),  # divided at the end
    ReduceOp.MAX: lambda a, b: np.maximum(a, b, out=a),
    ReduceOp.MIN: lambda a, b: np.minimum(a, b, out=a),
}


def resolve_divisor(
    op: ReduceOp, divisor: int, world: int
) -> Tuple[ReduceOp, int]:
    """What an allreduce's ``(op, divisor)`` means to a backend: ``AVG`` is
    ``SUM`` with the divisor ``world``, and a divisor goes with ``SUM``
    only — so a backend reduces with ONE path, sum then divide once."""
    if op == ReduceOp.AVG and divisor == 1:
        return ReduceOp.SUM, max(world, 1)
    if divisor < 1 or (divisor != 1 and op != ReduceOp.SUM):
        raise ValueError(
            f"allreduce: divisor {divisor} with {op}; a divisor goes with SUM only"
        )
    return op, divisor


def average_in_place(arrays: List[np.ndarray], divisor: int) -> None:
    """The allreduce's division where it cannot join the ring: one NumPy
    pass over each buffer, on the thread that completes the op. Every
    backend whose reduction cannot apply ``divisor`` as it writes an
    element's final value (the Python ring, a dtype or codec the native
    plane does not take, a world of one, :class:`CollectivesDummy`) comes
    through here, so a trace shows the pass wherever it still runs:
    ``tft.exchange.average`` (stats ``bytes``, ``divisor``)."""
    if divisor == 1 or not arrays:
        return
    with tracing.annotate(
        "exchange.average",
        bytes=sum(int(a.nbytes) for a in arrays),
        divisor=divisor,
    ):
        for a in arrays:
            np.divide(a, divisor, out=a)


# The ring's account of an allreduce: what ``tft.exchange.ring.account``
# carries for one op and ``ring_account()`` totals over all of them. Seconds
# are the mean over the native plane's stripes (they run in parallel, so the
# mean compares with ``tft.exchange.ring``'s wall seconds), bytes their sum;
# native/dataplane.h's DpAccount says where each starts and ends. Plane cma
# fills desc_wait / pull / ack_wait, plane tcp and the Python ring pump — a
# full-duplex pump cannot tell waiting from moving. An allreduce with sources
# adds copy: own elements copied from a source into its array (by the native
# ring's stripes on plane tcp, a quarter of them at world 4, none on plane
# cma; all of them by :func:`fill_from_sources` where the native ring does
# not run the array),
# the copies' bytes, those inside the 4K-aliasing window, and from_source:
# the arrays whose contribution the native ring read from their source.
RING_ACCOUNT = (
    "desc_wait_s", "pull_s", "ack_wait_s", "pump_s", "reduce_s", "codec_s",
    "slowest_stripe_s", "pull_bytes", "pump_bytes", "reduce_bytes", "stripes",
    "copy_s", "copy_bytes", "copy_aliased_bytes", "from_source",
)

# One array's source: arrays whose elements, one after the other, are what
# the array is to contribute. See :meth:`Collectives.allreduce`.
Source = Sequence[np.ndarray]


def fill_from_sources(
    arrays: Sequence[np.ndarray], sources: Optional[Sequence[Optional[Source]]]
) -> None:
    """Copy each array's source into it: what an allreduce with sources
    amounts to wherever the reduction is in place (every path but the
    native ring's)."""
    for arr, source in zip(arrays, sources or ()):
        if source is None:
            continue
        flat, off = _flat_view(arr), 0
        for piece in source:
            np.copyto(flat[off : off + piece.size].reshape(piece.shape), piece)
            off += piece.size


class Work:
    """Async op handle (torch Work analogue)."""

    def __init__(self, fut: Future) -> None:
        self._fut = fut

    def wait(self, timeout: Optional[timedelta] = None):
        return self._fut.wait(timeout)

    def get_future(self) -> Future:
        return self._fut

    @staticmethod
    def completed(value=None) -> "Work":
        return Work(Future.completed(value))

    @staticmethod
    def failed(exc: BaseException) -> "Work":
        fut: Future = Future()
        fut.set_exception(exc)
        return Work(fut)


class Collectives(ABC):
    """Abstract reconfigurable collectives over a replica axis."""

    @abstractmethod
    def configure(self, store_addr: str, rank: int, world_size: int) -> None:
        """Tear down the previous epoch and rendezvous a fresh one. Safe to
        call repeatedly; each call fully replaces connectivity."""

    @abstractmethod
    def allreduce(
        self,
        arrays: List[np.ndarray],
        op: ReduceOp = ReduceOp.SUM,
        divisor: int = 1,
    ) -> Work:
        """In-place allreduce of each array; future resolves to the list.

        A backend that answers :meth:`takes_sources` also takes
        ``sources``: per array ``None`` or a :data:`Source`, C-contiguous
        arrays of the array's dtype and together of its size, whose
        elements are the array's contribution in place of what it holds.
        They are only ever read, and the backend keeps them alive for as
        long as any of its threads may read them — longer than the
        returned future, which a deadline can complete early. The array
        ends as if the sources had been copied into it first.

        ``divisor`` (with ``SUM`` only) makes the sum an average: every
        element of the result is the sum divided ONCE by ``divisor`` in
        the array's own dtype — for f32 bit for bit
        ``np.divide(sum, divisor, out=sum)``. The caller names it because
        it is not always the world size (the Manager divides by the
        participants; a healing group's zeros are not one). A backend
        applies it where the final value is written if it can (the native
        ring, a device psum) and through :func:`average_in_place` if it
        cannot. ``AVG`` is ``SUM`` with the divisor ``size()``."""

    @abstractmethod
    def allgather(self, arr: np.ndarray) -> Work:
        """Future resolves to a list of ``world_size`` arrays, rank order."""

    @abstractmethod
    def broadcast(self, arr: np.ndarray, root: int = 0) -> Work:
        """In-place broadcast from ``root``; future resolves to the array."""

    @abstractmethod
    def reduce_scatter(
        self, arrays: List[np.ndarray], op: ReduceOp = ReduceOp.SUM
    ) -> Work:
        """Reduce ``world_size`` per-rank inputs; future resolves to this
        rank's reduced shard (``arrays[rank]``-shaped)."""

    @abstractmethod
    def alltoall(self, arrays: List[np.ndarray]) -> Work:
        """Exchange ``arrays[j]`` to rank j; future resolves to the received
        list in rank order. Shapes may vary per slot but must be
        SYMMETRIC: this rank's ``arrays[j]`` shape must equal rank j's
        ``arrays[this_rank]`` shape (the receive buffer is sized from the
        local input for that slot)."""

    @abstractmethod
    def send(self, arr: np.ndarray, dst: int, tag: int = 0) -> Work: ...

    @abstractmethod
    def recv(self, arr: np.ndarray, src: int, tag: int = 0) -> Work:
        """In-place receive into ``arr``.

        Point-to-point ops run concurrently (a worker pool, not the
        ordered collective-op thread). Frames are matched by ``tag``, so
        several outstanding recvs from one peer are safe with *distinct*
        tags; two concurrent recvs on the SAME (src, tag) race for frames
        in unspecified order — serialize them with ``wait()`` or use
        per-message tags (see checkpointing/collectives_transport.py)."""

    @abstractmethod
    def barrier(self) -> Work: ...

    @abstractmethod
    def size(self) -> int: ...

    @abstractmethod
    def rank(self) -> int: ...

    def plane_info(self) -> str:
        """Transport label for dashboards/metrics; backends override with
        their live routing (e.g. CollectivesTcp: cma / tcp-striped /
        python-ring). Wrappers must delegate to the inner backend."""
        return type(self).__name__

    def wire_codec(self) -> str:
        """Name of the codec large f32 allreduces ride the wire with
        (``"f32"`` = exact). Lossy codecs ("bfloat16"/"int8") are what
        :class:`~torchft_tpu.wire_codec.ErrorFeedback` compensates for;
        wrappers must delegate to the inner backend."""
        return "f32"

    def takes_sources(self) -> bool:
        """Whether :meth:`allreduce` takes ``sources`` and has a reduction
        that reads them where they lie instead of a packed copy (the
        native ring). ``ddp`` asks before it skips its pack; wrappers must
        delegate, and a backend that says no is never handed any."""
        return False

    def avg_in_ring_ops(self) -> int:
        """How many allreduces this backend has completed whose
        ``divisor`` (> 1) was applied inside the reduction for every
        array, with no :func:`average_in_place` pass (monotonic; read as a
        difference, e.g. ``ddp``'s ``buckets_avg_in_ring``). Wrappers
        must delegate; a backend that cannot tell (the proxy's parent
        side) says 0."""
        return 0

    def ring_account(self) -> Dict[str, float]:
        """Where this backend's allreduces have spent their time inside the
        ring, as monotonic totals of :data:`RING_ACCOUNT`'s fields (read as
        a difference, e.g. ``ddp``'s ``ring_wait_s``). Wrappers must
        delegate; a backend that keeps no such account says ``{}``."""
        return {}

    def shutdown(self) -> None:  # noqa: B027 — optional hook
        pass


# ---------------------------------------------------------------------------
# TCP backend
# ---------------------------------------------------------------------------

_HELLO_MAGIC = 0x7F7A0001
_FRAME_HDR = struct.Struct("<II")  # (tag, length) — tag catches desync bugs

# CMA fast path for LARGE p2p frames when the data-plane probe proved the
# peers same-host: instead of streaming the payload, the sender ships a
# 16-byte {addr, nbytes} descriptor (tag | _CMA_FLAG) and the receiver
# pulls the bytes straight out of the sender's address space
# (process_vm_readv), then acks (tag | _ACK_FLAG) so the sender may reuse
# the buffer. This is what lifts checkpoint heals and other big p2p
# transfers to memcpy-class speed on one host. The top two tag bits are
# reserved for the protocol — structurally safe: public send/recv mask
# user tags to 24 bits and every internal tag space tops out at
# 0x0DFFFFFF.
_CMA_FLAG = 0x80000000
_ACK_FLAG = 0x40000000
_CMA_DESC = struct.Struct("<QQ")  # (addr, nbytes)


def _env_int(name: str, default: int) -> int:
    """Guarded env knob parse: a typo'd deploy config must fall back, not
    crash the worker at construction."""
    import os

    raw = os.environ.get(name)
    if raw is None:
        return default
    try:
        return int(raw)
    except ValueError:
        logger.warning("ignoring malformed %s=%r; using %d", name, raw, default)
        return default


def _cma_p2p_min() -> int:
    return _env_int("TORCHFT_CMA_P2P_MIN", 1 << 20)


# Buffers whose pull-ack never arrived. PROCESS-GLOBAL and never dropped:
# process_vm_readv needs no socket, so a peer that already holds the
# descriptor in its kernel recv buffer can pull long after this epoch's
# sockets closed — the memory must stay pinned for the process lifetime.
# Growth is bounded by ack-failure events (rare); size is logged so a
# pathological loop is operator-visible.
_CMA_QUARANTINE: List[np.ndarray] = []

# PROCESS-LOCAL latch: the negotiation probe only proves a read of the
# LEFT ring neighbor, but a passing vote arms direct pulls between
# ARBITRARY rank pairs (p2p sends >= TORCHFT_CMA_P2P_MIN, descriptor
# pulls in _recv_matched). If process_vm_readv permission is pairwise-
# asymmetric (differing uids, YAMA ptrace_scope) the probe ring can pass
# while a non-adjacent pull fails at op time — and since the negotiation
# would re-succeed identically every epoch, the group would retry into
# the same failure forever. A failed pull latches this flag; the next
# epoch's negotiation publishes ok=0 so the whole group settles on TCP.
_CMA_BROKEN = False


def _cma_pull(pid: int, addr: int, view: memoryview) -> None:
    """process_vm_readv the peer's [addr, addr+len) into ``view``."""
    global _CMA_BROKEN
    import errno

    from torchft_tpu._native import cma_read_into

    inj = fault_point("cma.pull", match=f"pid{pid}", wire=True,
                      nbytes=len(view))
    if inj is not None and inj.action in ("torn", "drop"):
        # torn read: fill only a prefix of the caller's buffer (what a
        # pull from a peer dying mid-op would leave behind), then fail
        # the stream loudly so the step latches instead of committing
        # the partial bytes
        k = int(len(view) * inj.frac) if inj.action == "torn" else 0
        if k:
            cma_read_into(pid, addr, view[:k])
        raise ConnectionError(
            f"fault injection: torn CMA pull ({k}/{len(view)} bytes "
            f"from pid {pid})"
        )
    try:
        cma_read_into(pid, addr, view)
    except OSError as e:
        # Permission-class failures only: the probe ring proved a read of
        # the LEFT neighbor, so EPERM/EACCES on another pair means the
        # permission matrix is pairwise-asymmetric and every epoch's
        # negotiation would re-arm the same broken path. ESRCH/EFAULT
        # from a peer that just DIED is the normal FT case — re-quorum
        # recovers it and CMA must stay available for the next cohort.
        if e.errno in (errno.EPERM, errno.EACCES):
            _CMA_BROKEN = True
            logger.warning(
                "CMA pull from pid %d denied (%s); latching CMA off — "
                "next reconfigure converges the group to TCP", pid, e,
            )
        raise


def _send_frame(sock: socket.socket, tag: int, payload: memoryview) -> None:
    sock.sendall(_FRAME_HDR.pack(tag, len(payload)))
    sock.sendall(payload)


def _recv_exact_into(sock: socket.socket, view: memoryview) -> None:
    n = len(view)
    got = 0
    while got < n:
        k = sock.recv_into(view[got:], n - got)
        if k == 0:
            raise ConnectionError("peer closed connection")
        got += k


def _recv_exact(sock: socket.socket, n: int) -> bytearray:
    buf = bytearray(n)
    _recv_exact_into(sock, memoryview(buf))
    return buf


def _bytes_view(arr: np.ndarray) -> memoryview:
    """Byte-level view of an array (frame lengths are in bytes)."""
    arr = np.ascontiguousarray(arr)
    try:
        return memoryview(arr).cast("B")
    except (ValueError, TypeError):
        # ml_dtypes (bfloat16/fp8) reject the buffer protocol directly; a
        # uint8 reinterpret view of the same memory does not
        return memoryview(arr.view(np.uint8)).cast("B")


def _flat_view(arr: np.ndarray) -> np.ndarray:
    """Flat in-place view; in-place collectives need contiguous arrays."""
    v = arr.reshape(-1)
    if v.size and not np.shares_memory(v, arr):
        raise ValueError("in-place collectives require contiguous arrays")
    return v


def _corrupt_buffers(result: Any, frac: float) -> None:
    """``corrupt(frac)`` injection semantics at ``collective.complete``:
    silently perturb the leading ``frac`` of the first finished buffer's
    elements on THIS replica only (+1.0 — finite, so nothing downstream
    errors; the corruption is only observable as cross-group digest /
    checksum divergence, which is exactly the hole the commit-time
    divergence sentinel exists to close)."""
    arrays = result if isinstance(result, (list, tuple)) else [result]
    for arr in arrays:
        if isinstance(arr, np.ndarray) and arr.size:
            n = max(1, int(arr.size * frac))
            flat = arr.reshape(-1)
            flat[:n] += flat.dtype.type(1)
            return


class _Peer:
    def __init__(self, sock: socket.socket) -> None:
        self.sock = sock
        # One lock per direction: an op may concurrently send to and receive
        # from the same peer (ring steps do exactly that).
        self.send_lock = threading.Lock()
        self.recv_lock = threading.Lock()
        # Tag-matched receive state: concurrent ops (pipelined checkpoint
        # buffers, overlapped p2p + ring traffic) may interleave frames on
        # one socket; the reader thread stashes frames for tags other ops
        # are waiting on instead of declaring a desync.
        self.cond = threading.Condition(self.recv_lock)
        self.stash: Dict[int, List[bytearray]] = {}
        self.stash_bytes = 0
        self.reader_busy = False
        self.recv_error: Optional[BaseException] = None


class CollectivesTcp(Collectives):
    """Cross-replica-group collectives over TCP (Gloo analogue).

    Full-duplex mesh built lazily: both sides publish listeners through the
    store; for the pair (i, j) the higher rank dials the lower. Ring
    algorithms (reduce-scatter + allgather) bound per-step traffic to
    ``2 * nbytes / world``.
    """

    def __init__(
        self,
        timeout: timedelta = timedelta(seconds=60),
        hostname: Optional[str] = None,
        wire_dtype: Optional[str] = None,
        p2p_workers: int = 8,
        stash_limit: int = 1 << 30,
        native_plane: Optional[bool] = None,
        dp_stripes: Optional[int] = None,
    ) -> None:
        """
        Args:
            wire_dtype: optional on-the-wire compression for float32
                allreduce — a codec name from
                :mod:`torchft_tpu.wire_codec`: ``"bfloat16"`` halves DCN
                bytes, ``"int8"`` quarters them (per-chunk scale factors);
                partial sums are re-quantized each hop, accumulation stays
                f32, and the decoded average is bit-identical on every
                rank by construction (the allgather phase forwards the
                chunk owner's wire bytes verbatim). Defaults to the
                ``TORCHFT_WIRE_CODEC`` env knob, else exact f32. Opt-in,
                like the reference's NCCL bf16 gradient comms; pair lossy
                codecs with error feedback (docs/wire_plane.md).
            p2p_workers: thread pool size for send/recv ops — point-to-point
                transfers (checkpoint fan-out to several healing replicas,
                windowed buffer pipelines) run concurrently, off the ordered
                collective-op thread. Tag matching keeps interleaved frames
                safe (:meth:`_recv_matched`).
            stash_limit: byte cap on frames parked for tags no local op is
                consuming — the desync tripwire.
            native_plane: route large f32 allreduces through the striped
                C++ data plane (native/dataplane.cc) — the NCCL-role fast
                path (process_group.py:431-447): GIL-free, N sockets per
                peer, wire codec in C++. Default on; override with env
                ``TORCHFT_NATIVE_PLANE=0``. MUST agree across ranks (a
                split group would wait on different sockets), so setup
                failures raise instead of falling back.
            dp_stripes: sockets per peer for the native plane (default 4,
                env ``TORCHFT_DP_STRIPES``).
        """
        import os as _os

        if native_plane is None:
            native_plane = _os.environ.get("TORCHFT_NATIVE_PLANE", "1") != "0"
        if dp_stripes is None:
            dp_stripes = _env_int("TORCHFT_DP_STRIPES", 4)
        self._native_plane = native_plane
        self._dp_stripes = max(1, dp_stripes)
        self._dp = None  # NativeDataPlane for the current epoch
        self._dp_cma_pids: Optional[List[int]] = None  # p2p CMA fast path
        self._cma_p2p_min = _cma_p2p_min()  # resolved once, not per frame
        self._death_watch_cb: Optional[Callable[[int, int], None]] = None
        self._timeout = timeout
        self._hostname = hostname or socket.gethostname()
        if wire_dtype is None:
            wire_dtype = _os.environ.get("TORCHFT_WIRE_CODEC") or None
        self._codec: WireCodec = get_codec(wire_dtype or None)
        # per-epoch wire scratch (grown monotonically, cleared on
        # teardown): the ring must never allocate per chunk per round
        self._scratch_bufs: Dict[str, np.ndarray] = {}
        self._p2p_workers = p2p_workers
        self._stash_limit = stash_limit
        self._rank = -1
        self._world = 0
        self._generation = 0
        self._peers: Dict[int, _Peer] = {}  # guarded-by: _peers_lock
        self._peers_lock = threading.Lock()
        self._listener: Optional[socket.socket] = None
        self._acceptor: Optional[threading.Thread] = None
        self._store = None
        self._executor: Optional[ThreadPoolExecutor] = None
        self._ring_send_worker: Optional[ThreadPoolExecutor] = None
        self._p2p: Optional[ThreadPoolExecutor] = None
        self._op_seq = 0
        self._avg_in_ring_ops = 0  # written on the op thread only
        # totals of RING_ACCOUNT, written on the op thread only
        self._ring_account: Dict[str, float] = dict.fromkeys(RING_ACCOUNT, 0)

    # -- lifecycle --

    def configure(self, store_addr: str, rank: int, world_size: int) -> None:
        self._teardown()  # bumps _generation, so stale acceptors are fenced
        self._rank = rank
        self._world = world_size
        # Tags order ops SPMD-style, so every member must restart the
        # sequence together; configure() is that barrier (a rejoining
        # replica starts at 0 while survivors would otherwise keep counting).
        self._op_seq = 0
        with self._peers_lock:
            gen = self._generation
        self._executor = ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="tft_coll"
        )
        self._ring_send_worker = ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="tft_ring_send"
        )
        self._p2p = ThreadPoolExecutor(
            max_workers=self._p2p_workers, thread_name_prefix="tft_p2p"
        )
        if world_size == 1:
            return

        self._store = create_store_client(store_addr, connect_timeout=self._timeout)
        listener = socket.socket(socket.AF_INET6, socket.SOCK_STREAM)
        listener.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        listener.bind(("::", 0))
        listener.listen(64)
        self._listener = listener
        port = listener.getsockname()[1]
        self._store.set(f"coll/addr/{rank}", f"{self._hostname}:{port}")

        self._acceptor = threading.Thread(
            target=self._accept_loop, args=(listener, gen), daemon=True,
            name="tft_accept",
        )
        self._acceptor.start()
        # Eagerly establish the full mesh so configure() surfaces
        # connectivity failures (and later ops can't stall on dial).
        deadline = self._timeout
        for peer in range(world_size):
            if peer == rank:
                continue
            if peer < rank:
                self._dial(peer, deadline)
        # Wait for all higher ranks to dial us.
        self._wait_for_peers(set(range(rank + 1, world_size)))
        if self._native_plane:
            self._configure_dp(rank, world_size)
        if self._death_watch_cb is not None:
            threading.Thread(
                target=self._death_watch_loop,
                args=(gen,),
                daemon=True,
                name="tft_death_watch",
            ).start()

    def set_death_watch(self, cb: Callable[..., None]) -> None:
        """Register a peer-death callback, called ``cb(ring_rank, gen)``
        with the ring rank whose socket hit EOF/error and the plane
        generation whose ring that rank belongs to (pair it with
        :meth:`plane_generation` to drop callbacks that raced a
        reconfigure — the same ring rank means a different replica in a
        different epoch). Armed at the NEXT configure(). This is the
        active failure detector: a SIGKILLed peer's FIN reaches every
        survivor within milliseconds, long before their next collective op
        touches the socket — the callback lets the Manager evict and
        re-quorum DURING the doomed step instead of at its own step
        boundary. False positives (a peer tearing down an old epoch early)
        are safe: eviction is liveness-probe-guarded at the lighthouse."""
        self._death_watch_cb = cb

    def plane_generation(self) -> int:
        """Monotonic epoch counter, bumped by every configure()/teardown.
        Death-watch callbacks carry the generation they were armed for."""
        with self._peers_lock:
            return self._generation

    def _death_watch_loop(self, gen: int) -> None:
        import select

        # Poll cadence bounds detection latency, which bounds the
        # survivor's blackout: at the old 200 ms the 1-of-4 kill measured
        # ~1.7 steady steps of blackout with ~100 ms of it just waiting
        # for the next poll. 25 ms puts detection well under one toy
        # step; the idle cost (40 wakeups/s per plane) is negligible.
        poll_ms = _env_int("TORCHFT_DEATH_WATCH_POLL_MS", 25)
        poll_rdhup = getattr(select, "POLLRDHUP", 0x2000)
        poller = select.poll()
        with self._peers_lock:
            if gen != self._generation:
                return
            fds = {}
            for r, p in self._peers.items():
                try:
                    fd = p.sock.fileno()
                except OSError:
                    continue
                fds[fd] = r
        for fd in fds:
            poller.register(fd, select.POLLERR | select.POLLHUP | poll_rdhup)
        reported: set = set()
        while True:
            with self._peers_lock:
                if gen != self._generation:
                    return
            try:
                events = poller.poll(poll_ms)
            except OSError:
                return
            for fd, ev in events:
                if ev & select.POLLNVAL:
                    try:
                        poller.unregister(fd)
                    except (KeyError, OSError):
                        pass
                    continue
                rank = fds.get(fd)
                if rank is None or rank in reported:
                    continue
                reported.add(rank)
                try:
                    poller.unregister(fd)
                except (KeyError, OSError):
                    pass
                with self._peers_lock:
                    if gen != self._generation:
                        return
                cb = self._death_watch_cb
                if cb is not None:
                    try:
                        cb(rank, gen)
                    except Exception:  # noqa: BLE001
                        logger.exception("death-watch callback failed")

    def _configure_dp(self, rank: int, world_size: int) -> None:
        """Stand up the striped C++ gradient plane for this epoch. Same
        rendezvous shape as the Python mesh (store-published listeners,
        higher ranks dial lower); failures RAISE — every rank must land on
        the same plane or the group deadlocks across planes."""
        from torchft_tpu._native import NativeDataPlane

        import time as _time

        # ONE deadline for the whole data-plane rendezvous (store gets,
        # every peer's stripe dials, readiness, CMA negotiation): an
        # unreachable peer must cost one timeout budget, not
        # world × nstripes of them
        deadline = _time.monotonic() + self._timeout.total_seconds()

        def left() -> timedelta:
            remaining = deadline - _time.monotonic()
            if remaining <= 0:
                raise TimeoutError("data-plane rendezvous deadline exceeded")
            return timedelta(seconds=remaining)

        dp = NativeDataPlane(rank, world_size, self._dp_stripes)
        self._dp_cma = False
        try:
            self._store.set(f"coll/dpaddr/{rank}", f"{self._hostname}:{dp.port}")
            for peer in range(rank):
                addr = self._store.get(
                    f"coll/dpaddr/{peer}", timeout=left()
                ).decode()
                host, port = addr.rsplit(":", 1)
                dp.connect(
                    peer, host, int(port), int(left().total_seconds() * 1000)
                )
            dp.wait_ready(int(left().total_seconds() * 1000))
            self._maybe_enable_cma(dp, rank, world_size, left)
        except BaseException:
            dp.close()
            raise
        self._dp = dp

    def _maybe_enable_cma(self, dp, rank: int, world_size: int, remaining) -> None:
        """Negotiate the one-copy CMA transport (process_vm_readv pulls —
        the NCCL intra-node SHM/P2P analogue). Every rank probes its LEFT
        ring neighbor with a token read (proving same pid namespace +
        ptrace policy, not just same hostname) and publishes the result;
        the mode flips on only when ALL ranks proved their read, keeping
        the ring homogeneous — a mixed ring would deadlock or, with bf16
        wire, break bitwise determinism. Opt out: TORCHFT_DP_CMA=0."""
        import ctypes as ct
        import os
        import secrets

        # An opted-out rank STILL publishes its keys (with ok="0"): peers
        # that did not opt out would otherwise block their whole rendezvous
        # deadline on keys that never appear, failing configure on every
        # epoch instead of settling on TCP in one round.
        # the broken-latch counts as an opt-out: this rank votes ok=0 so
        # the group-wide all-ok conjunction converges everyone to TCP
        opt_out = os.environ.get("TORCHFT_DP_CMA", "1") == "0" or _CMA_BROKEN
        if _CMA_BROKEN:
            logger.info(
                "CMA disabled this epoch: a prior pull failed in this "
                "process (pairwise-asymmetric process_vm_readv permission)"
            )
        from torchft_tpu._native import cma_read

        token = secrets.token_bytes(16)
        # keep the probe target alive for the epoch (peers read it remotely)
        self._dp_probe_buf = ct.create_string_buffer(token, 16)
        self._store.set(
            f"coll/dpcma/{rank}",
            f"{self._hostname}|{os.getpid()}|{token.hex()}"
            f"|{ct.addressof(self._dp_probe_buf)}",
        )
        left = (rank - 1) % world_size
        ok = False
        if not opt_out:
            try:
                ent = self._store.get(
                    f"coll/dpcma/{left}", timeout=remaining()
                ).decode()
                lhost, lpid, ltok, laddr = ent.split("|")
                if lhost == self._hostname:
                    ok = (
                        cma_read(int(lpid), int(laddr), 16)
                        == bytes.fromhex(ltok)
                    )
            except Exception as e:  # noqa: BLE001 — any failure means TCP
                logger.info(
                    "CMA probe of rank %d failed (%s); staying on TCP", left, e
                )
        self._store.set(f"coll/dpcmaok/{rank}", "1" if ok else "0")
        pids = []
        all_ok = True
        for p in range(world_size):
            flag = self._store.get(
                f"coll/dpcmaok/{p}", timeout=remaining()
            ).decode()
            ent = self._store.get(
                f"coll/dpcma/{p}", timeout=remaining()
            ).decode()
            pids.append(int(ent.split("|")[1]))
            all_ok = all_ok and flag == "1"
        if all_ok:
            dp.enable_cma(pids)
            self._dp_cma = True
            self._dp_cma_pids = pids  # arms the p2p CMA fast path too
            logger.info(
                "data plane: CMA transport enabled (%d ranks, one host)",
                world_size,
            )

    def plane_info(self) -> str:
        """Which transport carries large f32 allreduces this epoch:
        ``"cma"`` (one-copy process_vm_readv pulls), ``"tcp-striped"``
        (C++ multi-socket ring) or ``"python-ring"`` (fallback)."""
        if self._dp is None:
            return "python-ring"
        return "cma" if getattr(self, "_dp_cma", False) else "tcp-striped"

    def wire_codec(self) -> str:
        # the CMA transport pulls exact f32 out of the peer's memory, so
        # a configured lossy codec is bypassed there (docs/wire_plane.md)
        if self._dp is not None and getattr(self, "_dp_cma", False):
            return "f32"
        return self._codec.name

    def takes_sources(self) -> bool:
        from torchft_tpu._native import NativeDataPlane

        return self._dp is not None and self._codec.name in NativeDataPlane.CODEC

    def avg_in_ring_ops(self) -> int:
        return self._avg_in_ring_ops

    def ring_account(self) -> Dict[str, float]:
        return dict(self._ring_account)

    def _epoch_scratch(self, dtype: np.dtype, nelems: int,
                       slot: str = "") -> np.ndarray:
        """Per-epoch reusable scratch (grown monotonically, torn down
        with the epoch): the old ring's ``astype`` per chunk per round
        allocated on the hot path."""
        key = f"{slot}:{np.dtype(dtype).str}"
        buf = self._scratch_bufs.get(key)
        if buf is None or buf.size < nelems:
            buf = np.empty(max(nelems, 1), dtype=dtype)
            self._scratch_bufs[key] = buf
        return buf[:nelems]

    def _wait_for_peers(self, expected: set) -> None:
        import time

        deadline = time.monotonic() + self._timeout.total_seconds()
        while True:
            with self._peers_lock:
                missing = expected - set(self._peers)
            if not missing:
                return
            if time.monotonic() > deadline:
                raise TimeoutError(f"peers never connected: {sorted(missing)}")
            time.sleep(0.01)

    def _accept_loop(self, listener: socket.socket, gen: int) -> None:
        while True:
            try:
                sock, _ = listener.accept()
            except OSError:
                return  # listener closed by teardown
            try:
                # deadline BEFORE the hello too: a connected-but-silent
                # dialer (killed mid-handshake, port scanner) must not wedge
                # the acceptor thread past the op timeout
                sock.settimeout(self._timeout.total_seconds())
                hello = _recv_exact(sock, 8)
                magic, peer_rank = struct.unpack("<II", bytes(hello))
                if magic != _HELLO_MAGIC:
                    sock.close()
                    continue
            except Exception:
                sock.close()
                continue
            sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            with self._peers_lock:
                if gen != self._generation:
                    sock.close()
                    return
                self._peers[peer_rank] = _Peer(sock)

    def _dial(self, peer: int, timeout: timedelta) -> None:
        addr = self._store.get(f"coll/addr/{peer}", timeout=timeout).decode()
        host, port = addr.rsplit(":", 1)
        sock = socket.create_connection(
            (host, int(port)), timeout=timeout.total_seconds()
        )
        # keep the op-timeout deadline on the connected socket (a dead peer
        # mid-ring must not wedge the op thread past self._timeout)
        sock.settimeout(self._timeout.total_seconds())
        sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        sock.sendall(struct.pack("<II", _HELLO_MAGIC, self._rank))
        with self._peers_lock:
            self._peers[peer] = _Peer(sock)

    def _teardown(self) -> None:
        # Order matters (round-1 review weak #2): fence stale acceptor
        # threads, then unblock any op thread stuck in a socket syscall
        # (shutdown() wakes a blocked recv/send; close() alone does not on
        # Linux), THEN join the executor so reconfigure never leaks a
        # wedged worker thread.
        with self._peers_lock:
            # the epoch ends HERE, not at the next configure(): an old
            # acceptor completing a handshake after this point must never
            # insert its socket into the next epoch's peer map
            self._generation += 1
        if self._listener is not None:
            try:
                self._listener.close()
            except OSError:
                pass
            self._listener = None
        with self._peers_lock:
            for p in self._peers.values():
                try:
                    p.sock.shutdown(socket.SHUT_RDWR)
                except OSError:
                    pass
                try:
                    p.sock.close()
                except OSError:
                    pass
            self._peers.clear()
        if self._dp is not None:
            # before joining the executor: closing the plane's sockets
            # unblocks an op thread parked inside the native allreduce
            self._dp.close()
            self._dp = None
        self._dp_cma_pids = None
        if self._executor is not None:
            self._executor.shutdown(wait=True, cancel_futures=True)
            self._executor = None
        if self._ring_send_worker is not None:
            self._ring_send_worker.shutdown(wait=True, cancel_futures=True)
            self._ring_send_worker = None
        if self._p2p is not None:
            self._p2p.shutdown(wait=True, cancel_futures=True)
            self._p2p = None
        # after the executors have drained: no op thread can still be
        # writing through these views
        self._scratch_bufs.clear()
        if self._store is not None:
            self._store.close()
            self._store = None

    def shutdown(self) -> None:
        self._teardown()

    def size(self) -> int:
        return self._world

    def rank(self) -> int:
        return self._rank

    # -- plumbing --

    def _peer(self, rank: int) -> _Peer:
        with self._peers_lock:
            p = self._peers.get(rank)
        if p is None:
            raise RuntimeError(f"no connection to peer {rank}")
        return p

    def _submit(self, fn: Callable, p2p: bool = False, op: str = "") -> Work:
        """Run ``fn`` async. Collective ops share ONE ordered thread (SPMD
        tag sequencing + natural per-bucket pipelining); point-to-point ops
        go to the p2p pool so transfers to/from different peers — and
        windowed buffer pipelines to one peer — overlap. Tag matching in
        :meth:`_recv_matched` keeps the interleaved frames safe."""
        executor = self._p2p if p2p else self._executor
        assert executor is not None, "configure() must be called first"
        out: Future = Future()

        def run() -> None:
            try:
                result = fn()
                if op:
                    # completion-side injection site: a delay here holds
                    # the op thread (stalling the ring like a wedged
                    # peer); an error fails the finished op before its
                    # future resolves; `corrupt` silently perturbs the
                    # finished buffers on THIS replica only — the
                    # divergence-sentinel adversary (no error surfaces,
                    # so without the commit-time digest compare the
                    # corrupt averages would commit)
                    inj = fault_point(
                        "collective.complete", match=op, rank=self._rank,
                        wire=True,
                    )
                    if inj is not None:
                        if inj.action == "corrupt":
                            _corrupt_buffers(result, inj.frac)
                        elif inj.action in ("drop", "torn"):
                            # no wire semantics for these here: degrade
                            # to error so a schedule can never silently
                            # no-op (delay/kill were already applied
                            # inline by fault_point — re-raising them
                            # would turn a stall into a failed op)
                            raise inj.make_exception()
                out.set_result(result)
            except BaseException as e:  # noqa: BLE001 — propagate via future
                out.set_exception(e)

        task = executor.submit(run)

        def on_done(t) -> None:
            # teardown cancels queued tasks whose run() never executes; the
            # caller's Work future must still resolve or a timeout-less
            # wait() would hang forever
            if t.cancelled() and not out.done():
                from torchft_tpu import telemetry

                telemetry.FUTURE_CANCELS.inc()
                out.set_exception(
                    RuntimeError("collectives reconfigured before op ran")
                )

        task.add_done_callback(on_done)
        return Work(out)

    def _send_to(self, rank: int, tag: int, data: memoryview) -> None:
        inj = fault_point(
            "rpc.send", match=f"peer{rank}", wire=True,
            tag=tag, nbytes=len(data), rank=self._rank,
        )
        if inj is not None:
            if inj.action == "drop":
                return  # silently unsent: the peer's recv hits its deadline
            if inj.action == "torn":
                self._torn_send(rank, tag, data, inj.frac)  # raises
        if (
            self._dp_cma_pids is not None
            and len(data) >= self._cma_p2p_min
            and not (tag & (_CMA_FLAG | _ACK_FLAG))
        ):
            self._send_cma(rank, tag, data)
            return
        p = self._peer(rank)
        try:
            with p.send_lock:
                _send_frame(p.sock, tag, data)
        except (ConnectionError, OSError) as e:
            if isinstance(e, (socket.timeout, TimeoutError)):
                raise  # slow-but-alive peer: latch the error, don't accuse
            raise PeerGoneError(rank, f"send to peer {rank} failed: {e}") from e

    def _torn_send(self, rank: int, tag: int, data: memoryview,
                   frac: float) -> None:
        """Fault-injection wire primitive: frame a FULL-length header,
        ship only ``frac`` of the payload, then hard-cut the socket —
        exactly what a peer dying mid-send leaves on the wire. The
        receiver must surface a mid-frame EOF (never half-filled data
        reported as success); this side latches like any dead-peer send."""
        p = self._peer(rank)
        k = int(len(data) * frac)
        try:
            with p.send_lock:
                p.sock.sendall(_FRAME_HDR.pack(tag, len(data)))
                if k:
                    p.sock.sendall(data[:k])
        finally:
            try:
                p.sock.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
        raise PeerGoneError(
            rank,
            f"fault injection: torn send to peer {rank} "
            f"({k}/{len(data)} bytes)",
        )

    def _send_cma(self, rank: int, tag: int, data: memoryview) -> None:
        """Ship a pull descriptor instead of the payload; the buffer must
        stay untouched until the peer's ack (awaited here) confirms the
        pull completed."""
        arr = np.frombuffer(data, dtype=np.uint8)
        desc = _CMA_DESC.pack(arr.ctypes.data, len(data))
        p = self._peer(rank)
        try:
            with p.send_lock:
                _send_frame(p.sock, tag | _CMA_FLAG, memoryview(desc))
        except (ConnectionError, OSError) as e:
            if isinstance(e, (socket.timeout, TimeoutError)):
                raise
            raise PeerGoneError(rank, f"send to peer {rank} failed: {e}") from e
        # the ack rides the normal tag-matched machinery (interleaves
        # safely with any concurrent traffic on this socket)
        try:
            self._recv_from(rank, tag | _ACK_FLAG)
        except BaseException as e:
            # ANY failure to observe the ack leaves the descriptor
            # DANGLING: the peer may still pull that address later (it
            # needs no socket for the pull, only the 16 descriptor bytes
            # it may already hold). Letting the caller reuse/free the
            # memory would hand the peer silently corrupt bytes — the TCP
            # path streamed a copy and never had this hazard. Pin the
            # buffer for the PROCESS lifetime and poison the stream so
            # both sides reconfigure.
            _CMA_QUARANTINE.append(arr)
            q_bytes = sum(a.nbytes for a in _CMA_QUARANTINE)
            logger.warning(
                "CMA pull-ack from peer %d failed (%s); buffer quarantined "
                "(%d buffers, %.1f MB pinned process-wide)",
                rank, e, len(_CMA_QUARANTINE), q_bytes / 1e6,
            )
            with p.cond:
                p.recv_error = e
                p.cond.notify_all()
            try:
                p.sock.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
            if isinstance(e, TimeoutError):
                raise ConnectionError(
                    f"CMA pull-ack from peer {rank} timed out; epoch "
                    f"poisoned (descriptor quarantined)"
                ) from e
            raise
        del arr  # keep the source buffer alive until the ack

    def _recv_from(
        self, rank: int, tag: int, into: Optional[memoryview] = None
    ) -> Optional[bytearray]:
        """Tag-matched receive. With ``into``, a frame of exactly
        ``len(into)`` bytes is received straight into the caller's buffer
        (zero-copy) and None is returned; otherwise the frame bytes are
        returned."""
        p = self._peer(rank)
        try:
            fault_point(
                "rpc.recv", match=f"peer{rank}", tag=tag, rank=self._rank,
            )
            return self._recv_matched(p, rank, tag, into)
        except (ConnectionError, OSError) as e:
            if isinstance(e, (socket.timeout, TimeoutError)):
                raise  # slow-but-alive peer: latch the error, don't accuse
            raise PeerGoneError(rank, f"recv from peer {rank} failed: {e}") from e

    def _recv_matched(
        self, p: _Peer, rank: int, tag: int, into: Optional[memoryview]
    ) -> Optional[bytearray]:
        """Core of the concurrent-safe receive path.

        Several ops may receive from the same peer at once (pipelined
        checkpoint buffers, p2p overlapping ring traffic); frames for one op
        must not be consumed by another. One thread at a time becomes the
        socket reader; frames for other tags are parked in the peer's stash
        and their waiters notified. A hard stash cap keeps a genuine desync
        (a tag nobody will ever wait for) loud instead of an unbounded leak.
        """
        import time

        deadline = time.monotonic() + self._timeout.total_seconds()
        while True:
            with p.cond:
                while True:
                    if p.recv_error is not None:
                        # preserve the reader's error *class*: a timeout
                        # must stay a timeout for waiters too, or a slow-
                        # but-alive peer gets accused via PeerGoneError
                        if isinstance(
                            p.recv_error, (socket.timeout, TimeoutError)
                        ):
                            raise TimeoutError(
                                f"receive stream timed out: {p.recv_error!r}"
                            ) from p.recv_error
                        raise ConnectionError(
                            f"receive stream broken: {p.recv_error!r}"
                        ) from p.recv_error
                    q = p.stash.get(tag)
                    if q:
                        if into is not None and len(into) != len(q[0]):
                            # leave the frame stashed: another (correctly
                            # sized) recv may still claim it
                            raise RuntimeError(
                                f"tag {tag:#x}: frame is {len(q[0])} bytes, "
                                f"recv buffer is {len(into)}"
                            )
                        data = q.pop(0)
                        if not q:
                            del p.stash[tag]
                        p.stash_bytes -= len(data)
                        if into is not None:
                            into[:] = data
                            return None
                        return data
                    if not p.reader_busy:
                        p.reader_busy = True
                        break  # we read the socket
                    remaining = deadline - time.monotonic()
                    if remaining <= 0:
                        raise TimeoutError(
                            f"recv tag {tag:#x} timed out waiting for reader; "
                            f"stashed tags: {sorted(map(hex, p.stash))}"
                        )
                    p.cond.wait(remaining)
            got_tag = -1
            filled = False
            data = None
            try:
                hdr = _recv_exact(p.sock, _FRAME_HDR.size)
                got_tag, length = _FRAME_HDR.unpack(bytes(hdr))
                if got_tag & _CMA_FLAG:
                    # pull descriptor: fetch the payload from the sender's
                    # address space, then ack so it may reuse the buffer
                    got_tag &= ~_CMA_FLAG
                    desc = _recv_exact(p.sock, length)
                    addr, nbytes = _CMA_DESC.unpack(bytes(desc))
                    pids = self._dp_cma_pids  # teardown may None the field
                    if pids is None:
                        raise ConnectionError(
                            "CMA descriptor arrived during teardown"
                        )
                    pid = pids[rank]
                    if (
                        got_tag == tag
                        and into is not None
                        and len(into) == nbytes
                    ):
                        _cma_pull(pid, addr, into)
                        filled = True
                    else:
                        data = bytearray(nbytes)
                        _cma_pull(pid, addr, memoryview(data))
                    self._send_to(rank, got_tag | _ACK_FLAG, memoryview(b""))
                elif got_tag == tag and into is not None and len(into) == length:
                    _recv_exact_into(p.sock, into)
                    filled = True
                else:
                    data = _recv_exact(p.sock, length)
            except BaseException as e:
                with p.cond:
                    p.reader_busy = False
                    # the stream position is now undefined (possibly mid-
                    # frame): the epoch is poisoned until reconfigure
                    p.recv_error = e
                    p.cond.notify_all()
                raise
            with p.cond:
                p.reader_busy = False
                if got_tag == tag:
                    if into is not None and not filled:
                        # size mismatch: stash the frame (a correctly sized
                        # recv may claim it) and fail loudly
                        p.stash.setdefault(got_tag, []).append(data)
                        p.stash_bytes += len(data)
                        p.cond.notify_all()
                        raise RuntimeError(
                            f"tag {tag:#x}: frame is {len(data)} bytes, "
                            f"recv buffer is {len(into)}"
                        )
                    p.cond.notify_all()
                    return None if filled else data
                p.stash.setdefault(got_tag, []).append(data)
                p.stash_bytes += len(data)
                over = p.stash_bytes > self._stash_limit
                p.cond.notify_all()
                if over:
                    raise RuntimeError(
                        f"collective desync: {p.stash_bytes} bytes stashed "
                        f"while waiting for tag {tag:#x}; stashed tags "
                        f"{sorted(map(hex, p.stash))}"
                    )

    def _exchange(
        self,
        dst: int,
        send_data: memoryview,
        src: int,
        tag: int,
        into: Optional[memoryview] = None,
    ) -> Optional[bytearray]:
        """Simultaneously send to dst and receive from src (ring step) —
        the send runs on a persistent helper worker so large transfers
        can't deadlock on full OS socket buffers (round-3 review weak #4:
        a fresh Thread per hop burned hundreds of creations per step on
        the GIL; collective ops are serialized on the op thread, so ONE
        worker suffices). With ``into``, the frame lands directly in the
        caller's scratch buffer (no per-hop allocation)."""
        send_fut = self._ring_send_worker.submit(self._send_to, dst, tag, send_data)
        recv_exc: Optional[BaseException] = None
        data = None
        try:
            data = self._recv_from(src, tag, into=into)
        except BaseException as e:  # noqa: BLE001
            recv_exc = e
            # the epoch is doomed either way (a failed hop latches the
            # step and forces a flush re-quorum): unwedge a send parked on
            # a full buffer so the drain below doesn't stall recovery for
            # the full socket timeout
            try:
                self._peer(dst).sock.shutdown(socket.SHUT_RDWR)
            except Exception:  # noqa: BLE001
                pass
        send_exc: Optional[BaseException] = None
        try:
            send_fut.result()
        except BaseException as e:  # noqa: BLE001
            send_exc = e
        if recv_exc is not None:
            # prefer the ACCUSING error: a PeerGone names the dead peer
            # for eviction, a bare timeout does not
            if isinstance(send_exc, PeerGoneError) and not isinstance(
                recv_exc, PeerGoneError
            ):
                raise send_exc from recv_exc
            raise recv_exc
        if send_exc is not None:
            raise send_exc
        return data

    def _next_tag(self) -> int:
        self._op_seq = (self._op_seq + 1) & 0x00FFFFFF
        return self._op_seq

    def _count_op(self, op_name: str, nbytes: int = 0, tag: int = 0) -> int:
        """Count the op and record its issue in the flight recorder;
        returns the flight sequence id for completion marking."""
        from torchft_tpu import telemetry

        fault_point(
            "collective.issue", match=op_name,
            nbytes=nbytes, tag=tag, rank=self._rank,
        )
        plane = self.plane_info()
        telemetry.COLLECTIVE_OPS.labels(op=op_name, plane=plane).inc()
        return telemetry.FLIGHT.record_issue(
            op_name, plane, nbytes, tag=tag, rank=self._rank
        )

    def _track_flight(self, work: Work, fid: int) -> Work:
        """Mark the flight record completed/failed when the op resolves."""
        from torchft_tpu import telemetry

        work.get_future().then(
            lambda f: telemetry.FLIGHT.record_complete(fid, error=f.exception())
        )
        return work

    # -- collectives (all run on the op thread, SPMD-ordered) --

    def allreduce(
        self,
        arrays: List[np.ndarray],
        op: ReduceOp = ReduceOp.SUM,
        divisor: int = 1,
        sources: Optional[Sequence[Optional[Source]]] = None,
    ) -> Work:
        world = self._world
        if sources is None:
            sources = [None] * len(arrays)
        for arr, source in zip(arrays, sources):
            # the native ring is handed bare addresses
            for piece in source or ():
                if piece.dtype != arr.dtype or not piece.flags.c_contiguous:
                    raise ValueError(
                        "allreduce: a source is C-contiguous arrays of its "
                        f"array's dtype ({arr.dtype}); got {piece.dtype}, "
                        f"strides {piece.strides}"
                    )
        op, divisor = resolve_divisor(op, divisor, world)
        tag = self._next_tag() | 0x01000000
        nbytes = sum(int(a.nbytes) for a in arrays)
        # counted at submission like every other op (uniform semantics);
        # bytes + latency are recorded at completion in run()
        fid = self._count_op("allreduce", nbytes, tag)
        t_submit = time.perf_counter()

        def run() -> List[np.ndarray]:
            from torchft_tpu import telemetry

            t0 = time.perf_counter()
            # what the native ring takes divides as it writes; the rest is
            # summed here and divided after the span, under its own name
            native = [world > 1 and self._dp_eligible(a) for a in arrays]
            in_ring = divisor > 1 and bool(arrays) and all(native)
            account: Dict[str, float] = dict.fromkeys(RING_ACCOUNT, 0)
            # queued_s: the op's wait for this one thread. Ops run here in
            # submission order, which is what ties an event to its submitter
            with tracing.annotate(
                "exchange.ring", bytes=nbytes, queued_s=t0 - t_submit,
                divisor=divisor if in_ring else 0,
            ):
                # ops are serialized on the op thread, so arrays of one
                # allreduce may share the tag (it is a desync check, not
                # a demultiplexer; the native plane offsets per-stripe)
                try:
                    for arr, dp, source in zip(arrays, native, sources):
                        if dp:
                            self._dp_allreduce(
                                arr, op, tag, divisor, account, source
                            )
                        else:
                            self._fill(arr, source, account)
                            if world > 1:
                                self._ring_allreduce(arr, op, tag, account)
                finally:  # a failed op's account counts too
                    for k, v in account.items():
                        self._ring_account[k] += v
            # `sources` stays referenced from this frame until here: the
            # stripe threads are out of the ring, whatever a deadline has
            # done to the op's future meanwhile
            # an annotation takes its stats at entry: a zero-length one
            # after the ring carries its account, the n-th of a step for
            # the step's n-th ring
            with tracing.annotate(
                "exchange.ring.account", bytes=nbytes,
                plane=self.plane_info(), **account,
            ):
                pass
            average_in_place(
                [a for a, dp in zip(arrays, native) if not dp], divisor
            )
            self._avg_in_ring_ops += in_ring
            telemetry.record_collective(
                "allreduce", nbytes, time.perf_counter() - t0,
                self.plane_info(), count_op=False,
            )
            return arrays

        return self._track_flight(self._submit(run, op="allreduce"), fid)

    def _dp_eligible(self, arr: np.ndarray) -> bool:
        if (
            self._dp is None
            or arr.dtype != np.float32
            or not arr.flags["C_CONTIGUOUS"]
        ):
            return False
        # the codec-name → DpCodec map lives ONCE, on the binding
        # (NativeDataPlane.CODEC); a Python-only codec with no native
        # twin keeps the Python ring so the compression contract holds
        from torchft_tpu._native import NativeDataPlane

        return self._codec.name in NativeDataPlane.CODEC

    @staticmethod
    def _fill(
        arr: np.ndarray, source: Optional[Source], account: Dict[str, float]
    ) -> None:
        """An array the native ring does not run gets its source the plain
        way, on the op thread, and the account says so."""
        if source is None:
            return
        t0 = time.perf_counter()
        fill_from_sources([arr], [source])
        account["copy_s"] += time.perf_counter() - t0
        account["copy_bytes"] += arr.nbytes

    def _dp_allreduce(
        self, arr: np.ndarray, op: ReduceOp, tag: int, divisor: int,
        account: Dict[str, float], source: Optional[Source] = None,
    ) -> None:
        """Hot path: the striped C++ ring (it applies ``divisor`` where an
        element's final value is written; the wire codec — bf16 or int8 —
        runs in C++, with the same owner-bytes verbatim allgather as the
        Python ring so the decoded average is bit-identical on every
        rank). With a ``source`` it reads this rank's contribution from
        there and only writes ``arr``."""
        import time as _time

        from torchft_tpu._native import DataPlaneError

        dp = self._dp  # teardown may None the field mid-op
        if dp is None:
            raise RuntimeError("data plane torn down")
        t0 = _time.perf_counter()
        try:
            dp.allreduce(
                arr.ctypes.data,
                arr.size,
                op.value,
                self._codec.name,  # resolved via NativeDataPlane.CODEC
                tag,
                int(self._timeout.total_seconds() * 1000),
                divisor,
                None
                if source is None
                else [(piece.ctypes.data, piece.nbytes) for piece in source],
            )
        except DataPlaneError as e:
            if e.peer_rank >= 0:
                raise PeerGoneError(e.peer_rank, str(e)) from e
            raise
        finally:
            # codec work happens inside the C++ stripe workers and is not
            # separable from here; the whole native op lands in "wire"
            record_wire_stage("wire", _time.perf_counter() - t0)
            # what the stripe workers say of their own hops ({} once the
            # plane is closed)
            for k, v in dp.last_account().items():
                if k.endswith("_ns"):
                    account[k[:-3] + "_s"] += v / 1e9
                else:
                    account[k] += v

    def _ring_allreduce(
        self, arr: np.ndarray, op: ReduceOp, tag: int,
        account: Optional[Dict[str, float]] = None,
    ) -> None:
        """The Python ring. Into ``account`` (RING_ACCOUNT) goes what it
        measures anyway: its hops' seconds and received bytes as the
        pump's, a lossy codec's passes (the reduce with the decode) as
        ``codec_s``."""
        world, rank = self._world, self._rank
        right = (rank + 1) % world
        left = (rank - 1) % world
        reduce_fn = _REDUCE_FNS[op]

        flat = _flat_view(arr)
        bounds = np.linspace(0, flat.size, world + 1).astype(np.int64)
        chunks = [flat[bounds[i] : bounds[i + 1]] for i in range(world)]
        max_elems = max((int(c.size) for c in chunks), default=0)

        # optional lossy wire codec (f32 → bf16/int8 on the wire, f32
        # accumulation locally; wire_codec.py): 2-4x fewer DCN bytes/hop
        codec = self._codec
        lossy = codec.lossy and arr.dtype == np.float32 and flat.size > 0
        if lossy:
            self._ring_allreduce_codec(
                arr, op, tag, chunks, max_elems, reduce_fn, account
            )
            return

        import time as _time

        scratch = self._epoch_scratch(arr.dtype, max_elems)
        t_wire = 0.0

        # reduce-scatter phase
        for step in range(world - 1):
            send_idx = (rank - step) % world
            recv_idx = (rank - step - 1) % world
            n = int(chunks[recv_idx].size)
            view = scratch[:n]
            t0 = _time.perf_counter()
            self._exchange(
                right, _bytes_view(chunks[send_idx]), left, tag,
                into=_bytes_view(view),
            )
            t_wire += _time.perf_counter() - t0
            reduce_fn(chunks[recv_idx], view.reshape(chunks[recv_idx].shape))
        # allgather phase (raw bytes: every rank forwards the owner's
        # exact bytes, so the result is bitwise identical by construction)
        for step in range(world - 1):
            send_idx = (rank + 1 - step) % world
            recv_idx = (rank - step) % world
            n = int(chunks[recv_idx].size)
            view = scratch[:n]
            t0 = _time.perf_counter()
            self._exchange(
                right, _bytes_view(chunks[send_idx]), left, tag,
                into=_bytes_view(view),
            )
            t_wire += _time.perf_counter() - t0
            chunks[recv_idx][:] = view.reshape(chunks[recv_idx].shape)
        record_wire_stage("wire", t_wire)
        if account is not None:
            account["pump_s"] += t_wire
            account["pump_bytes"] += (
                2 * flat.nbytes - chunks[rank].nbytes - chunks[right].nbytes
            )

    def _ring_allreduce_codec(
        self, arr: np.ndarray, op: ReduceOp, tag: int,
        chunks: List[np.ndarray], max_elems: int, reduce_fn,
        account: Optional[Dict[str, float]] = None,
    ) -> None:
        """Lossy-codec ring. Reduce-scatter ships freshly encoded partial
        sums per hop (re-quantized at each hop's own magnitude, residual
        handled one level up by error feedback); the allgather phase then
        forwards the chunk OWNER's wire bytes verbatim — decode work per
        rank, zero re-encode work, and bit-identity of the decoded average
        on every rank by construction rather than by fp-rounding luck."""
        import time as _time

        world, rank = self._world, self._rank
        right = (rank + 1) % world
        left = (rank - 1) % world
        codec = self._codec
        codec.ensure_capacity(max_elems)
        max_wire = codec.wire_nbytes(max_elems)
        # double buffer: at each allgather hop one holds the bytes being
        # forwarded while the other receives the next chunk's bytes
        buf_a = self._epoch_scratch(np.uint8, max_wire, slot="wireA")
        buf_b = self._epoch_scratch(np.uint8, max_wire, slot="wireB")
        t_quant = t_wire = t_dq = 0.0

        # reduce-scatter phase
        for step in range(world - 1):
            send_idx = (rank - step) % world
            recv_idx = (rank - step - 1) % world
            n = int(chunks[recv_idx].size)
            rn = codec.wire_nbytes(n)
            t0 = _time.perf_counter()
            sv = codec.encode_into(chunks[send_idx])
            t1 = _time.perf_counter()
            rv = buf_a[:rn]
            self._exchange(right, sv, left, tag, into=_bytes_view(rv))
            t2 = _time.perf_counter()
            incoming = codec.decode_tmp(rv, n)
            reduce_fn(
                chunks[recv_idx], incoming.reshape(chunks[recv_idx].shape)
            )
            t3 = _time.perf_counter()
            t_quant += t1 - t0
            t_wire += t2 - t1
            t_dq += t3 - t2

        # the owner of each fully reduced chunk encodes it ONCE; those
        # bytes circulate verbatim, and the owner itself keeps the decode
        # of its own bytes — every rank ends with the identical f32 image
        t0 = _time.perf_counter()
        owned = chunks[(rank + 1) % world]
        ow = codec.encode_into(owned)
        cur = buf_b[: len(ow)]
        cur[:] = np.frombuffer(ow, dtype=np.uint8)
        codec.decode_into(cur, owned)
        t_quant += _time.perf_counter() - t0

        # allgather phase: forward received wire bytes untouched
        bufs = (buf_a, buf_b)
        cur_view: np.ndarray = cur
        cur_i = 1  # cur lives in buf_b; buf_a is free to receive into
        for step in range(world - 1):
            recv_idx = (rank - step) % world
            n = int(chunks[recv_idx].size)
            rn = codec.wire_nbytes(n)
            rv = bufs[1 - cur_i][:rn]
            t0 = _time.perf_counter()
            self._exchange(
                right, _bytes_view(cur_view), left, tag, into=_bytes_view(rv)
            )
            t1 = _time.perf_counter()
            codec.decode_into(rv, chunks[recv_idx])
            t_dq += _time.perf_counter() - t1
            t_wire += t1 - t0
            # rv is next hop's outgoing frame; the old cur buffer is free
            cur_view, cur_i = rv, 1 - cur_i
        record_wire_stage("quantize", t_quant)
        record_wire_stage("wire", t_wire)
        record_wire_stage("dequant_reduce", t_dq)
        if account is not None:
            account["pump_s"] += t_wire
            account["codec_s"] += t_quant + t_dq

    def allgather(self, arr: np.ndarray) -> Work:
        world, rank = self._world, self._rank
        tag = self._next_tag() | 0x02000000
        fid = self._count_op("allgather", int(arr.nbytes), tag)

        def run() -> List[np.ndarray]:
            out: List[Optional[np.ndarray]] = [None] * world
            out[rank] = arr.copy()
            if world > 1:
                right, left = (rank + 1) % world, (rank - 1) % world
                cur = np.ascontiguousarray(arr)
                cur_idx = rank
                for _ in range(world - 1):
                    data = self._exchange(right, _bytes_view(cur), left, tag)
                    cur_idx = (cur_idx - 1) % world
                    cur = np.frombuffer(data, dtype=arr.dtype).reshape(arr.shape).copy()
                    out[cur_idx] = cur
            return out  # type: ignore[return-value]

        return self._track_flight(self._submit(run, op="allgather"), fid)

    def broadcast(self, arr: np.ndarray, root: int = 0) -> Work:
        world, rank = self._world, self._rank
        tag = self._next_tag() | 0x03000000
        fid = self._count_op("broadcast", int(arr.nbytes), tag)

        def run() -> np.ndarray:
            if world > 1:
                if rank == root:
                    data = _bytes_view(arr)
                    for peer in range(world):
                        if peer != rank:
                            self._send_to(peer, tag, data)
                else:
                    data = self._recv_from(root, tag)
                    _flat_view(arr)[:] = np.frombuffer(data, dtype=arr.dtype)
            return arr

        return self._track_flight(self._submit(run, op="broadcast"), fid)

    def reduce_scatter(
        self, arrays: List[np.ndarray], op: ReduceOp = ReduceOp.SUM
    ) -> Work:
        world, rank = self._world, self._rank
        if len(arrays) != world:
            raise ValueError(f"reduce_scatter needs {world} inputs, got {len(arrays)}")
        tag = self._next_tag() | 0x04000000
        fid = self._count_op(
            "reduce_scatter", sum(int(a.nbytes) for a in arrays), tag
        )
        reduce_fn = _REDUCE_FNS[op]

        def run() -> np.ndarray:
            if world == 1:
                acc = arrays[0].copy()
            else:
                # Same schedule as the allreduce reduce-scatter phase: rank r
                # fully owns slot (r+1)%world afterwards, so permute inputs
                # one step (slot i holds input (i-1)%world) to make each rank
                # end up with the reduction of its *own* input index.
                right, left = (rank + 1) % world, (rank - 1) % world
                local = [
                    np.ascontiguousarray(arrays[(i - 1) % world]).copy()
                    for i in range(world)
                ]
                for step in range(world - 1):
                    send_idx = (rank - step) % world
                    recv_idx = (rank - step - 1) % world
                    data = self._exchange(
                        right, _bytes_view(local[send_idx]), left, tag
                    )
                    incoming = np.frombuffer(data, dtype=local[recv_idx].dtype)
                    reduce_fn(local[recv_idx], incoming.reshape(local[recv_idx].shape))
                acc = local[(rank + 1) % world]
            if op == ReduceOp.AVG:
                np.divide(acc, world, out=acc)
            return acc

        return self._track_flight(self._submit(run, op="reduce_scatter"), fid)

    def alltoall(self, arrays: List[np.ndarray]) -> Work:
        world, rank = self._world, self._rank
        if len(arrays) != world:
            raise ValueError(f"alltoall needs {world} inputs, got {len(arrays)}")
        tag = self._next_tag() | 0x05000000
        fid = self._count_op(
            "alltoall", sum(int(a.nbytes) for a in arrays), tag
        )

        def run() -> List[np.ndarray]:
            out: List[Optional[np.ndarray]] = [None] * world
            out[rank] = arrays[rank].copy()
            # Rotation schedule: round r sends to rank+r while receiving
            # from rank-r (full duplex), which is deadlock-free for any
            # world size — a pairwise send-then-recv ordering is not.
            for r in range(1, world):
                dst = (rank + r) % world
                src = (rank - r) % world
                data = self._exchange(dst, _bytes_view(arrays[dst]), src, tag)
                out[src] = (
                    np.frombuffer(data, dtype=arrays[src].dtype)
                    .reshape(arrays[src].shape)
                    .copy()
                )
            return out  # type: ignore[return-value]

        return self._track_flight(self._submit(run, op="alltoall"), fid)

    def send(self, arr: np.ndarray, dst: int, tag: int = 0) -> Work:
        wire_tag = 0x06000000 | (tag & 0xFFFFFF)
        fid = self._count_op("send", int(arr.nbytes), wire_tag)

        def run() -> None:
            self._send_to(dst, wire_tag, _bytes_view(arr))

        return self._track_flight(self._submit(run, p2p=True, op="send"), fid)

    def recv(self, arr: np.ndarray, src: int, tag: int = 0) -> Work:
        wire_tag = 0x06000000 | (tag & 0xFFFFFF)
        fid = self._count_op("recv", int(arr.nbytes), wire_tag)

        def run() -> np.ndarray:
            _flat_view(arr)  # contiguity check up front, like the old path
            done = self._recv_from(src, wire_tag, into=_bytes_view(arr))
            assert done is None, "into-receive must fill in place"
            return arr

        return self._track_flight(self._submit(run, p2p=True, op="recv"), fid)

    def barrier(self) -> Work:
        token = np.zeros(1, dtype=np.int32)
        world = self._world
        tag = self._next_tag() | 0x07000000
        fid = self._count_op("barrier", 0, tag)

        def run() -> None:
            if world > 1:
                self._ring_allreduce(token, ReduceOp.SUM, tag)

        return self._track_flight(self._submit(run, op="barrier"), fid)


# ---------------------------------------------------------------------------
# Wrappers
# ---------------------------------------------------------------------------


class CollectivesDummy(Collectives):
    """No-op backend: every op completes immediately with identity results
    (ProcessGroupDummy analogue, process_group.py:450-558)."""

    def __init__(self, rank: int = 0, world_size: int = 1) -> None:
        self._rank = rank
        self._world = world_size
        self.configure_count = 0

    def configure(self, store_addr: str, rank: int, world_size: int) -> None:
        self._rank, self._world = rank, world_size
        self.configure_count += 1

    def allreduce(self, arrays, op=ReduceOp.SUM, divisor=1):
        average_in_place(arrays, divisor)
        return Work.completed(arrays)

    def allgather(self, arr):
        return Work.completed([arr.copy() for _ in range(self._world)])

    def broadcast(self, arr, root=0):
        return Work.completed(arr)

    def reduce_scatter(self, arrays, op=ReduceOp.SUM):
        return Work.completed(arrays[self._rank].copy())

    def alltoall(self, arrays):
        return Work.completed([a.copy() for a in arrays])

    def send(self, arr, dst, tag=0):
        return Work.completed(None)

    def recv(self, arr, src, tag=0):
        return Work.completed(arr)

    def barrier(self):
        return Work.completed(None)

    def size(self) -> int:
        return self._world

    def rank(self) -> int:
        return self._rank


class ErrorSwallowingCollectives(Collectives):
    """First error latches; subsequent ops are no-ops until the next
    configure() (ErrorSwallowingProcessGroupWrapper analogue,
    process_group.py:561-654). Keeps a failed replica from hanging its
    whole group mid-step — the Manager discards the step at commit time."""

    def __init__(self, inner: Collectives) -> None:
        self._inner = inner
        self._error: Optional[Exception] = None

    @property
    def device_arrays(self) -> bool:
        return bool(getattr(self._inner, "device_arrays", False))

    def error(self) -> Optional[Exception]:
        return self._error

    def plane_info(self) -> str:
        return self._inner.plane_info()

    def wire_codec(self) -> str:
        return self._inner.wire_codec()

    def takes_sources(self) -> bool:
        return self._inner.takes_sources()

    def avg_in_ring_ops(self) -> int:
        return self._inner.avg_in_ring_ops()

    def ring_account(self) -> Dict[str, float]:
        return self._inner.ring_account()

    def report_error(self, e: Exception) -> None:
        self._error = e

    def configure(self, store_addr: str, rank: int, world_size: int) -> None:
        self._error = None
        self._inner.configure(store_addr, rank, world_size)

    def _guard(self, fn: Callable[[], Work], default) -> Work:
        if self._error is not None:
            return Work.completed(default)
        try:
            work = fn()
        except Exception as e:
            self.report_error(e)
            return Work.completed(default)

        def swallow(fut: Future):
            exc = fut.exception()
            if exc is not None and self._error is None:
                logger.exception("collective failed; latching error: %s", exc)
                self.report_error(
                    exc if isinstance(exc, Exception) else RuntimeError(str(exc))
                )
                return default
            return fut.value() if exc is None else default

        return Work(work.get_future().then(swallow))

    def allreduce(self, arrays, op=ReduceOp.SUM, divisor=1, sources=None):
        # a swallowed failure hands back the buffers as the failed op left
        # them — half summed, divided or not: the step never commits
        # (sources go only to an inner backend that takes them)
        extra = {} if sources is None else {"sources": sources}
        return self._guard(
            lambda: self._inner.allreduce(arrays, op, divisor, **extra), arrays
        )

    def allgather(self, arr):
        return self._guard(
            lambda: self._inner.allgather(arr),
            [arr.copy() for _ in range(max(1, self._inner.size()))],
        )

    def broadcast(self, arr, root=0):
        return self._guard(lambda: self._inner.broadcast(arr, root), arr)

    def reduce_scatter(self, arrays, op=ReduceOp.SUM):
        return self._guard(
            lambda: self._inner.reduce_scatter(arrays, op), arrays[0].copy()
        )

    def alltoall(self, arrays):
        return self._guard(lambda: self._inner.alltoall(arrays), arrays)

    def send(self, arr, dst, tag=0):
        return self._guard(lambda: self._inner.send(arr, dst, tag), None)

    def recv(self, arr, src, tag=0):
        return self._guard(lambda: self._inner.recv(arr, src, tag), arr)

    def barrier(self):
        return self._guard(lambda: self._inner.barrier(), None)

    def size(self) -> int:
        return self._inner.size()

    def rank(self) -> int:
        return self._inner.rank()

    def shutdown(self) -> None:
        self._inner.shutdown()


class ManagedCollectives(Collectives):
    """Routes allreduce through a Manager so quorum waits, healing zeros and
    error reporting apply (ManagedProcessGroup analogue,
    process_group.py:657-722). ``size()`` reports the *participating* world
    size, which is how dynamic membership stays invisible to user code."""

    def __init__(self, manager) -> None:
        self._manager = manager

    def wire_codec(self) -> str:
        return self._manager.wire_codec()

    def configure(self, store_addr: str, rank: int, world_size: int) -> None:
        raise RuntimeError("ManagedCollectives is configured by its Manager")

    def allreduce(self, arrays, op=ReduceOp.SUM, divisor=1):
        if len(arrays) != 1:
            raise ValueError("ManagedCollectives.allreduce takes a single array")
        if divisor != 1:
            raise ValueError("the Manager divides by its participants itself")
        return Work(self._manager.allreduce(arrays[0]))

    def allgather(self, arr):
        raise NotImplementedError("only allreduce is managed")

    def broadcast(self, arr, root=0):
        raise NotImplementedError("only allreduce is managed")

    def reduce_scatter(self, arrays, op=ReduceOp.SUM):
        raise NotImplementedError("only allreduce is managed")

    def alltoall(self, arrays):
        raise NotImplementedError("only allreduce is managed")

    def send(self, arr, dst, tag=0):
        raise NotImplementedError("only allreduce is managed")

    def recv(self, arr, src, tag=0):
        raise NotImplementedError("only allreduce is managed")

    def barrier(self):
        raise NotImplementedError("only allreduce is managed")

    def size(self) -> int:
        return self._manager.num_participants()

    def rank(self) -> int:
        rank = self._manager.participating_rank()
        return rank if rank is not None else 0
