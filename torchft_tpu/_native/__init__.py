"""ctypes loader for the C++ coordination core (``native/``).

The reference binds its Rust core with pyo3 (/root/reference/src/lib.rs);
here the equivalent bridge is a C ABI + ctypes. If the shared library is
missing (fresh checkout), it is built on first import with ``make``.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
from typing import Any, Dict, Sequence, Tuple

from torchft_tpu.utils import wire

_HERE = os.path.dirname(os.path.abspath(__file__))
# TORCHFT_NATIVE_LIB points the loader at an alternate build of the core —
# the sanitizer runs load libtftcore_asan.so/_ubsan.so this way (built by
# `make -C native asan|ubsan`; the ASan runtime must also be LD_PRELOADed
# since the interpreter itself is uninstrumented).
_LIB_OVERRIDE = os.environ.get("TORCHFT_NATIVE_LIB")
_LIB_PATH = _LIB_OVERRIDE or os.path.join(_HERE, "libtftcore.so")
_NATIVE_SRC = os.path.normpath(os.path.join(_HERE, "..", "..", "native"))

# RPC status codes (native/wire.h). CANCELLED and DEADLINE_EXCEEDED map to
# TimeoutError, everything else to RuntimeError — parity with the reference's
# Status -> PyErr mapping (src/lib.rs:380-398).
OK = 0
CANCELLED = 1
INVALID_ARGUMENT = 2
NOT_FOUND = 3
DEADLINE_EXCEEDED = 4
INTERNAL = 5
UNAVAILABLE = 6

_TIMEOUT_CODES = (CANCELLED, DEADLINE_EXCEEDED)


# The C ABI contract between this loader and libtftcore.so; must match
# native `tft_abi_version()`. v2: tft_dp_allreduce's wire_bf16 int became
# the DpCodec enum — calling an old build with codec=2 would silently run
# the bf16 wire, so a mismatch forces a rebuild instead of proceeding.
# v3: tft_lathist_snapshot/tft_lathist_reset (native latency histograms).
# v4: tft_blob_* (striped checkpoint blob plane, native/blob.cc).
# v5: divergence sentinel (mgr.should_commit digest fields + lh.digest
#     RPC) and crash-durable native blackbox breadcrumbs (blackbox.h) —
#     an old build would silently drop digests, so mismatch = rebuild.
# v6: fixed-retention time-series store (tsdb.h): tft_tsdb_snapshot/
#     tft_tsdb_reset, lighthouse /timeseries.json + piggyback series
#     ingest — an old build would silently drop every sample.
# v7: always-on sampling profiler (profiler.h): tft_prof_set_hz/hz/
#     snapshot/reset/samples_total + /diagnosis.json bundle index — an
#     old build would fail the loader's symbol lookup at import.
# v8: tft_dp_allreduce takes the divisor after `op` (the average is taken
#     inside the ring) — an old build would read it as the codec.
# v9: tft_dp_last_account (the ring's account of its last allreduce) — an
#     old build would fail the loader's symbol lookup at import.
# v10: tft_dp_allreduce_from (an allreduce that reads this rank's
#     contribution from read-only segments) and four more fields of the
#     account (copy_ns, copy_bytes, copy_aliased_bytes, from_source).
_ABI_VERSION = 10


def _build(force: bool = False) -> None:
    # Serialize concurrent first-import builds across worker processes
    # (multi-rank launches all hit this path on a fresh checkout).
    import fcntl

    lock_path = os.path.join(_HERE, ".build.lock")
    with open(lock_path, "w") as lock:
        fcntl.flock(lock, fcntl.LOCK_EX)
        try:
            if force and os.path.exists(_LIB_PATH):
                # another rank may have rebuilt while we waited on the
                # lock: re-check the on-disk ABI (via a temp copy — a
                # direct dlopen would pin the path in this namespace)
                # before paying a redundant full rebuild
                if _abi_of_file(_LIB_PATH) == _ABI_VERSION:
                    return
            if force or not os.path.exists(_LIB_PATH):
                cmd = ["make", "-s", "-B"] if force else ["make", "-s"]
                proc = subprocess.run(
                    cmd, cwd=_NATIVE_SRC, capture_output=True, text=True
                )
                if proc.returncode != 0:
                    # the compiler's own words, not a bare CalledProcessError
                    raise RuntimeError(
                        f"native core build failed ({' '.join(cmd)} in "
                        f"{_NATIVE_SRC}, rc={proc.returncode}):\n"
                        f"{proc.stdout}{proc.stderr}"
                    )
        finally:
            fcntl.flock(lock, fcntl.LOCK_UN)


def _abi_of(lib: ctypes.CDLL) -> int:
    try:
        fn = lib.tft_abi_version
    except AttributeError:
        return 1  # pre-versioning build
    fn.restype = ctypes.c_int
    fn.argtypes = []
    return int(fn())


def _abi_of_file(path: str) -> int:
    """ABI of an on-disk library, probed through a unique temp copy so
    the real path never enters this process's dlopen namespace (a cached
    mapping there would mask later rebuilds)."""
    import shutil
    import tempfile

    fd, tmp = tempfile.mkstemp(suffix=".so", dir=_HERE)
    os.close(fd)
    try:
        shutil.copy2(path, tmp)
        return _abi_of(ctypes.CDLL(tmp))
    except OSError:
        return 0  # unreadable/unloadable: treat as stale
    finally:
        try:
            os.unlink(tmp)
        except OSError:
            pass


def _load() -> ctypes.CDLL:
    if not os.path.exists(_LIB_PATH):
        if _LIB_OVERRIDE:
            raise RuntimeError(
                f"TORCHFT_NATIVE_LIB={_LIB_OVERRIDE} does not exist; build "
                "it first (e.g. `make -C native asan`)"
            )
        _build()
    lib = ctypes.CDLL(_LIB_PATH)
    if _abi_of(lib) != _ABI_VERSION:
        if _LIB_OVERRIDE:
            raise RuntimeError(
                f"TORCHFT_NATIVE_LIB={_LIB_OVERRIDE} reports ABI "
                f"{_abi_of(lib)}, this loader needs {_ABI_VERSION}; "
                "rebuild it (e.g. `make -C native asan`)"
            )
        # Stale build from an older checkout: rebuild in place, then load
        # the fresh object through a unique temp path — re-dlopen of the
        # SAME path can return the old mapping (the C++ runtime marks the
        # object NODELETE, so dlclose never unloads it). The temp file is
        # unlinked immediately after dlopen; the mapping stays valid.
        import shutil
        import tempfile

        _build(force=True)
        fd, tmp = tempfile.mkstemp(suffix=".so", dir=_HERE)
        os.close(fd)
        try:
            shutil.copy2(_LIB_PATH, tmp)
            lib = ctypes.CDLL(tmp)
        finally:
            try:
                os.unlink(tmp)
            except OSError:
                pass
        got = _abi_of(lib)
        if got != _ABI_VERSION:
            raise RuntimeError(
                f"native ABI mismatch persists after rebuild: library "
                f"reports {got}, loader needs {_ABI_VERSION} — stale "
                f"{_LIB_PATH}? remove it and re-import"
            )

    c = ctypes
    u8p = c.POINTER(c.c_uint8)

    lib.tft_buf_free.argtypes = [u8p]
    lib.tft_buf_free.restype = None

    lib.tft_lighthouse_create.argtypes = [
        c.c_char_p, c.c_uint64, c.c_uint64, c.c_uint64, c.c_uint64, c.c_uint64,
        c.c_char_p, c.c_int,
    ]
    lib.tft_lighthouse_create.restype = c.c_int64
    lib.tft_lighthouse_address.argtypes = [c.c_int64, c.c_char_p, c.c_int]
    lib.tft_lighthouse_address.restype = None
    lib.tft_lighthouse_shutdown.argtypes = [c.c_int64]
    lib.tft_lighthouse_shutdown.restype = None

    lib.tft_manager_create.argtypes = [
        c.c_char_p, c.c_char_p, c.c_char_p, c.c_char_p, c.c_char_p,
        c.c_uint64, c.c_int64, c.c_int64, c.c_char_p, c.c_int,
    ]
    lib.tft_manager_create.restype = c.c_int64
    lib.tft_manager_address.argtypes = [c.c_int64, c.c_char_p, c.c_int]
    lib.tft_manager_address.restype = None
    lib.tft_manager_shutdown.argtypes = [c.c_int64]
    lib.tft_manager_shutdown.restype = None

    lib.tft_store_create.argtypes = [c.c_char_p, c.c_char_p, c.c_int]
    lib.tft_store_create.restype = c.c_int64
    lib.tft_store_address.argtypes = [c.c_int64, c.c_char_p, c.c_int]
    lib.tft_store_address.restype = None
    lib.tft_store_shutdown.argtypes = [c.c_int64]
    lib.tft_store_shutdown.restype = None

    lib.tft_client_create.argtypes = [c.c_char_p, c.c_int64, c.c_char_p, c.c_int]
    lib.tft_client_create.restype = c.c_int64
    lib.tft_client_call.argtypes = [
        c.c_int64, c.c_char_p, u8p, c.c_int64, c.c_int64,
        c.POINTER(u8p), c.POINTER(c.c_int64), c.c_char_p, c.c_int,
    ]
    lib.tft_client_call.restype = c.c_int64
    lib.tft_client_free.argtypes = [c.c_int64]
    lib.tft_client_free.restype = None

    # native latency histograms (native/lathist.h)
    lib.tft_lathist_snapshot.argtypes = [
        c.POINTER(u8p), c.POINTER(c.c_int64), c.c_char_p, c.c_int,
    ]
    lib.tft_lathist_snapshot.restype = c.c_int64
    lib.tft_lathist_reset.argtypes = []
    lib.tft_lathist_reset.restype = None

    # time-series store (native/tsdb.h)
    lib.tft_tsdb_snapshot.argtypes = [
        c.POINTER(u8p), c.POINTER(c.c_int64), c.c_char_p, c.c_int,
    ]
    lib.tft_tsdb_snapshot.restype = c.c_int64
    lib.tft_tsdb_reset.argtypes = []
    lib.tft_tsdb_reset.restype = None

    # always-on sampling profiler (native/profiler.h)
    lib.tft_prof_set_hz.argtypes = [c.c_double]
    lib.tft_prof_set_hz.restype = None
    lib.tft_prof_hz.argtypes = []
    lib.tft_prof_hz.restype = c.c_double
    lib.tft_prof_snapshot.argtypes = [
        c.POINTER(u8p), c.POINTER(c.c_int64), c.c_char_p, c.c_int,
    ]
    lib.tft_prof_snapshot.restype = c.c_int64
    lib.tft_prof_samples_total.argtypes = []
    lib.tft_prof_samples_total.restype = c.c_int64
    lib.tft_prof_reset.argtypes = []
    lib.tft_prof_reset.restype = None

    lib.tft_quorum_compute.argtypes = [
        u8p, c.c_int64, c.POINTER(u8p), c.POINTER(c.c_int64), c.c_char_p, c.c_int,
    ]
    lib.tft_quorum_compute.restype = c.c_int64
    lib.tft_compute_quorum_results.argtypes = [
        u8p, c.c_int64, c.c_char_p, c.c_int64,
        c.POINTER(u8p), c.POINTER(c.c_int64), c.c_char_p, c.c_int,
    ]
    lib.tft_compute_quorum_results.restype = c.c_int64

    # striped cross-process gradient data plane (native/dataplane.cc)
    lib.tft_dp_create.argtypes = [c.c_int, c.c_int, c.c_int, c.c_char_p, c.c_int]
    lib.tft_dp_create.restype = c.c_int64
    lib.tft_dp_port.argtypes = [c.c_int64]
    lib.tft_dp_port.restype = c.c_int
    lib.tft_dp_connect.argtypes = [
        c.c_int64, c.c_int, c.c_char_p, c.c_int, c.c_int64, c.c_char_p, c.c_int,
    ]
    lib.tft_dp_connect.restype = c.c_int
    lib.tft_dp_wait_ready.argtypes = [c.c_int64, c.c_int64, c.c_char_p, c.c_int]
    lib.tft_dp_wait_ready.restype = c.c_int
    lib.tft_dp_enable_cma.argtypes = [
        c.c_int64, c.POINTER(c.c_int64), c.c_int, c.c_char_p, c.c_int,
    ]
    lib.tft_dp_enable_cma.restype = c.c_int
    lib.tft_dp_allreduce.argtypes = [
        c.c_int64, c.c_void_p, c.c_int64, c.c_int, c.c_int, c.c_int, c.c_int,
        c.c_uint32, c.c_int64, c.POINTER(c.c_int), c.c_char_p, c.c_int,
    ]
    lib.tft_dp_allreduce.restype = c.c_int
    lib.tft_dp_allreduce_from.argtypes = [
        c.c_int64, c.c_void_p, c.c_int64, c.POINTER(c.c_uint64),
        c.POINTER(c.c_int64), c.c_int, c.c_int, c.c_int, c.c_int, c.c_int,
        c.c_uint32, c.c_int64, c.POINTER(c.c_int), c.c_char_p, c.c_int,
    ]
    lib.tft_dp_allreduce_from.restype = c.c_int
    lib.tft_dp_last_account.argtypes = [
        c.c_int64, c.POINTER(c.c_int64), c.c_int
    ]
    lib.tft_dp_last_account.restype = c.c_int
    lib.tft_dp_free.argtypes = [c.c_int64]
    lib.tft_dp_free.restype = None

    # striped checkpoint blob plane (native/blob.cc)
    lib.tft_blob_serve_create.argtypes = [c.c_char_p, c.c_int]
    lib.tft_blob_serve_create.restype = c.c_int64
    lib.tft_blob_serve_port.argtypes = [c.c_int64]
    lib.tft_blob_serve_port.restype = c.c_int
    lib.tft_blob_stage.argtypes = [
        c.c_int64, c.POINTER(c.c_uint64), c.POINTER(c.c_int64), c.c_int,
        c.c_uint64, c.c_char_p, c.c_int,
    ]
    lib.tft_blob_stage.restype = c.c_int
    lib.tft_blob_unstage.argtypes = [c.c_int64]
    lib.tft_blob_unstage.restype = c.c_int
    lib.tft_blob_serve_free.argtypes = [c.c_int64]
    lib.tft_blob_serve_free.restype = None
    lib.tft_blob_fetch.argtypes = [
        c.c_char_p, c.c_int, c.c_uint64, c.c_uint64, c.c_uint64,
        c.c_void_p, c.c_int64, c.c_char_p, c.c_int,
    ]
    lib.tft_blob_fetch.restype = c.c_int

    return lib


_lib = _load()

_ERRLEN = 1024


def _raise_status(code: int, msg: str) -> None:
    if code in _TIMEOUT_CODES:
        raise TimeoutError(msg)
    raise RuntimeError(msg)


def _errbuf() -> ctypes.Array:
    return ctypes.create_string_buffer(_ERRLEN)


def _take_out(outp: Any, outlen: Any) -> bytes:
    try:
        return ctypes.string_at(outp, outlen.value)
    finally:
        _lib.tft_buf_free(outp)


class NativeClient:
    """Generic RPC client over the C++ transport (retry/backoff/keepalive
    live in native/rpc.cc, parity with src/net.rs + src/retry.rs)."""

    def __init__(self, addr: str, connect_timeout_ms: int) -> None:
        err = _errbuf()
        self._h = _lib.tft_client_create(
            addr.encode(), int(connect_timeout_ms), err, _ERRLEN
        )
        if self._h == 0:
            _raise_status(UNAVAILABLE, err.value.decode())
        self._addr = addr

    @property
    def addr(self) -> str:
        return self._addr

    def call(self, method: str, req: Dict[str, Any], timeout_ms: int) -> Dict[str, Any]:
        buf = wire.encode(req)
        cbuf = (ctypes.c_uint8 * len(buf)).from_buffer_copy(buf) if buf else None
        outp = ctypes.POINTER(ctypes.c_uint8)()
        outlen = ctypes.c_int64()
        err = _errbuf()
        code = _lib.tft_client_call(
            self._h, method.encode(), cbuf, len(buf), int(timeout_ms),
            ctypes.byref(outp), ctypes.byref(outlen), err, _ERRLEN,
        )
        if code != OK:
            _raise_status(code, f"{method}: {err.value.decode()}")
        return wire.decode(_take_out(outp, outlen))

    def close(self) -> None:
        if self._h:
            _lib.tft_client_free(self._h)
            self._h = 0

    def __del__(self) -> None:
        try:
            self.close()
        except Exception:
            pass


def _server_address(getter: Any, h: int) -> str:
    buf = ctypes.create_string_buffer(512)
    getter(h, buf, 512)
    return buf.value.decode()


def lighthouse_create(
    bind: str,
    min_replicas: int,
    join_timeout_ms: int,
    quorum_tick_ms: int,
    heartbeat_timeout_ms: int,
    evict_probe_ms: int = 100,
) -> Tuple[int, str]:
    err = _errbuf()
    h = _lib.tft_lighthouse_create(
        bind.encode(), min_replicas, join_timeout_ms, quorum_tick_ms,
        heartbeat_timeout_ms, evict_probe_ms, err, _ERRLEN,
    )
    if h == 0:
        raise RuntimeError(err.value.decode())
    return h, _server_address(_lib.tft_lighthouse_address, h)


def lighthouse_shutdown(h: int) -> None:
    _lib.tft_lighthouse_shutdown(h)


def manager_create(
    replica_id: str,
    lighthouse_addr: str,
    hostname: str,
    bind: str,
    store_addr: str,
    world_size: int,
    heartbeat_interval_ms: int,
    connect_timeout_ms: int,
) -> Tuple[int, str]:
    err = _errbuf()
    h = _lib.tft_manager_create(
        replica_id.encode(), lighthouse_addr.encode(), hostname.encode(),
        bind.encode(), store_addr.encode(), world_size,
        heartbeat_interval_ms, connect_timeout_ms, err, _ERRLEN,
    )
    if h == 0:
        msg = err.value.decode()
        if "timed out" in msg:
            raise TimeoutError(msg)
        raise RuntimeError(msg)
    return h, _server_address(_lib.tft_manager_address, h)


def manager_shutdown(h: int) -> None:
    _lib.tft_manager_shutdown(h)


def store_create(bind: str) -> Tuple[int, str]:
    err = _errbuf()
    h = _lib.tft_store_create(bind.encode(), err, _ERRLEN)
    if h == 0:
        raise RuntimeError(err.value.decode())
    return h, _server_address(_lib.tft_store_address, h)


def store_shutdown(h: int) -> None:
    _lib.tft_store_shutdown(h)


def _pure_call(fn: Any, buf: bytes, *extra: Any) -> Dict[str, Any]:
    cbuf = (ctypes.c_uint8 * len(buf)).from_buffer_copy(buf)
    outp = ctypes.POINTER(ctypes.c_uint8)()
    outlen = ctypes.c_int64()
    err = _errbuf()
    code = fn(cbuf, len(buf), *extra, ctypes.byref(outp), ctypes.byref(outlen),
              err, _ERRLEN)
    if code != OK:
        _raise_status(code, err.value.decode())
    return wire.decode(_take_out(outp, outlen))


def quorum_compute(state: Dict[str, Any]) -> Dict[str, Any]:
    """Run the C++ quorum_compute pure function on an explicit state.

    For unit tests (parity with src/lighthouse.rs:582-1001 table tests)."""
    return _pure_call(_lib.tft_quorum_compute, wire.encode(state))


def compute_quorum_results(
    quorum: Dict[str, Any], replica_id: str, rank: int
) -> Dict[str, Any]:
    """Run the C++ compute_quorum_results pure function.

    For unit tests (parity with src/manager.rs:720-850 table tests)."""
    return _pure_call(
        _lib.tft_compute_quorum_results, wire.encode(quorum),
        replica_id.encode(), rank,
    )


# Fixed log2 bucket grid of the native latency histograms, in seconds —
# MUST mirror native/lathist.h (kMinExp=-20, kNumBounds=27): one bucket
# per binary order of magnitude from ~1 µs to 64 s plus an overflow slot.
# Shared with telemetry.anatomy.LOG2_BUCKETS so Python- and native-side
# distributions live on one grid and cross-process merges are exact.
LATHIST_BOUNDS_S = tuple(2.0 ** e for e in range(-20, 7))


def lathist_snapshot() -> Dict[str, Dict[str, Any]]:
    """Snapshot this process's native latency histograms (dp.hop,
    dp.stripe, rpc.serve, quorum.fanout) as
    ``{op: {"counts": [int x 28], "count": int, "sum_ns": int}}``.
    ``counts`` are RAW per-bucket tallies on the fixed
    :data:`LATHIST_BOUNDS_S` grid (last slot = overflow), so merging two
    processes' snapshots is exact elementwise addition."""
    outp = ctypes.POINTER(ctypes.c_uint8)()
    outlen = ctypes.c_int64()
    err = _errbuf()
    code = _lib.tft_lathist_snapshot(
        ctypes.byref(outp), ctypes.byref(outlen), err, _ERRLEN
    )
    if code != OK:
        _raise_status(code, err.value.decode())
    return wire.decode(_take_out(outp, outlen))


def lathist_reset() -> None:
    """Zero every native latency histogram (tests/bench interval resets)."""
    _lib.tft_lathist_reset()


def tsdb_snapshot() -> Dict[str, Dict[str, Any]]:
    """Snapshot this process's time-series store (the in-process
    lighthouse's fixed-retention sample rings, ``native/tsdb.h``) as
    ``{replica: {series: {"samples": [[epoch, step, value], ...]}}}``,
    oldest-first per series — the test surface behind the lighthouse's
    ``GET /timeseries.json`` range queries."""
    outp = ctypes.POINTER(ctypes.c_uint8)()
    outlen = ctypes.c_int64()
    err = _errbuf()
    code = _lib.tft_tsdb_snapshot(
        ctypes.byref(outp), ctypes.byref(outlen), err, _ERRLEN
    )
    if code != OK:
        _raise_status(code, err.value.decode())
    return wire.decode(_take_out(outp, outlen))


def tsdb_reset() -> None:
    """Clear the process time-series store (tests)."""
    _lib.tft_tsdb_reset()


def prof_set_hz(hz: float) -> None:
    """Retarget the native sampling profiler's rate live (0 pauses, >0
    arms — the diagnosis engine's burst boost; see native/profiler.h)."""
    _lib.tft_prof_set_hz(float(hz))


def prof_hz() -> float:
    """The native profiler's effective sampling rate (resolving the
    ``TORCHFT_PROF_HZ`` env default on first call; 0 = disarmed)."""
    return float(_lib.tft_prof_hz())


def prof_snapshot() -> str:
    """Flamegraph-ready collapsed stacks of every native sample drained
    so far: ``"label;root;...;leaf count\\n"`` per unique (thread label,
    stack), sorted. Cumulative — diff two snapshots
    (:func:`torchft_tpu.telemetry.profiler.subtract_folded`) for a
    bounded capture window."""
    outp = ctypes.POINTER(ctypes.c_uint8)()
    outlen = ctypes.c_int64()
    err = _errbuf()
    code = _lib.tft_prof_snapshot(
        ctypes.byref(outp), ctypes.byref(outlen), err, _ERRLEN
    )
    if code != OK:
        _raise_status(code, err.value.decode())
    return _take_out(outp, outlen).decode(errors="replace")


def prof_samples_total() -> int:
    """Native samples aggregated since process start (or the last
    :func:`prof_reset`)."""
    return int(_lib.tft_prof_samples_total())


def prof_reset() -> None:
    """Drop every aggregated native sample (tests / capture windows)."""
    _lib.tft_prof_reset()


class _iovec(ctypes.Structure):
    _fields_ = [("iov_base", ctypes.c_void_p), ("iov_len", ctypes.c_size_t)]


def _libc() -> ctypes.CDLL:
    global _LIBC
    if _LIBC is None:
        _LIBC = ctypes.CDLL("libc.so.6", use_errno=True)
        # ssize_t return: the default c_int would truncate >=2GiB pulls
        # into spurious errors or wrong offset advances
        _LIBC.process_vm_readv.restype = ctypes.c_ssize_t
        _LIBC.process_vm_readv.argtypes = [
            ctypes.c_int,
            ctypes.POINTER(_iovec),
            ctypes.c_ulong,
            ctypes.POINTER(_iovec),
            ctypes.c_ulong,
            ctypes.c_ulong,
        ]
    return _LIBC


_LIBC: "ctypes.CDLL | None" = None


def cma_read_into(pid: int, addr: int, view: memoryview) -> None:
    """process_vm_readv ``len(view)`` bytes from ``pid``'s address space
    straight into the writable buffer ``view`` (single copy — the p2p CMA
    fast path's pull primitive). Raises OSError when the kernel says no."""
    libc = _libc()
    n = len(view)
    buf = (ctypes.c_char * n).from_buffer(view)
    off = 0
    while off < n:
        local = _iovec(ctypes.addressof(buf) + off, n - off)
        remote = _iovec(addr + off, n - off)
        got = libc.process_vm_readv(
            pid, ctypes.byref(local), 1, ctypes.byref(remote), 1, 0
        )
        if got <= 0:
            raise OSError(ctypes.get_errno(), "process_vm_readv failed")
        off += got


def cma_read(pid: int, addr: int, n: int) -> bytes:
    """One process_vm_readv of ``n`` bytes from ``pid``'s address space —
    the rendezvous probe for the CMA transport (a token round-trip proves
    the published pid is addressable from THIS pid namespace and ptrace
    policy allows the attach). Raises OSError when the kernel says no."""
    libc = _libc()
    buf = ctypes.create_string_buffer(n)
    local = _iovec(ctypes.addressof(buf), n)
    remote = _iovec(addr, n)
    got = libc.process_vm_readv(
        pid, ctypes.byref(local), 1, ctypes.byref(remote), 1, 0
    )
    if got != n:
        raise OSError(ctypes.get_errno(), "process_vm_readv failed")
    return buf.raw


class DataPlaneError(ConnectionError):
    """Native data-plane op failed; ``peer_rank`` is the ring rank whose
    socket broke (−1 when indeterminate) for eviction attribution."""

    def __init__(self, peer_rank: int, msg: str) -> None:
        super().__init__(msg)
        self.peer_rank = peer_rank


class BlobServer:
    """ctypes wrapper for the striped checkpoint blob plane's serving
    side (native/blob.cc): stages the flattened state tree's host buffers
    (scattered — no coalescing copy) and serves arbitrary byte ranges of
    their logical concatenation to healing peers, GIL-free. The caller
    must keep the staged buffers alive until :meth:`unstage` returns."""

    def __init__(self) -> None:
        err = _errbuf()
        self._h = _lib.tft_blob_serve_create(err, _ERRLEN)
        if self._h == 0:
            raise RuntimeError(f"blob server create: {err.value.decode()}")
        self.port = int(_lib.tft_blob_serve_port(self._h))

    def stage(self, ptrs: "list[int]", lens: "list[int]", token: int) -> None:
        """Open the serving window over the buffers at ``ptrs``/``lens``
        (base addresses + byte lengths, stream order). ``token`` names
        this staging generation; fetches carrying any other token are
        answered with a loud stale error, never stale bytes."""
        n = len(ptrs)
        arr_p = (ctypes.c_uint64 * n)(*ptrs)
        arr_l = (ctypes.c_int64 * n)(*lens)
        err = _errbuf()
        rc = _lib.tft_blob_stage(self._h, arr_p, arr_l, n, token, err, _ERRLEN)
        if rc != 0:
            raise RuntimeError(f"blob stage: {err.value.decode()}")

    def unstage(self) -> None:
        """Close the serving window; returns once no in-flight serve
        still reads the staged buffers (they may be freed after this)."""
        if self._h:
            _lib.tft_blob_unstage(self._h)

    def close(self) -> None:
        if self._h:
            _lib.tft_blob_serve_free(self._h)
            self._h = 0

    def __del__(self) -> None:
        try:
            self.close()
        except Exception:
            pass


def blob_fetch(
    host: str,
    port: int,
    token: int,
    offset: int,
    length: int,
    view: memoryview,
    timeout_ms: int = 60000,
) -> None:
    """Pull ``length`` bytes at ``offset`` of the peer's staged blob
    straight into the writable buffer ``view`` (the healer-side range
    primitive; the GIL is released for the duration). Raises
    TimeoutError on deadline, ConnectionError on any transfer failure —
    a cut connection surfaces as a failed range, never short data."""
    assert len(view) == length, (len(view), length)
    buf = (ctypes.c_char * length).from_buffer(view)
    err = _errbuf()
    rc = _lib.tft_blob_fetch(
        host.encode(), port, token, offset, length,
        ctypes.addressof(buf), timeout_ms, err, _ERRLEN,
    )
    if rc == -2:
        raise TimeoutError(f"blob fetch: {err.value.decode()}")
    if rc != 0:
        raise ConnectionError(f"blob fetch: {err.value.decode()}")


class NativeDataPlane:
    """ctypes wrapper for the striped C++ gradient plane (dataplane.cc).

    One instance per collectives epoch: rendezvous (store addresses,
    who-dials-whom) stays in Python; the hot allreduce bytes never touch
    the interpreter (ctypes drops the GIL for the duration of the call).
    """

    DTYPE_F32 = 0
    OP = {"sum": 0, "avg": 1, "max": 2, "min": 3}
    # wire codecs (native/dataplane.h DpCodec; formats mirror
    # torchft_tpu/wire_codec.py byte for byte)
    CODEC = {"f32": 0, "bfloat16": 1, "int8": 2}
    # the fields of last_account(), in the order of native/dataplane.h's
    # DpAccount (which says what each one times or counts)
    ACCOUNT = (
        "desc_wait_ns", "pull_ns", "ack_wait_ns", "pump_ns", "reduce_ns",
        "codec_ns", "pull_bytes", "pump_bytes", "reduce_bytes", "stripes",
        "slowest_stripe_ns", "copy_ns", "copy_bytes", "copy_aliased_bytes",
        "from_source",
    )

    def __init__(self, rank: int, world: int, nstripes: int = 4) -> None:
        err = _errbuf()
        self._h = _lib.tft_dp_create(rank, world, nstripes, err, _ERRLEN)
        if self._h == 0:
            raise RuntimeError(f"dataplane create: {err.value.decode()}")
        self.rank = rank
        self.world = world
        self.nstripes = nstripes
        self.port = int(_lib.tft_dp_port(self._h))

    def connect(self, peer: int, host: str, port: int, timeout_ms: int) -> None:
        err = _errbuf()
        rc = _lib.tft_dp_connect(
            self._h, peer, host.encode(), port, timeout_ms, err, _ERRLEN
        )
        if rc != 0:
            raise DataPlaneError(
                peer, f"dataplane dial {peer}: {err.value.decode()}"
            )

    def wait_ready(self, timeout_ms: int) -> None:
        err = _errbuf()
        rc = _lib.tft_dp_wait_ready(self._h, timeout_ms, err, _ERRLEN)
        if rc != 0:
            raise TimeoutError(f"dataplane rendezvous: {err.value.decode()}")

    def enable_cma(self, pids: "list[int]") -> None:
        """Switch ring payloads to cross-memory attach (one-copy pulls
        from the left neighbor's address space). Caller must have proven
        all ranks same-host + CMA-capable; ``pids`` indexed by rank."""
        arr = (ctypes.c_int64 * len(pids))(*pids)
        err = _errbuf()
        rc = _lib.tft_dp_enable_cma(self._h, arr, len(pids), err, _ERRLEN)
        if rc != 0:
            raise RuntimeError(f"enable_cma: {err.value.decode()}")

    def allreduce(
        self,
        ptr: int,
        nelems: int,
        op: str,
        codec: "int | str" = 0,
        tag: int = 0,
        timeout_ms: int = 60000,
        divisor: int = 1,
        source: "Sequence[tuple[int, int]] | None" = None,
    ) -> None:
        """In-place f32 ring allreduce on the buffer at ``ptr``. Blocking —
        call from the collectives op thread; the GIL is released.
        ``codec`` selects the wire format (``CODEC`` map / DpCodec enum):
        lossy codecs quantize on the wire while accumulation stays f32,
        and the decoded result is bit-identical on every rank.
        ``divisor`` (``"sum"`` only) makes the sum an average inside the
        ring — each element divided once, where its final f32 value is
        written, bit for bit ``np.divide(sum, divisor)``; ``"avg"`` is
        ``"sum"`` with the divisor ``world``.
        With ``source`` — ``(address, nbytes)`` of read-only segments whose
        concatenation is this rank's ``nelems`` f32 — the op is not in
        place: the ring reads its contribution from the segments and only
        writes the buffer at ``ptr``, which ends bit for bit as the
        in-place op on a packed copy would leave it. The caller keeps the
        segments' memory alive until this returns."""
        err = _errbuf()
        bad_peer = ctypes.c_int(-1)
        codec_i = self.CODEC[codec] if isinstance(codec, str) else int(codec)
        tail = (
            self.DTYPE_F32, self.OP[op], int(divisor), codec_i, tag,
            timeout_ms, ctypes.byref(bad_peer), err, _ERRLEN,
        )
        if source is None:
            rc = _lib.tft_dp_allreduce(self._h, ptr, nelems, *tail)
        else:
            addrs = (ctypes.c_uint64 * len(source))(*(a for a, _ in source))
            sizes = (ctypes.c_int64 * len(source))(*(n for _, n in source))
            rc = _lib.tft_dp_allreduce_from(
                self._h, ptr, nelems, addrs, sizes, len(source), *tail
            )
        if rc == -2:
            # deadline, no peer named: slow-but-alive must be retryable,
            # never an eviction-worthy accusation
            raise TimeoutError(f"dataplane allreduce: {err.value.decode()}")
        if rc != 0:
            raise DataPlaneError(
                int(bad_peer.value),
                f"dataplane allreduce: {err.value.decode()}",
            )

    def last_account(self) -> "dict[str, int]":
        """Where the last :meth:`allreduce` on this plane spent its hops
        (``ACCOUNT``; a failed op reports what it had): each time the mean
        over the op's stripes, each byte count their sum; ``{}`` once the
        plane is closed. Call it from the thread that called
        ``allreduce``."""
        out = (ctypes.c_int64 * len(self.ACCOUNT))()
        n = _lib.tft_dp_last_account(self._h, out, len(self.ACCOUNT))
        return dict(zip(self.ACCOUNT, out)) if n == len(self.ACCOUNT) else {}

    def close(self) -> None:
        if self._h:
            _lib.tft_dp_free(self._h)
            self._h = 0

    def __del__(self) -> None:
        try:
            self.close()
        except Exception:
            pass
