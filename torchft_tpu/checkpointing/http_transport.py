"""HTTP checkpoint transport — the default live-recovery path.

Reference: torchft/checkpointing/http_transport.py (in-process
ThreadingHTTPServer serving ``/checkpoint/{step}/...``, RWLock-gated so
GETs block while no checkpoint is staged) and http.py (IPv6 server with a
deep accept backlog). Same design here, serving JAX pytrees via the raw
buffer streaming in :mod:`torchft_tpu.checkpointing.serialization`.

Beyond the reference (docs/heal_plane.md):

* **Striped multi-source heal** — :meth:`HTTPTransport.recv_checkpoint_multi`
  pulls byte-balanced ranges of the flattened state tree from EVERY live
  peer in parallel (work-queue scheduling, so a source dying mid-heal just
  hands its pending ranges to the survivors). The bulk bytes ride the
  native blob plane (``native/blob.cc``, GIL-free, shared stripe layer
  with the gradient data plane) when available, with the HTTP
  ``/range_{offset}_{len}`` endpoint as the fallback; metadata, the
  stripe plan and the differential negotiation stay on HTTP.
* **Differential heal** — a healer that still holds the committed state
  at its last step asks ``/delta_{since}_{digest}`` and receives only the
  leaves that changed since (:mod:`torchft_tpu.checkpointing.delta`).
* **Consistency by digest** — every source's ``/stripemeta`` carries the
  staged tree digest; the healer only stripes across sources whose
  digests agree with the primary's (so e.g. LocalSGD groups with diverged
  inner state automatically degrade to single-source heal instead of
  mixing bytes from two different states).

Chunked mode (``num_chunks > 0``): the header plus a chunk manifest is
served at ``/metadata``; array buffers are grouped by greedy-LPT size
balance into ``num_chunks`` groups fetched in parallel — the analogue of
the reference's parallel chunk GETs (http_transport.py:243-266). The
striped path above supersedes it for heals (byte ranges balance exactly
where whole-buffer LPT cannot), but the endpoint remains for tooling.
"""

from __future__ import annotations

import logging
import os
import pickle
import socket
import struct
import threading
import time
import urllib.parse
import urllib.request
from collections import deque
from datetime import timedelta
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Callable, Dict, Generic, List, Optional, Tuple, TypeVar

import numpy as np

from torchft_tpu import telemetry
from torchft_tpu.checkpointing._rwlock import RWLock
from torchft_tpu.checkpointing import delta as delta_mod
from torchft_tpu.checkpointing.serialization import (
    Flattening,
    as_bytes,
    unflatten_state,
)
from torchft_tpu.checkpointing.stripes import (
    assign_chunk_groups,
    heal_sources_limit,
    heal_stripes_per_source,
    slice_buffers,
    stripe_ranges,
)
from torchft_tpu.checkpointing.transport import CheckpointTransport

logger = logging.getLogger(__name__)

T = TypeVar("T")

__all__ = ["HTTPTransport"]


class _Server(ThreadingHTTPServer):
    address_family = socket.AF_INET6
    request_queue_size = 1024
    daemon_threads = True


TRACE_HEADER = "X-TFT-Trace"

# staging tokens are process-global so a transport recreated in-place can
# never reissue a token an old healer still holds
_STAGING_TOKEN = iter(range(1, 1 << 62))
_STAGING_TOKEN_LOCK = threading.Lock()


def _next_token() -> int:
    with _STAGING_TOKEN_LOCK:
        return next(_STAGING_TOKEN)


def _heal_digest_enabled() -> bool:
    """``TORCHFT_HEAL_DIGEST=0`` disables staging digests — and with
    them multi-source striping AND differential heal (both are
    digest-anchored); heals then behave like the single-source
    reference path."""
    return os.environ.get("TORCHFT_HEAL_DIGEST", "1") != "0"


def _heal_meta_timeout_s() -> float:
    """How long the striped-heal endpoints wait for a source that is NOT
    staging to start (``TORCHFT_HEAL_META_TIMEOUT_S``, default 5): a
    source that will not stage this round costs seconds, not the transfer
    timeout. A source that IS staging is waited for up to the transfer
    timeout — flatten + digest of a multi-GB state takes longer than any
    fixed few seconds (:meth:`HTTPTransport._await_window`)."""
    try:
        return float(os.environ.get("TORCHFT_HEAL_META_TIMEOUT_S", "5"))
    except ValueError:
        return 5.0


def _heal_native_enabled() -> bool:
    """``TORCHFT_HEAL_NATIVE=0`` keeps heal bytes on HTTP (the native
    blob plane is the default bulk path when the core is loadable)."""
    return os.environ.get("TORCHFT_HEAL_NATIVE", "1") != "0"


def _traced_urlopen(url: str, timeout: float):
    """urlopen with the caller's trace context attached, so the serving
    side records its span as a child of the requesting replica's span —
    the cross-replica parent/child link on the merged timeline."""
    req = urllib.request.Request(url)
    try:
        req.add_header(
            TRACE_HEADER,
            telemetry.TRACER.format_carrier(telemetry.TRACER.inject()),
        )
    except Exception:  # noqa: BLE001 — tracing must never fail a transfer
        pass
    return urllib.request.urlopen(req, timeout=timeout)


def _alloc_dest(total: int) -> np.ndarray:
    """The destination of a striped fetch: ``total`` bytes, not zeroed."""
    return np.empty(total, dtype=np.uint8)


# retained import surface: the chunk grouping moved to stripes.py (shared
# with tests and the heal planner)
_assign_chunks = assign_chunk_groups


class HTTPTransport(CheckpointTransport[T], Generic[T]):
    """Serves the staged checkpoint over HTTP from an in-process server."""

    def __init__(
        self,
        timeout: timedelta = timedelta(seconds=60),
        num_chunks: int = 0,
        hostname: Optional[str] = None,
    ) -> None:
        self._timeout = timeout
        self._num_chunks = num_chunks
        self._hostname = hostname or socket.gethostname()
        # payload size of the last recv_checkpoint — the Manager reads it
        # for the heal_end event's bytes field
        self.last_recv_bytes: int = 0
        # per-source throughput + stage attribution of the last
        # multi-source recv (docs/heal_plane.md; the Manager embeds it in
        # heal_end and the recovery bench exports it)
        self.last_heal_stats: Dict[str, Any] = {}
        # differential-heal digest trail (checkpointing/delta.CommitTrail)
        # — attached by the Manager when TORCHFT_HEAL_DIFF is on
        self.commit_trail: Optional[delta_mod.CommitTrail] = None

        self._lock = RWLock(timeout=timeout.total_seconds())
        self._step: Optional[int] = None
        self._header: Optional[bytes] = None
        self._buffers: List[np.ndarray] = []
        self._sizes: List[int] = []
        self._total = 0
        self._digests: Optional[List[str]] = None
        self._tree_digest: Optional[str] = None
        self._groups: List[List[int]] = []
        self._token = 0
        self._stage_stats: Dict[str, Any] = {}
        # native blob server (bulk heal bytes), created lazily at first
        # staging; None when the native core is unavailable or disabled
        self._blob = None
        self._blob_failed = False
        # serving starts disallowed: readers block until first staging.
        # _allowed tracks whether the write lock is currently released (the
        # serving window is open); only the manager's quorum/commit path
        # flips it, and that path is single-threaded by the Manager.
        self._lock.w_acquire()
        self._allowed = False
        # True while send_checkpoint is between closing the old window and
        # opening the new one: the window WILL open, however long the
        # flatten + digest of this state takes
        self._staging = False

        transport = self

        class Handler(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"

            def log_message(self, fmt, *args):  # quiet
                pass

            def do_GET(self) -> None:
                # /metrics needs no checkpoint state: serve the process
                # telemetry BEFORE the staging lock, so a scrape succeeds
                # even while no checkpoint is staged (readers would block)
                if self.path.rstrip("/") == "/metrics":
                    body = telemetry.render_prometheus().encode()
                    self.send_response(200)
                    self.send_header(
                        "Content-Type", "text/plain; version=0.0.4"
                    )
                    self.send_header("Content-Length", str(len(body)))
                    self.end_headers()
                    try:
                        self.wfile.write(body)
                    except (BrokenPipeError, socket.timeout):
                        pass
                    return
                # bound socket writes so one stalled healing peer can't hold
                # the read lock forever (which would block the next
                # disallow_checkpoint and fail should_commit on this side)
                self.connection.settimeout(transport._timeout.total_seconds())
                parts = self.path.strip("/").split("/")
                # striped-heal endpoints wait only briefly for a staging
                # window: a healer probing a source whose quorum round ran
                # allow_heal=False (death-watch re-quorum racing a rejoin)
                # would otherwise park for the full transfer timeout on a
                # window that never opens this round — it should drop the
                # source fast and retry next quorum (docs/heal_plane.md)
                bounded = len(parts) == 3 and (
                    parts[2] == "stripemeta"
                    or parts[2].startswith(("range_", "delta_"))
                )
                try:
                    transport._await_window(bounded)
                except TimeoutError:
                    self.send_error(503, "no checkpoint staged within timeout")
                    return
                try:
                    # /checkpoint/{step}/{what}
                    if len(parts) != 3 or parts[0] != "checkpoint":
                        self.send_error(404, f"bad path {self.path}")
                        return
                    step = int(parts[1])
                    if step != transport._step:
                        self.send_error(
                            410, f"step {step} not staged (have {transport._step})"
                        )
                        return
                    what = parts[2]
                    if what == "full":
                        payload = transport._render_full()
                    elif what == "metadata":
                        payload = transport._render_metadata()
                    elif what == "stripemeta":
                        payload = transport._render_stripemeta()
                    elif what.startswith("chunk_"):
                        payload = transport._render_chunk(int(what[len("chunk_") :]))
                    elif what.startswith("range_"):
                        _, off_s, len_s = what.split("_")
                        payload = transport._render_range(
                            int(off_s), int(len_s)
                        )
                    elif what.startswith("delta_"):
                        _, since_s, digest = what.split("_")
                        payload = transport._render_delta(int(since_s), digest)
                    else:
                        self.send_error(404, f"bad path {self.path}")
                        return
                    from torchft_tpu.faultinject.core import fault_point

                    inj = fault_point(
                        "ckpt.serve", match=what, wire=True, step=step,
                        nbytes=sum(len(p) for p in payload),
                    )
                    if inj is not None and inj.action in ("drop", "torn"):
                        # checkpoint-serve death mid-heal: promise the
                        # full Content-Length, stream only a prefix, then
                        # cut the connection — the healer must fail the
                        # transfer (short read), never stage the torn
                        # state; it retries on its next quorum
                        self._serve_torn(
                            payload,
                            inj.frac if inj.action == "torn" else 0.0,
                        )
                        return
                    self.send_response(200)
                    nbytes = sum(len(p) for p in payload)
                    self.send_header("Content-Type", "application/octet-stream")
                    self.send_header("Content-Length", str(nbytes))
                    self.end_headers()
                    t0 = time.perf_counter()
                    # child span of the healing replica's heal_recv span:
                    # the requester ships its trace context in a header
                    carrier = telemetry.TRACER.parse_carrier(
                        self.headers.get(TRACE_HEADER, "") or ""
                    )
                    with telemetry.TRACER.span(
                        "checkpoint_serve",
                        parent=carrier,
                        # our own identity, not the carrier's: the span
                        # joins the HEALER's trace (parent/trace_id) but
                        # must render on the SERVING replica's lane
                        replica_id=(
                            telemetry.TRACER.context()["replica_id"] or None
                        ),
                        path=self.path,
                        bytes=nbytes,
                    ):
                        for part in payload:
                            self.wfile.write(part)
                    telemetry.record_checkpoint(
                        "send", nbytes, time.perf_counter() - t0, "http"
                    )
                except (BrokenPipeError, socket.timeout):
                    pass
                except Exception as e:  # noqa: BLE001 — report to the peer
                    logger.exception("checkpoint GET failed")
                    try:
                        self.send_error(500, str(e))
                    except Exception:
                        pass
                finally:
                    transport._lock.r_release()

            def _serve_torn(self, payload, frac: float) -> None:
                nbytes = sum(len(p) for p in payload)
                self.send_response(200)
                self.send_header("Content-Type", "application/octet-stream")
                self.send_header("Content-Length", str(nbytes))
                self.end_headers()
                budget = int(nbytes * frac)
                try:
                    for part in payload:
                        if budget <= 0:
                            break
                        chunk = part[:budget]
                        self.wfile.write(chunk)
                        budget -= len(chunk)
                    self.wfile.flush()
                finally:
                    # hard-cut so the client sees EOF mid-body, exactly
                    # like the serving process dying mid-transfer
                    try:
                        self.connection.shutdown(socket.SHUT_RDWR)
                    except OSError:
                        pass
                    self.close_connection = True

        self._server = _Server(("::", 0), Handler)
        self._port = self._server.server_address[1]
        self._thread = threading.Thread(
            target=self._server.serve_forever, name="tft_ckpt_http", daemon=True
        )
        self._thread.start()

    # -- render (read lock held) --

    def _render_full(self) -> List[bytes]:
        assert self._header is not None
        out = [struct.pack("<Q", len(self._header)), self._header]
        out.extend(as_bytes(b) for b in self._buffers)
        return out

    def _render_metadata(self) -> List[bytes]:
        return [pickle.dumps((self._header, self._groups))]

    def _render_chunk(self, i: int) -> List[bytes]:
        return [as_bytes(self._buffers[j]) for j in self._groups[i]]

    def _render_stripemeta(self) -> List[bytes]:
        """Everything a healer needs to plan + verify a striped fetch
        from THIS source: the header (treedef + leaf infos), the buffer
        byte layout, the staging token, the staged tree digest (None when
        digests are disabled) and the native blob port (None when the
        bulk path is HTTP-only)."""
        blob = self._blob
        meta = {
            "step": self._step,
            "header": self._header,
            "sizes": list(self._sizes),
            "total": self._total,
            "tree_digest": self._tree_digest,
            "token": self._token,
            "blob_port": getattr(blob, "port", None),
            "stage": dict(self._stage_stats),
        }
        return [pickle.dumps(meta)]

    def _render_range(self, offset: int, length: int) -> List[bytes]:
        if offset < 0 or length <= 0 or offset + length > self._total:
            raise ValueError(
                f"bad range [{offset}, {offset + length}) of {self._total}"
            )
        return list(
            slice_buffers(self._buffers, self._sizes, offset, length)
        )

    def _render_delta(self, since_step: int, healer_digest: str) -> List[bytes]:
        """Differential response: only the buffers that changed since the
        healer's last committed step — or a loud ``{"mode": "full"}``
        refusal whenever a delta is not provably sound (no trail entry
        for that step, digest mismatch, digests disabled)."""
        assert self._header is not None
        trail = self.commit_trail
        built = None
        if trail is not None and self._digests is not None:
            built = delta_mod.build_delta(
                self._header,
                self._buffers,
                self._digests,
                trail.get(since_step),
                healer_digest,
            )
        if built is None:
            return delta_mod.pack_delta({"mode": "full"}, [])
        manifest, changed = built
        return delta_mod.pack_delta(manifest, changed)

    # -- CheckpointTransport --

    def metadata(self) -> str:
        return f"http://{self._hostname}:{self._port}"

    def _await_window(self, bounded: bool) -> None:
        """Take the read lock for one GET. Unbounded requests wait the
        transfer timeout. The striped-heal endpoints give a source that is
        not staging only :func:`_heal_meta_timeout_s` to start; once it is
        staging they wait for it like everyone else. Raises TimeoutError."""
        if not bounded:
            self._lock.r_acquire()
            return
        start = time.monotonic()
        brief = _heal_meta_timeout_s()
        full = max(brief, self._timeout.total_seconds())
        while True:
            waited = time.monotonic() - start
            limit = full if self._staging else brief
            if waited >= limit:
                raise TimeoutError(f"no serving window after {waited:.1f}s")
            try:
                # short slices: staging may begin while this request waits
                self._lock.r_acquire(timeout=min(0.25, limit - waited))
                return
            except TimeoutError:
                continue

    def send_checkpoint(
        self, dst_ranks: List[int], step: int, state_dict: T, timeout: timedelta
    ) -> None:
        # reclaim the write lock if a previous window is still open (e.g. a
        # step aborted before should_commit ran disallow_checkpoint), so
        # staging never races active GET streams
        self.disallow_checkpoint()
        self._staging = True
        try:
            self._stage(dst_ranks, step, state_dict)
        finally:
            self._staging = False

    def _stage(self, dst_ranks: List[int], step: int, state_dict: T) -> None:
        t0 = time.perf_counter()
        # leaf by leaf: a leaf is hashed while the next ones land
        # (docs/heal_plane.md "Staging")
        flat = Flattening(state_dict)
        buffers = flat.landed
        workers, digest_s = 1, 0.0
        trail = self.commit_trail if _heal_digest_enabled() else None

        def hashed(landed_or_landing) -> List[str]:
            nonlocal workers, digest_s
            workers = delta_mod.digest_workers(flat.nbytes, flat.nbuffers)
            ts, waited = time.perf_counter(), flat.wait_s
            out = delta_mod.leaf_digests(landed_or_landing, workers=workers)
            # the hashing this thread saw: what it did not spend waiting
            # for bytes to land
            digest_s = time.perf_counter() - ts - (flat.wait_s - waited)
            if trail is not None:
                trail.record(step, buffers, digests=out)
            return out

        ent = trail.get(step) if trail is not None else None
        digests: Optional[List[str]] = None
        if _heal_digest_enabled() and ent is None:
            # each leaf is hashed as it lands, on a few threads for a
            # state worth them, so the window opens when the last digest
            # is in — not a pass over the whole tree after the last byte
            digests = hashed(flat.buffers())
        else:
            for _ in flat.buffers():
                pass
            if ent is not None:
                # the Manager records the trail from the SAME state at the
                # step boundary; reuse its digests instead of re-hashing
                same = ent["sizes"] == [int(b.nbytes) for b in buffers]
                digests = list(ent["leaves"]) if same else hashed(buffers)
        header = flat.header
        self._header = header
        self._buffers = buffers
        self._sizes = [int(b.nbytes) for b in buffers]
        self._total = sum(self._sizes)
        self._digests = digests
        self._tree_digest = (
            delta_mod.tree_digest(digests) if digests is not None else None
        )
        nchunks = min(self._num_chunks, len(buffers)) if self._num_chunks else 0
        self._groups = (
            assign_chunk_groups(self._sizes, nchunks) if nchunks else []
        )
        self._step = step
        self._token = _next_token()
        self._stage_blob()
        stage_s = time.perf_counter() - t0
        # what the window cost its source, by stage: on the event, and in
        # /stripemeta so that a healer's heal_stats can say why it waited
        self._stage_stats = {
            "stage_s": round(stage_s, 4),
            "d2h_s": round(flat.wait_s, 4),
            "digest_s": round(digest_s, 4),
            "digest_workers": workers,
        }
        nbytes = len(header) + self._total
        telemetry.record_checkpoint("stage", nbytes, stage_s, "http")
        telemetry.LEDGER.record_heal_stage("stage", stage_s)
        telemetry.emit(
            "checkpoint_send",
            transport="http",
            dst_ranks=list(dst_ranks),
            step=step,
            bytes=nbytes,
            **self._stage_stats,
        )
        self._lock.w_release()  # open the serving window
        self._allowed = True

    def _stage_blob(self) -> None:
        """Stage the flattened buffers on the native blob plane (bulk
        heal bytes, GIL-free). Best-effort: any failure falls back to the
        HTTP range endpoint — the stripemeta simply advertises no port."""
        if not _heal_native_enabled() or self._blob_failed:
            return
        try:
            if self._blob is None:
                from torchft_tpu import _native

                self._blob = _native.BlobServer()
            self._blob.stage(
                [b.ctypes.data for b in self._buffers],
                self._sizes,
                self._token,
            )
        except Exception as e:  # noqa: BLE001 — HTTP fallback stays correct
            logger.warning("native blob staging unavailable: %s", e)
            self._blob = None
            self._blob_failed = True

    def disallow_checkpoint(self) -> None:
        if self._allowed:
            self._lock.w_acquire()
            self._allowed = False
        if self._blob is not None:
            # returns once no in-flight native serve still reads the
            # staged buffers, so the next staging may drop them
            self._blob.unstage()

    # -- single-source receive (reference path) --

    def _fetch_full(self, base: str, secs: float, step: int) -> T:
        t0 = time.perf_counter()
        with _traced_urlopen(f"{base}/full", timeout=secs) as resp:
            from torchft_tpu.checkpointing.serialization import load_state

            state = load_state(resp)
            nbytes = int(resp.headers.get("Content-Length") or 0)
        self._record_recv(nbytes, time.perf_counter() - t0, step)
        return state

    def _record_recv(self, nbytes: int, seconds: float, step: int) -> None:
        self.last_recv_bytes = nbytes
        telemetry.record_checkpoint("recv", nbytes, seconds, "http")
        telemetry.emit(
            "checkpoint_recv",
            transport="http",
            step=step,
            bytes=nbytes,
            duration_s=round(seconds, 4),
        )

    def recv_checkpoint(
        self, src_rank: int, metadata: str, step: int, timeout: timedelta
    ) -> T:
        from torchft_tpu.faultinject.core import fault_point

        fault_point("ckpt.recv", match=str(step), step=step)
        base = f"{metadata}/checkpoint/{step}"
        secs = timeout.total_seconds()
        if self._num_chunks == 0:
            return self._fetch_full(base, secs, step)

        t0 = time.perf_counter()
        with _traced_urlopen(f"{base}/metadata", timeout=secs) as resp:
            header, groups = pickle.loads(resp.read())
        if not groups:
            # sender staged unchunked (its num_chunks=0 wins over ours)
            return self._fetch_full(base, secs, step)
        _, infos = pickle.loads(header)
        from torchft_tpu.checkpointing.serialization import buffer_sizes

        sizes = buffer_sizes(infos)
        buffers: List[Optional[np.ndarray]] = [None] * len(sizes)

        def fetch(ci: int) -> None:
            with _traced_urlopen(f"{base}/chunk_{ci}", timeout=secs) as r:
                for j in groups[ci]:
                    nbytes = sizes[j]
                    raw = r.read(nbytes)
                    if len(raw) != nbytes:
                        raise EOFError(f"truncated chunk {ci}")
                    buffers[j] = np.frombuffer(raw, dtype=np.uint8)

        with ThreadPoolExecutor(max_workers=len(groups) or 1) as pool:
            for f in [pool.submit(fetch, ci) for ci in range(len(groups))]:
                f.result()
        self._record_recv(
            len(header) + sum(sizes), time.perf_counter() - t0, step
        )
        return unflatten_state(header, [b for b in buffers if b is not None])

    # -- striped multi-source receive (docs/heal_plane.md) --

    def recv_checkpoint_multi(
        self,
        sources: List[str],
        step: int,
        timeout: timedelta,
        since_step: Optional[int] = None,
        own: Optional[Tuple[List[np.ndarray], str]] = None,
        header_cb: Optional[Callable[[bytes], None]] = None,
    ) -> T:
        """Fetch ``step``'s state dict striped across ``sources`` (each a
        transport metadata URL; ``sources[0]`` is the lighthouse-named
        primary). With ``since_step``/``own`` the differential fast path
        is tried first (``own`` = this replica's flattened buffers + tree
        digest at ``since_step``). ``header_cb`` fires as soon as the
        header is known — before any bulk bytes land — so the caller can
        overlap jit compile/warmup with the transfer."""
        from torchft_tpu.faultinject.core import fault_point

        fault_point("ckpt.recv", match=str(step), step=step)
        assert sources, "need at least one heal source"
        secs = timeout.total_seconds()
        deadline = time.monotonic() + secs
        t_start = time.perf_counter()
        stats: Dict[str, Any] = {
            "mode": "striped",
            "sources": {},
            "stages": {},
        }
        self.last_heal_stats = stats

        # ---- differential fast path -----------------------------------
        if since_step is not None and own is not None:
            state = self._try_delta(
                sources[0], step, since_step, own, secs, stats,
                header_cb=header_cb,
            )
            if state is not None:
                self._record_recv(
                    int(stats["bytes"]), time.perf_counter() - t_start, step
                )
                return state

        # ---- stripe planning ------------------------------------------
        t0 = time.perf_counter()
        sources = sources[: heal_sources_limit()]
        metas: Dict[str, Dict[str, Any]] = {}
        meta_errors: Dict[str, str] = {}

        # planning probe. A source that is not staging answers 503 within
        # _heal_meta_timeout_s; one that is staging answers when its window
        # opens, which for a multi-GB state is well past that. The primary
        # (lighthouse-named, so alive) gets the whole deadline; a secondary
        # stays bounded so that a blackholed host cannot consume the
        # deadline before a single range moves
        meta_secs = min(secs, _heal_meta_timeout_s() + 5.0)

        def fetch_meta(src: str) -> None:
            try:
                with _traced_urlopen(
                    f"{src}/checkpoint/{step}/stripemeta",
                    timeout=secs if src == sources[0] else meta_secs,
                ) as r:
                    metas[src] = pickle.loads(r.read())
            except Exception as e:  # noqa: BLE001 — a dead source is dropped
                meta_errors[src] = str(e)

        if len(sources) == 1:
            fetch_meta(sources[0])
        else:
            with ThreadPoolExecutor(
                max_workers=len(sources), thread_name_prefix="tft_heal_meta"
            ) as pool:
                list(pool.map(fetch_meta, sources))
        alive = [s for s in sources if s in metas]
        if not alive:
            raise ConnectionError(
                f"no heal source reachable for step {step}: {meta_errors}"
            )
        primary = alive[0]
        pmeta = metas[primary]
        if pmeta.get("tree_digest"):
            # stripe only across sources provably staging the SAME bytes;
            # anything else (diverged LocalSGD inner state, a source that
            # re-staged a different step mid-plan) degrades to fewer
            # sources rather than ever mixing two states
            active = []
            for s in alive:
                if metas[s].get("tree_digest") == pmeta["tree_digest"]:
                    active.append(s)
                else:
                    logger.warning(
                        "heal source %s staged a different tree than the "
                        "primary (digest %s vs %s, %d vs %d bytes, header "
                        "%d vs %d) — excluded from striping",
                        s,
                        metas[s].get("tree_digest"),
                        pmeta["tree_digest"],
                        metas[s].get("total"),
                        pmeta.get("total"),
                        len(metas[s].get("header") or b""),
                        len(pmeta.get("header") or b""),
                    )
        else:
            active = [primary]
        header: bytes = pmeta["header"]
        sizes: List[int] = list(pmeta["sizes"])
        total: int = int(pmeta["total"])
        telemetry.LEDGER.record_heal_stage(
            "meta", time.perf_counter() - t0
        )
        stats["stages"]["meta_s"] = round(time.perf_counter() - t0, 4)
        if pmeta.get("stage"):
            # what the window cost the source (a source of an older
            # build says nothing)
            stats["source_stage"] = dict(pmeta["stage"])

        if header_cb is not None:
            try:
                header_cb(header)
            except Exception:  # noqa: BLE001 — warmup is best-effort
                logger.exception("heal header callback failed")

        # ---- striped fetch (work queue: a dead source's pending ranges
        # re-stripe onto the survivors) ---------------------------------
        t0 = time.perf_counter()
        # never zeroed: a range fills its pages or the heal is refused
        # below (done_bytes), so the first touch of each page happens in
        # the fetch threads, spread over the ranges — not in one memset
        # pass over the whole tree that the fetch then overwrites
        dest = _alloc_dest(total)
        mv = memoryview(dest)
        alloc_s = time.perf_counter() - t0
        # as many ranges a source as its bytes are worth, one stream each
        per_source = heal_stripes_per_source(-(-total // len(active)))
        ranges = stripe_ranges(total, len(active) * per_source)
        queue: deque = deque(ranges)
        qlock = threading.Lock()
        failures: Dict[str, str] = {}
        done_bytes = [0]
        in_flight = [0, 0]  # ranges being fetched now, and the most at once
        src_started: Dict[str, float] = {}  # a source's first range began

        def fetch_range(src: str, off: int, length: int) -> None:
            left = max(0.1, deadline - time.monotonic())
            meta = metas[src]
            view = mv[off : off + length]
            if (
                meta.get("blob_port")
                and _heal_native_enabled()
                and not self._blob_failed
            ):
                from torchft_tpu import _native

                host = urllib.parse.urlsplit(src).hostname or "localhost"
                _native.blob_fetch(
                    host,
                    int(meta["blob_port"]),
                    int(meta["token"]),
                    off,
                    length,
                    view,
                    timeout_ms=int(left * 1000),
                )
            else:
                url = f"{src}/checkpoint/{step}/range_{off}_{length}"
                with _traced_urlopen(url, timeout=left) as r:
                    got = 0
                    while got < length:
                        k = r.readinto(view[got:])
                        if not k:
                            raise EOFError(
                                f"short range read {got}/{length} from {src}"
                            )
                        got += k

        def worker(src: str) -> None:
            with qlock:
                srcstat = stats["sources"].setdefault(
                    src, {"bytes": 0, "seconds": 0.0, "ranges": 0}
                )
            while True:
                with qlock:
                    # a source one stream saw fail takes no further range
                    if not queue or src in failures:
                        return
                    off, length = queue.popleft()
                    in_flight[0] += 1
                    in_flight[1] = max(in_flight)
                    src_started.setdefault(src, time.perf_counter())
                try:
                    fetch_range(src, off, length)
                except Exception as e:  # noqa: BLE001 — re-stripe and retire
                    with qlock:
                        in_flight[0] -= 1
                        queue.append((off, length))
                        failures[src] = str(e)
                    logger.warning(
                        "heal source %s failed mid-stripe (%s); "
                        "re-striping its ranges over survivors",
                        src,
                        e,
                    )
                    return
                te = time.perf_counter()
                with qlock:
                    in_flight[0] -= 1
                    done_bytes[0] += length
                    srcstat["bytes"] += length
                    srcstat["ranges"] += 1
                    # wall seconds from the source's first range to its
                    # last, so the rate is the source's as this healer saw
                    # it, whatever the number of streams
                    srcstat["seconds"] = max(
                        srcstat["seconds"], te - src_started[src]
                    )

        # re-striping loop: a worker that observed an empty queue exits,
        # but a FAILING worker may re-queue its in-flight range after
        # that — so keep relaunching workers for the surviving sources
        # until the queue drains or every source has failed (each pass
        # either finishes the queue or retires at least one source, so
        # the loop is bounded by len(active)). One worker per range in
        # flight: a source serves as many connections as it has ranges
        # outstanding (a thread a connection on the blob plane and on
        # the HTTP fallback alike)
        t_fetch = time.perf_counter()
        while queue and len(failures) < len(active):
            survivors = [s for s in active if s not in failures]
            streams = min(per_source, -(-len(queue) // len(survivors)))
            threads = [
                threading.Thread(
                    target=worker, args=(s,), name=f"tft_heal_stripe{i}.{j}"
                )
                for i, s in enumerate(survivors)
                for j in range(streams)
            ]
            for th in threads:
                th.start()
            for th in threads:
                th.join()
        fetch_s = time.perf_counter() - t_fetch
        if done_bytes[0] != total:
            raise ConnectionError(
                f"striped heal incomplete: {done_bytes[0]}/{total} bytes "
                f"(source failures: {failures or meta_errors})"
            )
        recv_s = time.perf_counter() - t0
        telemetry.LEDGER.record_heal_stage("recv", recv_s)
        for st in stats["sources"].values():
            st["gb_per_sec"] = (
                round(st["bytes"] / st["seconds"] / 1e9, 3)
                if st["seconds"] > 0
                else 0.0
            )
        stats["stages"]["alloc_s"] = round(alloc_s, 4)
        stats["stages"]["fetch_s"] = round(fetch_s, 4)
        stats["stages"]["recv_s"] = round(recv_s, 4)
        stats["streams"] = in_flight[1]
        stats["nsources"] = len(active) - len(failures)
        stats["failures"] = failures

        # ---- decode ----------------------------------------------------
        t0 = time.perf_counter()
        buffers: List[np.ndarray] = []
        off = 0
        for s in sizes:
            buffers.append(
                np.frombuffer(dest, dtype=np.uint8, count=s, offset=off)
            )
            off += s
        state = unflatten_state(header, buffers)
        decode_s = time.perf_counter() - t0
        telemetry.LEDGER.record_heal_stage("decode", decode_s)
        stats["stages"]["decode_s"] = round(decode_s, 4)
        stats["bytes"] = len(header) + total
        self._record_recv(
            len(header) + total, time.perf_counter() - t_start, step
        )
        return state

    def _try_delta(
        self,
        primary: str,
        step: int,
        since_step: int,
        own: Tuple[List[np.ndarray], str],
        secs: float,
        stats: Dict[str, Any],
        header_cb: Optional[Callable[[bytes], None]] = None,
    ) -> Optional[T]:
        """Differential attempt against the primary source; None on any
        refusal/failure (the caller proceeds with the striped full path)."""
        own_buffers, own_digest = own
        t0 = time.perf_counter()
        try:
            url = (
                f"{primary}/checkpoint/{step}/delta_{since_step}_{own_digest}"
            )
            with _traced_urlopen(url, timeout=secs) as r:
                body = r.read()
            manifest, payload = delta_mod.unpack_delta(body)
            if manifest.get("mode") != "delta":
                return None
            if header_cb is not None:
                # the heal/compile overlap applies to delta heals too —
                # fire the warmup before the (decode) apply
                try:
                    header_cb(manifest["header"])
                except Exception:  # noqa: BLE001 — warmup is best-effort
                    logger.exception("heal header callback failed")
            header, buffers = delta_mod.apply_delta(
                manifest, payload, own_buffers
            )
            state = unflatten_state(header, buffers)
        except Exception as e:  # noqa: BLE001 — degrade to the full path
            logger.warning(
                "differential heal unavailable (%s); falling back to full",
                e,
            )
            return None
        dur = time.perf_counter() - t0
        telemetry.LEDGER.record_heal_stage("recv", dur)
        stats["mode"] = "delta"
        stats["bytes"] = len(body)
        stats["delta"] = {
            "since_step": since_step,
            "changed": len(manifest["changed"]),
            "leaves": len(own_buffers),
            "bytes": len(body),
            "seconds": round(dur, 4),
        }
        stats["sources"][primary] = {
            "bytes": len(body),
            "seconds": round(dur, 4),
            "ranges": 1,
            "gb_per_sec": round(len(body) / max(dur, 1e-9) / 1e9, 3),
        }
        return state

    def shutdown(self, wait: bool = True) -> None:
        self._server.shutdown()
        self._server.server_close()
        if self._blob is not None:
            self._blob.close()
        if wait:
            self._thread.join(timeout=5)
