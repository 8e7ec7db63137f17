"""Manager — the per-replica fault-tolerance runtime.

Re-implements the reference's Manager state machine
(/root/reference/torchft/manager.py:87-728) for a JAX data plane:

* ``start_quorum`` kicks off an async quorum on a worker thread so the
  quorum RPC overlaps the forward pass (manager.py:366-416).
* ``allreduce`` averages host gradient buffers across replica groups via
  the reconfigurable collectives; healing/spare replicas contribute zeros
  and the division is by ``num_participants()``, not world size
  (manager.py:243-304).
* ``should_commit`` is the per-step commit barrier: drain pending work,
  apply any staged recovery state, vote through the manager server; the
  optimizer steps only on a unanimous True (manager.py:546-599).

TPU framing: within a replica group, parallelism is a jax Mesh and XLA's
own ICI collectives (torchft_tpu.parallel); the Manager governs only the
*cross-replica-group* axis, which lives outside jit on host buffers so the
compiled train step never recompiles when membership changes.
"""

from __future__ import annotations

import concurrent.futures
import logging
import os
import socket
import threading
import uuid
from concurrent.futures import ThreadPoolExecutor
from datetime import timedelta
from enum import Enum
from typing import Any, Callable, Dict, List, Optional, Tuple, TypeVar, cast

import numpy as np

from torchft_tpu import telemetry
from torchft_tpu.checkpointing.http_transport import HTTPTransport
from torchft_tpu.checkpointing.transport import CheckpointTransport
from torchft_tpu.collectives import Collectives, ReduceOp, fill_from_sources
from torchft_tpu.coordination import ManagerClient, ManagerServer
from torchft_tpu.faultinject.core import fault_point
from torchft_tpu.futures import Future, future_timeout
from torchft_tpu.profiling import StepTimer
from torchft_tpu.store import StoreClient

T = TypeVar("T")

logger = logging.getLogger(__name__)

MANAGER_ADDR_KEY: str = "manager/addr"
REPLICA_ID_KEY: str = "manager/replica_id"
MANAGER_PORT_ENV: str = "TORCHFT_MANAGER_PORT"
LIGHTHOUSE_ENV: str = "TORCHFT_LIGHTHOUSE"
STORE_ADDR_ENV: str = "TORCHFT_STORE_ADDR"

__all__ = ["Manager", "WorldSizeMode"]


class WorldSizeMode(Enum):
    """Numerics policy when replica groups die (manager.py:55-70).

    DYNAMIC: batch size scales with the live group count — gradients divide
    by the *current* participant count.
    FIXED_WITH_SPARES: world size is pinned at ``min_replica_size``; extra
    groups are demoted to hot spares that contribute zeros, so the divisor
    (and effective batch size) never changes.
    """

    DYNAMIC = 0
    FIXED_WITH_SPARES = 1


_DIV_JIT = None


def _divide_tree(arrays: List[Any], n: int) -> List[Any]:
    """One jitted kernel dividing every array by ``n`` (device path of
    gradient normalization). ``n`` is a traced scalar so membership changes
    never recompile; the jit caches per list structure/shapes."""
    global _DIV_JIT
    import jax

    if _DIV_JIT is None:

        def f(xs, n):
            return [(x / n).astype(x.dtype) for x in xs]

        _DIV_JIT = jax.jit(f)
    return _DIV_JIT(arrays, np.float32(n))


class _ManagerLogger:
    """Prefixes every line with ``[replica_id/rank - step N]``
    (manager.py:709-728)."""

    def __init__(self, manager: "Manager", replica_id: str, rank: int) -> None:
        self._logger = logging.getLogger("torchft_tpu.manager")
        self._replica_id = replica_id
        self._rank = rank
        self._manager = manager

    def _prefix(self) -> str:
        return f"[{self._replica_id}/{self._rank} - step {self._manager.current_step()}]"

    def info(self, msg: str) -> None:
        self._logger.info(f"{self._prefix()} {msg}")

    def warn(self, msg: str) -> None:
        self._logger.warning(f"{self._prefix()} {msg}")

    def exception(self, msg: str) -> None:
        self._logger.exception(f"{self._prefix()} {msg}")


class Manager:
    """Fault-tolerance manager for one rank of one replica group."""

    def __init__(
        self,
        collectives: Collectives,
        load_state_dict: Optional[Callable[[T], None]],
        state_dict: Optional[Callable[[], T]],
        min_replica_size: int,
        use_async_quorum: bool = True,
        timeout: timedelta = timedelta(seconds=60),
        quorum_timeout: timedelta = timedelta(seconds=60),
        connect_timeout: timedelta = timedelta(seconds=60),
        rank: Optional[int] = None,
        world_size: Optional[int] = None,
        world_size_mode: WorldSizeMode = WorldSizeMode.DYNAMIC,
        store_addr: Optional[str] = None,
        lighthouse_addr: Optional[str] = None,
        replica_id: Optional[str] = None,
        port: Optional[int] = None,
        hostname: Optional[str] = None,
        heartbeat_interval: timedelta = timedelta(milliseconds=100),
        checkpoint_transport: Optional[CheckpointTransport[Dict[str, T]]] = None,
    ) -> None:
        """
        Args:
            collectives: the reconfigurable cross-replica-group collectives
                (unconfigured; the Manager configures it each quorum change)
            load_state_dict / state_dict: user snapshot/restore callbacks for
                live recovery (set later via :meth:`set_state_dict_fns` if
                the model is built after the manager)
            min_replica_size: minimum replica groups for a step to commit
            use_async_quorum: overlap the quorum RPC with the forward pass
            timeout: default deadline for collectives, commit votes, and
                checkpoint transfers
            quorum_timeout: deadline for quorum formation — must exceed the
                interval between syncs (≈1h for infrequent LocalSGD syncs)
            rank / world_size: this rank within the replica group (env RANK /
                WORLD_SIZE fallback)
            store_addr: ``host:port`` of the replica group's KV store
                (TORCHFT_STORE_ADDR fallback)
            lighthouse_addr: rank-0 only; TORCHFT_LIGHTHOUSE fallback
            replica_id: rank-0 only; a uuid4 suffix is always appended so
                restarted groups are distinct lighthouse members
            port: rank-0 manager server port (TORCHFT_MANAGER_PORT fallback,
                else ephemeral)
        """
        self._load_state_dict = load_state_dict
        self._user_state_dict = state_dict
        # unguarded-ok: quorum-thread handoff — staged by the quorum
        #   thread during heal, applied on the main thread strictly after
        #   wait_quorum() (asserted in _apply_pending_state_dict)
        self._pending_state_dict: Optional[Dict[str, object]] = None
        self._use_async_quorum = use_async_quorum
        self._timeout = timeout
        self._quorum_timeout = quorum_timeout
        self._connect_timeout = connect_timeout
        self._world_size_mode = world_size_mode
        self._min_replica_size = min_replica_size

        store_addr = store_addr or os.environ[STORE_ADDR_ENV]
        self._rank: int = rank if rank is not None else int(os.environ["RANK"])
        rank = self._rank
        world_size = world_size or int(os.environ["WORLD_SIZE"])

        if checkpoint_transport is None:
            checkpoint_transport = HTTPTransport(timeout=timeout, num_chunks=0)
        self._checkpoint_transport: CheckpointTransport[Dict[str, T]] = (
            checkpoint_transport
        )

        self._executor = ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="async_quorum"
        )
        self._quorum_future: Optional[concurrent.futures.Future] = None  # guarded-by: _qf_lock
        # guards _quorum_future replacement: the death watch may submit a
        # premature re-quorum from its monitor thread (see _on_peer_death)
        self._qf_lock = threading.Lock()
        self._shutting_down = False
        self._last_quorum_args: tuple = (True, False, None)

        self._store = StoreClient(store_addr, connect_timeout=connect_timeout)
        self._collectives = collectives
        self._manager: Optional[ManagerServer] = None

        self._lighthouse_addr: Optional[str] = None
        if rank == 0:
            if port is None:
                port = int(os.environ.get(MANAGER_PORT_ENV, 0))
            lighthouse_addr = lighthouse_addr or os.environ[LIGHTHOUSE_ENV]
            self._lighthouse_addr = lighthouse_addr
            replica_id = (replica_id or "") + str(uuid.uuid4())
            self._manager = ManagerServer(
                replica_id=replica_id,
                lighthouse_addr=lighthouse_addr,
                hostname=hostname or socket.gethostname(),
                bind=f"[::]:{port}",
                store_addr=store_addr,
                world_size=world_size,
                heartbeat_interval=heartbeat_interval,
                connect_timeout=connect_timeout,
            )
            self._store.set(MANAGER_ADDR_KEY, self._manager.address())
            self._store.set(REPLICA_ID_KEY, replica_id)

        addr = self._store.get(MANAGER_ADDR_KEY).decode()
        self._manager_addr = addr
        self._client = ManagerClient(addr, connect_timeout=connect_timeout)
        replica_id = self._store.get(REPLICA_ID_KEY).decode()
        self._logger = _ManagerLogger(self, replica_id or "", rank)
        self._replica_id = replica_id or ""

        # unguarded-ok: quorum-thread handoff — the caller's wait_quorum()
        #   barrier (and the commit drain) orders the quorum thread's heal
        #   write of _step against main-thread reads/increments
        self._step = 0
        self._step_label = 0  # physical-step coordinate (see start_quorum)
        self._quorum_id = -1
        # _participant_ids/_evicted cross three threads: the quorum thread
        # replaces membership each epoch, while the death-watch monitor and
        # main-thread error paths report evictions. The lock closes the
        # check-then-add race (a victim double-reported = a wasted
        # lighthouse liveness probe + duplicate trail records) and keeps
        # attribution reading a consistent (ids, evicted) pair
        # [found by the analysis gate: unguarded-shared-write].
        self._evict_lock = threading.Lock()
        self._participant_ids: List[str] = []  # guarded-by: _evict_lock
        self._evicted: set = set()  # guarded-by: _evict_lock
        # (plane_generation, participant_ids) armed for the death watch
        self._death_watch_snapshot: Optional[Tuple[int, List[str]]] = None
        # unguarded-ok: issue-time latch — written by the main thread and
        #   op-callback/quorum threads, read at the commit barrier after
        #   the pending-work drain (last-write-wins is the latch contract)
        self._commit_failures = 0  # pending data-plane flush request
        # unguarded-ok: error latch — any thread may latch, the commit
        #   barrier reads after draining pending work; a racing latch only
        #   changes WHICH error aborts the step, never whether it aborts
        self._errored: Optional[Exception] = None
        self._errored_epoch = -1  # quorum_id whose plane produced _errored
        self._step_epochs: set = set()  # quorum_ids this step's ops ran on
        self._step_n: Optional[int] = None  # issue-time participant count

        # Active failure detection: the data plane's sockets learn about a
        # dead peer (FIN/RST) within milliseconds — long before the next
        # collective op would trip over them. Wire that signal back so the
        # eviction + re-quorum overlap the doomed step instead of starting
        # at the next step boundary (the "<1 step" recovery envelope).
        if hasattr(collectives, "set_death_watch"):
            collectives.set_death_watch(self._on_peer_death)
        # unguarded-ok: quorum-thread handoff — wait_quorum() (async mode)
        #   or the synchronous start_quorum path is the happens-before
        #   barrier between the quorum thread's writes and main reads
        self._healing = False
        # unguarded-ok: quorum-thread handoff — same barrier as _healing
        self._group_healing = False
        self._pending_work: List[Future] = []
        # unguarded-ok: quorum-thread handoff — heal-path restore on the
        #   quorum thread, increments on the main thread post-drain
        self._batches_committed = 0

        # rolling steps/sec with quorum/heal steps tagged as outliers;
        # should_commit ticks it, so its outlier durations are the
        # recorded per-step recovery cost (telemetry step_outlier events)
        self.step_timer = StepTimer()
        # step-anatomy ledger (ISSUE 8): should_commit ticks the process
        # ledger so every step's wall clock is decomposed into phases;
        # attaching the timer exports its tagged-outlier digest through
        # anatomy summaries and the flight-recorder dumps
        telemetry.LEDGER.attach_timer(self.step_timer)
        # burn-rate SLO evaluators (telemetry/slo.py; env-gated — zero
        # cost unless TORCHFT_SLO_STEP_S / TORCHFT_SLO_REJOIN_S are set);
        # the latch rides the telemetry piggyback to the lighthouse
        from torchft_tpu.telemetry.slo import SloManager

        self._slo = SloManager()
        # unguarded-ok: quorum-thread handoff — set at heal begin on the
        #   quorum thread, consumed at the next committed should_commit
        #   on the main thread (wait_quorum is the barrier)
        self._rejoin_t0: Optional[float] = None
        # opt-in fleet straggler monitor: any Manager that knows the
        # lighthouse address can host the detector (one per fleet is
        # enough; the faultmatrix runner runs its own)
        self._fleet_monitor = None
        if (
            os.environ.get("TORCHFT_STRAGGLER_MONITOR", "0") == "1"
            and self._lighthouse_addr is not None
            and self._rank == 0
        ):
            from torchft_tpu.telemetry.slo import FleetMonitor

            self._fleet_monitor = FleetMonitor(self._lighthouse_addr).start()

        self._participating_rank: Optional[int] = None
        self._participating_world_size: int = 0

        # Differential heal (docs/heal_plane.md, TORCHFT_HEAL_DIFF=1):
        # a bounded per-leaf digest trail over recent committed steps.
        # Recorded on the MAIN thread at each step's start_quorum (the
        # state there is exactly the committed state at current_step);
        # the serving side's delta endpoint and this replica's own heal
        # request both read it. None when the feature is off — the
        # per-step flatten+digest is not free.
        from torchft_tpu.checkpointing import delta as _delta

        self._heal_trail = _delta.CommitTrail() if _delta.diff_enabled() else None
        if self._heal_trail is not None and hasattr(
            self._checkpoint_transport, "commit_trail"
        ):
            self._checkpoint_transport.commit_trail = self._heal_trail
        # heal-recv/compile overlap: a user-registered warmup callback,
        # fired on a daemon thread with the incoming state's spec tree as
        # soon as the transfer header is known (set_heal_warmup)
        self._heal_warmup: Optional[Callable[[Any], None]] = None

        # Hang forensics (PR 2): SIGUSR2 dumps the collective flight
        # recorder (best-effort — only possible from the main thread), and
        # the step watchdog turns a silently wedged step into a
        # watchdog_stall event + flight dump + a stuck flag the lighthouse
        # dashboard surfaces per replica.
        telemetry.install_sigusr2()
        # on_stall pushes the stuck report DIRECTLY to the lighthouse:
        # the regular piggyback rides quorum RPCs, which a wedged step
        # never issues — exactly the scenario the stuck flag exists for
        self._watchdog = telemetry.StepWatchdog(on_stall=self._on_stall)
        # Diagnosis plane (ISSUE 12): the Python stack sampler runs
        # always-on at TORCHFT_PROF_HZ (0 disarms; the native sampler
        # arms itself at thread registration), and — when
        # TORCHFT_DIAG_DIR is set — a DiagnosisEngine turns latch events
        # (straggler / SLO / watchdog / divergence)
        # into bounded deep-capture bundles, announced on the piggyback.
        from torchft_tpu.telemetry.diagnosis import DiagnosisEngine, diag_dir
        from torchft_tpu.telemetry.profiler import PROFILER

        PROFILER.ensure_started()
        self._diagnosis: Optional[DiagnosisEngine] = None
        if diag_dir():
            self._diagnosis = DiagnosisEngine(
                replica_id=self._replica_id,
                lighthouse_addr=self._lighthouse_addr,
            ).install()
        self._last_heal_ts = 0.0
        telemetry.TRACER.set_context(
            replica_id=self._replica_id, step=self._step, quorum_epoch=-1
        )
        # crash-durable black box (docs/observability.md "Forensics"):
        # keep its (replica, step, epoch) context in lockstep with the
        # tracer's so every mirrored record carries the clock-sync-free
        # coordinates the postmortem merge orders by
        telemetry.BLACKBOX.set_context(
            replica_id=self._replica_id, step=self._step, quorum_epoch=-1
        )

        # Divergence sentinel (docs/observability.md): digest the step's
        # post-reduce state and let the lighthouse compare it within the
        # (epoch, step) cohort at the commit boundary. The fence
        # (TORCHFT_DIVERGENCE_FENCE=1, implies the sentinel) additionally
        # vetoes the commit on a mismatch — all groups must agree on the
        # fence. Off by default: hashing every reduced buffer is not free.
        self._divergence_fence = (
            os.environ.get("TORCHFT_DIVERGENCE_FENCE", "0") == "1"
        )
        self._divergence_sentinel = self._divergence_fence or (
            os.environ.get("TORCHFT_DIVERGENCE_SENTINEL", "0") == "1"
        )
        # ordered per-op tree digests of this step's reduced outputs;
        # appended on the op-callback thread (ops resolve in issue order
        # — the op thread is serial), folded + cleared at should_commit
        self._step_digests: List[str] = []
        self._divergence_latched = False

    def _on_stall(self, step: int, elapsed_s: float, threshold_s: float) -> None:
        """Watchdog stall callback (watchdog thread): ship the stuck
        report out-of-band. A wedged step sends no quorum RPCs, so the
        normal piggyback can't carry the flag; push one heartbeat with
        the telemetry payload straight to the lighthouse instead
        (rank 0 only — it knows the lighthouse address). Best-effort and
        time-bounded: forensics must never deepen a hang. Note this adds
        no liveness signal the C++ manager's own heartbeat loop isn't
        already sending — it only attaches the telemetry."""
        if self._lighthouse_addr is None or self._shutting_down:
            return

        def _push() -> None:
            try:
                from torchft_tpu.coordination import LighthouseClient

                # out-of-band push: always the self-contained legacy
                # JSON row, never the delta encoder — this thread racing
                # the quorum path's encode (or its heartbeat arriving
                # out of order) would break the version chain; a JSON
                # row lands regardless of the chain's state, even after
                # a lighthouse restart (the ingest is format-blind)
                client = LighthouseClient(
                    self._lighthouse_addr, connect_timeout=timedelta(seconds=5)
                )
                try:
                    client.heartbeat(
                        self._replica_id,
                        timeout=timedelta(seconds=5),
                        telemetry_payload=self._telemetry_payload_json(),
                    )
                finally:
                    client.close()
            except Exception:  # noqa: BLE001 — best effort
                pass

        threading.Thread(target=_push, daemon=True, name="tft_stall_push").start()

    def _trace_id(self) -> str:
        """Trace identity for the in-flight step: (replica, step, epoch)
        are globally agreed values, so spans from different replicas with
        equal step/epoch coordinates correlate on the merged timeline.
        Uses the step's label (see start_quorum), so spans that close
        after the commit carry the same step coordinate as the commit
        event they belong to."""
        return f"{self._replica_id}:{self._step_label}:{self._quorum_id}"

    def _delta_encoder(self):
        """Lazy per-manager DeltaEncoder (ISSUE 16). One encoder per
        Manager lifetime: its random incarnation is what lets the
        lighthouse tell a respawned replica from a delta-chain
        continuation, so it must NOT be recreated across steps."""
        enc = getattr(self, "_tdelta_encoder", None)
        if enc is None:
            from torchft_tpu.telemetry.fleetdelta import DeltaEncoder

            enc = DeltaEncoder()
            self._tdelta_encoder = enc
        return enc

    def _telemetry_report(self) -> Dict[str, Any]:
        """The nested per-replica report the delta encoder flattens:
        health scalars + counters digest + anatomy + mergeable log2
        histograms + time-series samples. Keys here ARE the wire
        vocabulary the lighthouse rebuilds /cluster.json fields from."""
        from torchft_tpu.telemetry.fleetdelta import collect_hists
        from torchft_tpu.telemetry.timeseries import build_series

        report: Dict[str, Any] = {
            "step": self._step,
            "epoch": self._quorum_id,
            "stuck": bool(self._watchdog.stalled),
            "slo_breach": bool(self._slo.breached()),
            "local_step_p50_s": float(telemetry.LEDGER.local_p50() or 0.0),
            "last_heal_ts": float(self._last_heal_ts),
            "summary": telemetry.summary(),
            "anatomy": telemetry.LEDGER.summary(),
            "hist": collect_hists(),
        }
        diagnosis = getattr(self, "_diagnosis", None)
        if diagnosis is not None and diagnosis.bundle_count:
            report["diag_bundles"] = diagnosis.bundle_count
            report["diag_last"] = diagnosis.last_bundle or ""
            report["diag_dir"] = diagnosis.directory or ""
        series = build_series(
            slo_breach=bool(self._slo.breached()),
            stuck=bool(self._watchdog.stalled),
            divergence=bool(self._divergence_latched),
        )
        if series:
            report["series"] = series
        return report

    def _telemetry_payload_delta(self) -> Optional[Dict[str, Any]]:
        """Delta-encoded piggyback (ISSUE 16): the report is flattened
        and only fields changed since the lighthouse's last ack ship, so
        steady-state bytes are O(changed), not O(report). Spans ride
        OUTSIDE the blob as the lowest-priority tier: when blob + spans
        would blow the 64KiB cap the spans are requeued for a lighter
        round instead of starving the latches inside the blob."""
        import time as _time

        from torchft_tpu.telemetry.fleetdelta import max_blob_bytes

        t0 = _time.perf_counter()
        try:
            enc = self._delta_encoder()
            blob = enc.encode(self._telemetry_report())
            payload: Dict[str, Any] = {"tdelta": blob}
            telemetry.TELEMETRY_BYTES.labels(channel="piggyback").inc(
                len(blob)
            )
            spans = telemetry.TRACER.drain_chrome_fragment()
            if spans:
                if len(blob) + len(spans) <= max_blob_bytes():
                    payload["spans"] = spans
                    telemetry.TELEMETRY_BYTES.labels(channel="spans").inc(
                        len(spans)
                    )
                else:
                    # tier 3 drops first — requeue, don't lose them
                    telemetry.TRACER.requeue_last_batch()
            return payload
        except Exception:  # noqa: BLE001 — observability must not fail quorum
            return None
        finally:
            # the telemetry plane meters itself: encode+drain cost is a
            # first-class anatomy phase, so an overhead regression shows
            # up in the same percentile tables as compute/wire
            telemetry.LEDGER.record(
                "telemetry", _time.perf_counter() - t0
            )

    def _telemetry_payload(self) -> Optional[Dict[str, Any]]:
        """Compact per-replica report piggybacked on the quorum RPC:
        counters digest + recent span batch + health scalars. The manager
        server forwards it to the lighthouse for /cluster.json and the
        merged /trace. Must never fail the quorum path. Kill switch:
        ``TORCHFT_TELEMETRY_PIGGYBACK=0``. Default wire format is the
        delta encoding (telemetry/fleetdelta.py); set
        ``TORCHFT_TELEMETRY_DELTA=0`` for the legacy full-JSON payload."""
        if os.environ.get("TORCHFT_TELEMETRY_PIGGYBACK", "1") == "0":
            return None
        from torchft_tpu.telemetry.fleetdelta import delta_enabled

        if delta_enabled():
            return self._telemetry_payload_delta()
        return self._telemetry_payload_json()

    def _telemetry_payload_json(self) -> Optional[Dict[str, Any]]:
        """The legacy full-JSON payload — the ``TORCHFT_TELEMETRY_DELTA=0``
        wire format, and ALSO the out-of-band stall push's format even in
        delta mode: the push runs on its own thread, and the delta
        encoder is thread-compatible (quorum-path-only) — touching it
        here would race the quorum thread's encode, and an out-of-order
        heartbeat would break the version chain into resync round-trips
        that drop time-series samples. The lighthouse ingest is
        format-blind, so a self-contained JSON row lands regardless of
        what the delta chain is doing."""
        import json as _json

        if os.environ.get("TORCHFT_TELEMETRY_PIGGYBACK", "1") == "0":
            return None
        try:
            # step-anatomy digest + the two detector scalars (ISSUE 8):
            # the lighthouse stores the digest verbatim (spliced into
            # /cluster.json like the summary) and serves the scalars to
            # the fleet straggler detector / dashboard SLO column
            anatomy = _json.dumps(
                telemetry.LEDGER.summary(),
                separators=(",", ":"),
                default=str,
            )
            if len(anatomy) > (1 << 16):
                # the lighthouse refuses (loudly) anything past its 64KiB
                # cap; sending the oversize anyway would only burn quorum
                # bandwidth — replace with a marker so /cluster.json
                # shows WHY the digest is missing from both ends. Warn
                # once per EPISODE (the flag resets when the digest
                # shrinks back under the cap): oversize is steady-state
                # while it lasts and this path runs at step rate, but a
                # later unrelated episode must not be silent
                if not getattr(self, "_anatomy_oversize_warned", False):
                    self._anatomy_oversize_warned = True
                    self._logger.warning(
                        "anatomy digest %d bytes exceeds the 64KiB "
                        "piggyback cap; sending an oversize marker "
                        "instead (warned once per episode)",
                        len(anatomy),
                    )
                anatomy = _json.dumps({"_oversized_bytes": len(anatomy)})
            else:
                self._anatomy_oversize_warned = False
            payload = {
                "summary": _json.dumps(
                    telemetry.summary(), separators=(",", ":"), default=str
                ),
                "anatomy": anatomy,
                "local_step_p50_s": float(
                    telemetry.LEDGER.local_p50() or 0.0
                ),
                "slo_breach": bool(self._slo.breached()),
                "step": self._step,
                # quorum epoch keys this report's time-series samples
                # alongside step — the same clock-sync-free coordinates
                # every other forensic surface orders by
                "epoch": self._quorum_id,
                "stuck": bool(self._watchdog.stalled),
                "last_heal_ts": float(self._last_heal_ts),
                "spans": telemetry.TRACER.drain_chrome_fragment(),
            }
            # diagnosis-bundle availability (ISSUE 12): counts + the
            # latest bundle name ride the same piggyback; the lighthouse
            # serves the fleet index at GET /diagnosis.json (getattr:
            # the payload builder must also work on partially-built
            # Managers — tests drive it standalone)
            diagnosis = getattr(self, "_diagnosis", None)
            if diagnosis is not None and diagnosis.bundle_count:
                payload["diag_bundles"] = diagnosis.bundle_count
                payload["diag_last"] = diagnosis.last_bundle or ""
                payload["diag_dir"] = diagnosis.directory or ""
            # per-step sample map for the lighthouse time-series store
            # (ISSUE 11): last step row's wall/local/phase seconds,
            # lathist quantiles and detector flags — telemetry/
            # timeseries.py owns the vocabulary, the lighthouse stays
            # schema-blind
            from torchft_tpu.telemetry.timeseries import build_series

            series = build_series(
                slo_breach=bool(self._slo.breached()),
                stuck=bool(self._watchdog.stalled),
                divergence=bool(self._divergence_latched),
            )
            if series:
                payload["series"] = series
            return payload
        except Exception:  # noqa: BLE001 — observability must not fail quorum
            return None

    def set_state_dict_fns(
        self, load_state_dict: Callable[[T], None], state_dict: Callable[[], T]
    ) -> None:
        self._load_state_dict = load_state_dict
        self._user_state_dict = state_dict

    def set_heal_warmup(self, fn: Callable[[Any], None]) -> None:
        """Register a warmup callback for the heal/compile overlap
        (docs/heal_plane.md): during a heal, ``fn(spec_tree)`` runs on a
        daemon thread as soon as the incoming state's header (dtypes +
        shapes) is known — while the stripes are still streaming — so jit
        compilation/warmup costs overlap the transfer instead of
        serializing after it. ``spec_tree`` mirrors the state dict with
        ``jax.ShapeDtypeStruct`` leaves. Best-effort: a failing warmup
        never fails the heal."""
        self._heal_warmup = fn

    def _heal_header_cb(self, header: bytes) -> None:
        """Transport header hook (runs on the quorum thread mid-recv):
        kick the registered warmup off-thread so recv keeps streaming."""
        fn = self._heal_warmup
        if fn is None:
            return

        def run() -> None:
            try:
                from torchft_tpu.checkpointing.serialization import (
                    spec_tree_from_header,
                )

                fn(spec_tree_from_header(header))
            except Exception:  # noqa: BLE001 — warmup is best-effort
                self._logger.exception("heal warmup failed")

        threading.Thread(
            target=run, daemon=True, name="tft_heal_warmup"
        ).start()

    def _record_commit_trail(self) -> None:
        """Record the committed state's per-leaf digests at the current
        step (main thread, step boundary — the state HERE is exactly the
        state a heal at this step would serve). Idempotent per step; the
        trail evicts past its horizon."""
        assert self._heal_trail is not None
        if self._user_state_dict is None:
            return
        try:
            from torchft_tpu.checkpointing.serialization import flatten_state

            _header, buffers = flatten_state(self._manager_state_dict())
            self._heal_trail.record(self._step, buffers)
        except Exception:  # noqa: BLE001 — the trail must never fail a step
            self._logger.exception("commit-trail record failed")

    def _heal_own_digest(self) -> Optional[tuple]:
        """This replica's flattened state + tree digest at its last
        committed step — the differential heal request's credentials.
        None when differential heal can't apply (no state callbacks, step
        0, feature off)."""
        if (
            self._heal_trail is None
            or self._user_state_dict is None
            or self._step <= 0
        ):
            return None
        try:
            from torchft_tpu.checkpointing import delta as _delta
            from torchft_tpu.checkpointing.serialization import Flattening

            # once a heal, on the quorum thread: each leaf hashed as it
            # lands, on as many threads as the state is worth
            flat = Flattening(self._manager_state_dict())
            digests = _delta.leaf_digests(
                flat.buffers(),
                workers=_delta.digest_workers(flat.nbytes, flat.nbuffers),
            )
            return flat.landed, _delta.tree_digest(digests)
        except Exception:  # noqa: BLE001 — degrade to a full heal
            self._logger.exception("own-state digest failed")
            return None

    def _heal_sources(self, quorum) -> List[tuple]:
        """Resolve the striped-heal source list: the lighthouse-named
        primary first, then the rest of the max-step cohort, each mapped
        to its checkpoint transport URL via ``mgr.checkpoint_metadata``.
        A peer that fails the metadata RPC is dropped (it may be mid-death
        — the stripe fetch would re-stripe around it anyway, this is just
        cheaper). Returns ``[(manager_addr, transport_metadata), ...]``."""
        from torchft_tpu.checkpointing.stripes import heal_sources_limit

        addrs = [quorum.recover_src_manager_address]
        for a in quorum.recover_src_addresses:
            if a and a not in addrs:
                addrs.append(a)
        addrs = addrs[: heal_sources_limit()]
        out: List[tuple] = []
        lock = threading.Lock()

        def resolve(addr: str) -> None:
            try:
                client = ManagerClient(
                    addr, connect_timeout=self._connect_timeout
                )
                try:
                    meta = client._checkpoint_metadata(
                        self._rank, timeout=self._timeout
                    )
                finally:
                    client.close()
                with lock:
                    out.append((addr, meta))
            except Exception as e:  # noqa: BLE001 — drop the source
                self._logger.warn(
                    f"heal source {addr} metadata fetch failed: {e}"
                )

        if len(addrs) == 1:
            resolve(addrs[0])
        else:
            threads = [
                threading.Thread(
                    target=resolve, args=(a,), name="tft_heal_meta"
                )
                for a in addrs
            ]
            for th in threads:
                th.start()
            for th in threads:
                th.join()
        # keep the lighthouse-named primary first (deterministic plan)
        out.sort(key=lambda t: addrs.index(t[0]))
        return out

    def shutdown(self, wait: bool = True) -> None:
        """Shut down the manager, checkpoint transport and data plane."""
        self._shutting_down = True
        self._watchdog.stop()
        if self._diagnosis is not None:
            self._diagnosis.remove()
        if self._fleet_monitor is not None:
            self._fleet_monitor.stop()
        self._checkpoint_transport.shutdown(wait=wait)
        if self._manager is not None:
            self._manager.shutdown()
        self._executor.shutdown(wait=wait)
        self._collectives.shutdown()
        self._client.close()
        self._store.close()

    # ------------------------------------------------------------------
    # quorum
    # ------------------------------------------------------------------

    def start_quorum(
        self,
        allow_heal: bool = True,
        shrink_only: bool = False,
        timeout: Optional[timedelta] = None,
    ) -> None:
        """Compute a new quorum (async by default) and ready the manager for
        a new step. Call before the forward pass; the RPC overlaps compute.

        All replicas must pass the same ``allow_heal``. With
        ``shrink_only`` the quorum can only lose members (planned
        downscale)."""
        self._errored = None
        self._healing = False
        self._group_healing = False
        self._step_epochs = set()
        self._step_n = None
        # Step coordinate for this step's trail events and trace ids:
        # fixed here, so quorum_start/spans/commit of ONE step join on one
        # value although the commit advances self._step before the step's
        # last spans close.
        self._step_label = self._step
        telemetry.TRACER.set_context(
            replica_id=self._replica_id,
            step=self._step_label,
            quorum_epoch=self._quorum_id,
        )
        telemetry.BLACKBOX.set_context(
            replica_id=self._replica_id,
            step=self._step_label,
            quorum_epoch=self._quorum_id,
        )
        self._watchdog.arm(self._step_label)
        telemetry.emit(
            "quorum_start",
            step=self._step_label,
            allow_heal=allow_heal,
            shrink_only=shrink_only,
        )
        if self._heal_trail is not None:
            # differential heal: digest the committed state at this step
            # boundary
            self._record_commit_trail()

        # Replace-under-lock, wait-outside-lock. Replacement only happens
        # after observing a DONE future under the lock, so a death-watch
        # submission can never be silently overwritten (its exception
        # unobserved, a duplicate lighthouse RPC from this replica) — but
        # the waiting itself must not hold _qf_lock: the previous future
        # can be an in-flight death-watch re-quorum long-poll, and an
        # earlier version that held the lock across .result() blocked
        # _on_peer_death's monitor thread (stalling dead-peer eviction
        # reports) for up to quorum_timeout [found by the analysis gate:
        # blocking-under-lock].
        while True:
            with self._qf_lock:
                prev = self._quorum_future
                if prev is None or prev.done():
                    if prev is not None:
                        try:
                            exc = prev.exception()  # done ⇒ returns now
                        except Exception as e:  # noqa: BLE001 — cancelled
                            exc = e
                        if exc is not None:
                            # the failure already surfaced to the caller
                            # through wait_quorum/allreduce/should_commit
                            # on the step that scheduled it; calling
                            # start_quorum again IS the retry — start
                            # fresh instead of re-raising history forever
                            self._logger.warn(
                                f"previous quorum attempt failed ({exc}); "
                                "retrying"
                            )
                    self._last_quorum_args = (allow_heal, shrink_only, timeout)
                    self._quorum_future = self._executor.submit(
                        self._async_quorum,
                        allow_heal=allow_heal,
                        shrink_only=shrink_only,
                        quorum_timeout=timeout or self._quorum_timeout,
                    )
                    break
            # an in-flight previous attempt: wait it out with the lock
            # RELEASED, then re-check — a death-watch submission landing
            # in between is observed (not clobbered) by the next pass
            try:
                prev.result()
            except Exception:  # noqa: BLE001 — consumed under the lock above
                pass
        if not self._use_async_quorum:
            self.wait_quorum()
            if self._healing:
                # eagerly apply the recovered state so the forward pass runs
                # from a good state; no zero-grad dance needed
                self._apply_pending_state_dict()
                self._healing = False
            # sync quorum: every rank healed before the forward pass, so
            # the whole group participates with real gradients
            self._group_healing = False

    def wait_quorum(self) -> None:
        """Block until the in-flight quorum completes; the data plane is
        configured for the new membership after this returns."""
        assert (
            self._quorum_future is not None
        ), "must call start_quorum before wait_quorum"
        if self._quorum_future.done():
            self._quorum_future.result()
            return
        # step-anatomy: the time the MAIN thread actually blocked on the
        # quorum (the RPC itself overlaps compute in async mode — only
        # the tail the trainer had to wait out is step cost). Peer skew
        # lands here too: the lighthouse's long-poll waits for the whole
        # fleet, so a straggler stretches every OTHER group's quorum_wait
        # — which is exactly why the local-time signal excludes it.
        import time as _time

        t0 = _time.perf_counter()
        try:
            self._quorum_future.result()
        finally:
            telemetry.LEDGER.record(
                "quorum_wait", _time.perf_counter() - t0
            )

    def _async_quorum(
        self, allow_heal: bool, shrink_only: bool, quorum_timeout: timedelta
    ) -> None:
        import time as _time

        t_quorum = _time.perf_counter()
        with telemetry.TRACER.span(
            "quorum", trace_id=self._trace_id(), rank=self._rank
        ) as q_span:
            try:
                quorum = self._client._quorum(
                    rank=self._rank,
                    step=self._step,
                    checkpoint_metadata=self._checkpoint_transport.metadata(),
                    shrink_only=shrink_only,
                    timeout=quorum_timeout,
                    # latched data-plane errors request a flush: quorum_id
                    # bumps so all groups (healthy ones too) re-rendezvous
                    commit_failures=self._commit_failures,
                    # data-plane transport label for the lighthouse
                    # dashboard — lets an operator spot a group that fell
                    # back to a slower plane (e.g. CMA broken-latch
                    # converging everyone to TCP)
                    plane=self.plane_info(),
                    # piggybacked telemetry: counters digest + span batch
                    # for the lighthouse's /cluster.json and merged /trace
                    telemetry_payload=self._telemetry_payload(),
                )
            except BaseException:
                # the drained span batch never reached the lighthouse —
                # requeue it so the outage window keeps its spans in the
                # merged trace (the incident is exactly what /trace is for)
                telemetry.TRACER.requeue_last_batch()
                raise
            q_span.set(quorum_id=quorum.quorum_id, heal=quorum.heal)

        # telemetry-delta ack loop (ISSUE 16): the lighthouse's
        # last-applied version rides the quorum reply; feeding it to the
        # encoder is what collapses the NEXT piggyback to only-changed
        # fields (and triggers a full resync when the lighthouse lost
        # our chain — restart, eviction, version skew)
        if quorum.telemetry_ack:
            enc = getattr(self, "_tdelta_encoder", None)
            if enc is not None:
                try:
                    enc.on_ack(quorum.telemetry_ack)
                except Exception:  # noqa: BLE001 — never fail quorum
                    pass

        # Async quorum overlaps the forward pass, so a healing replica can't
        # participate this step (its state is mid-flight) — take the max-step
        # cohort. Sync quorum heals eagerly, so everyone participates.
        self._participating_rank, self._participating_world_size = (
            (quorum.max_rank, quorum.max_world_size)
            if self._use_async_quorum or not allow_heal
            else (quorum.replica_rank, quorum.replica_world_size)
        )
        # plane-consistent zero-contribution gate: if ANY local rank of
        # this group heals, every rank contributes zeros this step (see
        # coord.cc compute_quorum_results group_heal)
        self._group_healing = allow_heal and quorum.group_heal

        if self._world_size_mode == WorldSizeMode.FIXED_WITH_SPARES:
            # demote groups beyond min_replica_size to zero-contributing spares
            self._participating_world_size = min(
                self._participating_world_size, self._min_replica_size
            )
            if (
                self._participating_rank is not None
                and self._participating_rank >= self._min_replica_size
            ):
                self._participating_rank = None

        with self._evict_lock:
            prev_participants = self._participant_ids
            self._participant_ids = quorum.participant_ids
            self._evicted.clear()

        telemetry.PARTICIPANTS.set(self._participating_world_size)
        # prev_participants is [] before the first quorum: joining is not
        # membership CHURN, so don't count it (a cohort restart would
        # otherwise record N phantom changes)
        if prev_participants and set(quorum.participant_ids) != set(
            prev_participants
        ):
            telemetry.MEMBERSHIP_CHANGES.inc()
        telemetry.emit(
            "quorum_ready",
            quorum_id=quorum.quorum_id,
            step=self._step_label,
            participants=list(quorum.participant_ids),
            num_participants=self._participating_world_size,
            heal=quorum.heal,
            reconfigure=quorum.quorum_id != self._quorum_id,
            duration_s=round(_time.perf_counter() - t_quorum, 4),
        )

        if quorum.quorum_id != self._quorum_id:
            # epoch-scoped rendezvous namespace on the primary's store
            store_prefixed_addr = (
                f"{quorum.store_address}/torchft/{quorum.quorum_id}/{self._rank}"
            )
            self._logger.info(
                f"reconfiguring for quorum_id={quorum.quorum_id} store={store_prefixed_addr}"
            )
            self._collectives.configure(
                store_prefixed_addr, quorum.replica_rank, quorum.replica_world_size
            )
            if hasattr(self._collectives, "plane_generation"):
                # (gen, ids) snapshot for death-watch callbacks: published
                # AFTER configure, so a callback from the new ring that
                # races this store is dropped as stale — safe, the lease
                # still expires passively
                self._death_watch_snapshot = (
                    self._collectives.plane_generation(),
                    list(quorum.participant_ids),
                )
            self._quorum_id = quorum.quorum_id
            telemetry.TRACER.set_context(quorum_epoch=quorum.quorum_id)
            telemetry.BLACKBOX.set_context(quorum_epoch=quorum.quorum_id)
            telemetry.QUORUM_RECONFIGURES.inc()
            self.step_timer.mark_quorum()
            # fresh epoch: the flush request (if any) has been honored
            self._commit_failures = 0
            if self._rank == 0:
                self._sweep_stale_epochs(quorum.quorum_id)

        if allow_heal:
            from torchft_tpu.checkpointing.stripes import heal_sources_limit

            # Striped multi-source heal (docs/heal_plane.md): when ANYONE
            # heals this round, every max-step cohort member a healer may
            # actually contact stages a checkpoint — not just the
            # round-robin-assigned sources — so the healer can pull a
            # stripe from each of them in parallel. Members past the
            # healer-side source cap never get contacted for stripes, so
            # they skip the flatten+stage (a 32-group fleet must not pay
            # 31 full device-to-host copies for one rejoiner); a member
            # that can't FIND itself in the cohort list stages
            # conservatively (an address-format drift must degrade to
            # wasted staging, never to an unserved healer).
            _src_limit = heal_sources_limit()
            stage_for_stripes = (
                quorum.heal_pending
                and not quorum.heal
                and quorum.max_rank is not None
                and _src_limit > 1
                and (
                    self._manager_addr in quorum.recover_src_addresses[:_src_limit]
                    or self._manager_addr not in quorum.recover_src_addresses
                )
            )
            if quorum.recover_dst_ranks or stage_for_stripes:
                self._logger.info(
                    f"peers need recovery from us {quorum.recover_dst_ranks}"
                    + (" (stripe source)" if stage_for_stripes else "")
                )
                with telemetry.TRACER.span(
                    "heal_send",
                    trace_id=self._trace_id(),
                    dst_ranks=list(quorum.recover_dst_ranks),
                    step=quorum.max_step,
                ):
                    self._checkpoint_transport.send_checkpoint(
                        dst_ranks=quorum.recover_dst_ranks,
                        step=quorum.max_step,
                        state_dict=self._manager_state_dict(),
                        timeout=self._timeout,
                    )
                telemetry.HEALS_TOTAL.labels(role="send").inc(
                    len(quorum.recover_dst_ranks)
                )
            if quorum.heal:
                self._healing = True
                t_heal = _time.perf_counter()
                # rejoin-to-commit SLO clock starts at heal begin; the
                # first committed should_commit on the main thread
                # observes and clears it
                self._rejoin_t0 = t_heal
                telemetry.emit(
                    "heal_begin",
                    step=quorum.max_step,
                    src=quorum.recover_src_manager_address,
                )
                self._logger.info(
                    f"healing: fetching checkpoint metadata from "
                    f"{quorum.recover_src_manager_address} at step {quorum.max_step}"
                )
                # protocol invariant, NOT a retryable transfer failure —
                # keep it outside the retry handler below so a lighthouse
                # that heals us without naming a source crashes loudly
                # instead of looping on a doomed heal forever
                assert (
                    quorum.recover_src_rank is not None
                ), "must have a recover rank when healing"
                try:
                    sources = self._heal_sources(quorum)
                    if not sources:
                        raise ConnectionError(
                            "no heal source answered the checkpoint-"
                            "metadata RPC"
                        )
                    multi = getattr(
                        self._checkpoint_transport,
                        "recv_checkpoint_multi",
                        None,
                    )
                    # the user state dict is only applied from the main
                    # thread; stage it here
                    with telemetry.TRACER.span(
                        "heal_recv",
                        trace_id=self._trace_id(),
                        src=quorum.recover_src_manager_address,
                        sources=len(sources),
                        step=quorum.max_step,
                    ):
                        if multi is not None:
                            own = self._heal_own_digest()
                            self._pending_state_dict = cast(
                                Dict[str, object],
                                multi(
                                    [m for _, m in sources],
                                    step=quorum.max_step,
                                    timeout=self._timeout,
                                    since_step=(
                                        self._step if own is not None else None
                                    ),
                                    own=own,
                                    header_cb=self._heal_header_cb,
                                ),
                            )
                        else:
                            self._pending_state_dict = cast(
                                Dict[str, object],
                                self._checkpoint_transport.recv_checkpoint(
                                    src_rank=quorum.recover_src_rank,
                                    metadata=sources[0][1],
                                    step=quorum.max_step,
                                    timeout=self._timeout,
                                ),
                            )
                except Exception as e:  # noqa: BLE001 — heal must be retryable
                    # A torn/failed checkpoint transfer (the serving peer
                    # died mid-stream — fault-injection scenario
                    # ckpt_serve_death, previously a trainer-killing
                    # struct.error through wait_quorum) must not take this
                    # worker down: the quorum/plane are fine, only the
                    # state fetch failed. Stay un-healed, latch the error
                    # so the step aborts at the commit barrier, and let
                    # the next start_quorum re-request the heal (we are
                    # still behind max_step, so the lighthouse re-selects
                    # us for recovery).
                    self._healing = False
                    self._pending_state_dict = None
                    self._logger.exception(
                        f"heal transfer failed; retrying next quorum: {e}"
                    )
                    telemetry.emit(
                        "heal_failed", step=quorum.max_step, error=str(e)
                    )
                    self.report_error(e)
                    return
                self.load_state_dict(
                    cast(Dict[str, int], self._pending_state_dict["torchft"])
                )
                # the received state dict is authoritative: never rewind
                # below the state the bytes actually encode
                self._step = max(self._step, quorum.max_step)
                heal_s = _time.perf_counter() - t_heal
                nbytes = getattr(
                    self._checkpoint_transport, "last_recv_bytes", 0
                )
                if not isinstance(nbytes, int):  # un-instrumented transport
                    nbytes = 0
                telemetry.HEALS_TOTAL.labels(role="recv").inc()
                telemetry.HEAL_DURATION.observe(heal_s)
                self._last_heal_ts = _time.time()
                self.step_timer.mark_heal()
                # per-source stripe throughput + stage split from the
                # multi-source transport (empty dict on legacy paths) —
                # the recovery bench and the trail both read this, so a
                # rejoin regression names its stage instead of one
                # opaque duration
                heal_stats = getattr(
                    self._checkpoint_transport, "last_heal_stats", None
                )
                telemetry.emit(
                    "heal_end",
                    step=quorum.max_step,
                    bytes=nbytes,
                    duration_s=round(heal_s, 4),
                    **(
                        {"heal_stats": heal_stats}
                        if isinstance(heal_stats, dict) and heal_stats
                        else {}
                    ),
                )

    def _sweep_stale_epochs(self, current_qid: int) -> None:
        """GC rendezvous keys from dead epochs (round-2 verdict weak #5).

        Every quorum epoch writes ``coll/addr/*`` keys under
        ``torchft/{quorum_id}/...`` on the primary's store and nothing else
        deletes them, so long jobs with flush re-quorums grow the store
        without bound. Each group's rank 0 sweeps its *own* store on every
        reconfigure, keeping one epoch of slack for groups still dialing
        the previous epoch. Best-effort: a failed sweep never fails the
        quorum."""
        try:
            for key in self._store.keys("torchft/"):
                if isinstance(key, bytes):
                    key = key.decode()
                parts = key.split("/")
                if len(parts) < 2 or parts[0] != "torchft":
                    continue
                try:
                    qid = int(parts[1])
                except ValueError:
                    continue
                if qid < current_qid - 1:
                    self._store.delete(key)
        except Exception as ex:  # noqa: BLE001 — GC must never fail a step
            self._logger.warn(f"epoch GC failed: {ex}")

    def _apply_pending_state_dict(self) -> None:
        assert self._healing, "must be in healing state"
        assert self._quorum_future is not None, "missing quorum future"
        self._quorum_future.result()
        assert self._pending_state_dict is not None, "checkpoint was not staged"
        assert self._load_state_dict is not None, "user load_state_dict not set"
        self._logger.info("applying pending state dict")
        import time as _time

        t0 = _time.perf_counter()
        self._load_state_dict(cast(T, self._pending_state_dict["user"]))
        dur = _time.perf_counter() - t0
        # step-anatomy `heal` phase: the main-thread share of a heal (the
        # staged-state apply; the transfer itself rides the quorum thread
        # and shows as quorum_wait — docs/observability.md "Step anatomy").
        # The same duration feeds the heal-stage view as `device_put` so
        # the rejoin ledger (meta/recv/decode/device_put) is complete.
        telemetry.LEDGER.record("heal", dur)
        telemetry.LEDGER.record_heal_stage("device_put", dur)
        self._pending_state_dict = None

    # ------------------------------------------------------------------
    # collectives
    # ------------------------------------------------------------------

    def device_data_plane(self) -> bool:
        """True when the configured collectives move ``jax.Array``s directly
        (ICI path, :class:`~torchft_tpu.collectives_device.CollectivesDevice`)
        — gradient averaging then skips the host round trip entirely."""
        return bool(getattr(self._collectives, "device_arrays", False))

    def takes_sources(self) -> bool:
        """Whether the data plane has a reduction that reads an array's
        contribution from a source (``Collectives.takes_sources``): ``ddp``
        then hands :meth:`allreduce_many` its landing arrays instead of
        packing them."""
        fn = getattr(self._collectives, "takes_sources", None)
        return bool(fn()) if callable(fn) else False

    def plane_info(self) -> str:
        """Which transport carries large allreduces this epoch
        (``Collectives.plane_info``: ``cma`` — the groups share one host's
        memory —, ``tcp-striped``, ``python-ring``, ...); ``ddp`` orders its
        landings and rings by it."""
        fn = getattr(self._collectives, "plane_info", None)
        return str(fn()) if callable(fn) else type(self._collectives).__name__

    def avg_in_ring_ops(self) -> int:
        """The data plane's count of allreduces whose average was taken
        inside the reduction (``Collectives.avg_in_ring_ops``); ``ddp``
        reads its growth over an exchange as ``buckets_avg_in_ring``."""
        fn = getattr(self._collectives, "avg_in_ring_ops", None)
        return int(fn()) if callable(fn) else 0

    def ring_account(self) -> Dict[str, float]:
        """The data plane's running account of where its allreduces spent
        their time inside the ring (``Collectives.ring_account``; ``{}``
        where it keeps none); ``ddp`` reads its growth over an exchange as
        ``ring_wait_s``, ``ring_pull_s`` and the rest."""
        fn = getattr(self._collectives, "ring_account", None)
        return dict(fn()) if callable(fn) else {}

    def wire_codec(self) -> str:
        """Name of the codec the configured data plane ships large f32
        allreduces with (``"f32"`` = exact). ``ManagedOptimizer`` keys its
        automatic error-feedback enablement off this — a lossy wire
        without residual compensation drifts (docs/wire_plane.md)."""
        fn = getattr(self._collectives, "wire_codec", None)
        return fn() if callable(fn) else "f32"

    def allreduce(self, tensor: np.ndarray) -> Future:
        """Fault-tolerant cross-replica-group allreduce of one buffer,
        scaled by ``1 / num_participants()``; see :meth:`allreduce_many`."""
        return self.allreduce_many([tensor]).then(lambda f: f.value()[0])

    def allreduce_many(
        self, tensors: List[Any], sources: Optional[List[Any]] = None
    ) -> Future:
        """Fault-tolerant cross-replica-group allreduce of a list of
        buffers (numpy, averaged in place — or ``jax.Array``s when the data
        plane is device-path, averaged on device), scaled by
        ``1 / num_participants()``.

        ``sources`` (host path; per buffer ``None`` or a
        ``collectives.Source``) names where each buffer's contribution
        lies in place of what the buffer holds: arrays that are only ever
        read. A data plane that :meth:`takes_sources` reads them there;
        for any other, and for a step that is already lost, they are
        copied into the buffers here — the result is the same.

        On error the future still completes (with the possibly-corrupt
        tensors) and the error is latched — subsequent calls no-op and the
        step fails at the commit barrier. Healing/spare replicas contribute
        zeros so the participants' average is unperturbed."""
        if not tensors or self.errored():
            fill_from_sources(tensors, sources)
            return Future.completed(tensors)

        self.wait_quorum()
        if self.errored():
            # the quorum thread may have latched a failure DURING the wait
            # (e.g. a failed heal transfer): the step is already doomed, and
            # issuing the collective anyway would park this rank in a ring
            # whose peers aborted — a full op-timeout of dead wait before
            # the inevitable abort (observed in the stripe_heal_peer_death
            # bring-up: +30s per step on the healer)
            fill_from_sources(tensors, sources)
            return Future.completed(tensors)
        # record which plane epoch this op rides: a death-watch re-quorum
        # can land MID-step, and a step whose ops span two epochs mixes
        # normalization denominators — should_commit vetoes those
        self._step_epochs.add(self._quorum_id)
        # participant count captured at ISSUE time: an op can never span
        # plane epochs (configure tears down its executor, cancelling or
        # failing it), so the membership the op actually reduced over is
        # the one in force now. Reading it at COMPLETION time instead
        # would (a) mis-scale a finished op if a death-watch re-quorum
        # lands before its callback runs, and (b) deadlock: the callback
        # runs on the collectives op thread, and blocking there on the
        # re-quorum future while its configure() waits to join that very
        # thread is a cycle.
        n_at_issue = self._participating_world_size
        # ... and the COMMIT accounting must use the same snapshot: a
        # death-watch re-quorum landing between the step's last op and
        # should_commit would otherwise count the new cohort's size for
        # batches averaged over the old one (or veto on the new cohort's
        # min_replicas when the reduction was over enough replicas)
        self._step_n = n_at_issue

        # branch on the *configured* data plane, not the input type: the
        # device backend converts numpy inputs to jax.Arrays, so its results
        # must be normalized on device regardless of what the caller passed
        device = self.device_data_plane()
        if not self.is_participating():
            if device:
                import jax.numpy as jnp

                tensors = [jnp.zeros_like(t) for t in tensors]
            else:
                for t in tensors:
                    t[...] = 0  # in place: host buffers are bucket views
            sources = None  # zeros are the whole of its contribution
        elif sources is not None and not self.takes_sources():
            fill_from_sources(tensors, sources)
            sources = None

        # snapshot this epoch's rank→replica map: an in-flight op can fail
        # AFTER the next quorum has renumbered ranks, and a PeerGoneError
        # mapped through the new list would accuse an innocent replica
        with self._evict_lock:
            ids_snapshot = list(self._participant_ids)

        try:
            # host path: the divisor travels with the op, and the backend
            # applies it where an element's final value is written (the
            # native ring: in the owner's last reduce step, no pass of its
            # own on the op thread the step waits for). Device path: one
            # jitted divide with n traced, so membership never recompiles
            # (sources: only to a backend that said it takes them)
            extra = {} if sources is None else {"sources": sources}
            work = self._collectives.allreduce(
                tensors, ReduceOp.SUM,
                divisor=1 if device else max(n_at_issue, 1), **extra,
            )

            def normalize(fut: Future) -> List[Any]:
                try:
                    reduced = fut.value()  # surface exceptions
                except BaseException as e:  # noqa: BLE001 — annotate + rethrow
                    e._tft_participants = ids_snapshot
                    raise
                if device and n_at_issue > 1:
                    reduced = _divide_tree(reduced, n_at_issue)
                if self._divergence_sentinel:
                    self._digest_reduced(reduced)
                return reduced

            fut = self.wrap_future(work.get_future().then(normalize), tensors)
            # close the issue-time race: if a death-watch reconfigure slid
            # in between the epoch read above and the submission, the two
            # reads differ and the veto catches the step
            self._step_epochs.add(self._quorum_id)
            return fut
        except Exception as e:  # noqa: BLE001 — latch and continue
            self._logger.exception(f"exception in allreduce, skipping remaining: {e}")
            self.report_error(e)
            return Future.completed(tensors)

    def _digest_reduced(self, reduced: List[Any]) -> None:
        """Divergence sentinel: fold one op's post-reduce outputs into
        this step's ordered digest list (op-callback thread; ops resolve
        in issue order, so the list is deterministic across groups —
        which is what makes equality the invariant). blake2b via the
        differential-heal digest helpers; failures degrade to "no digest
        this step", never to a failed op."""
        try:
            from torchft_tpu.checkpointing import delta as _delta

            bufs = [np.asarray(t) for t in reduced]
            self._step_digests.append(
                _delta.tree_digest(_delta.leaf_digests(bufs))
            )
        except Exception:  # noqa: BLE001 — sentinel must not fail the op
            self._logger.exception("divergence digest failed")

    def _note_divergence(self, step: int) -> None:
        """The should_commit reply carried the lighthouse's divergence
        latch: record it once per process (the lighthouse latch never
        clears, so every later vote re-reports it)."""
        if self._divergence_latched:
            return
        self._divergence_latched = True
        telemetry.DIVERGENCE_TOTAL.inc()
        telemetry.emit(
            "divergence_detected", step=step, fence=self._divergence_fence
        )
        self._logger.warn(
            f"divergence sentinel latched at step {step}: post-reduce "
            "state digests disagreed across the cohort"
            + (" (fence vetoed the commit)" if self._divergence_fence else "")
        )

    def report_error(self, e: Exception) -> None:
        """Latch an error: the current step will not commit and the data
        plane reconfigures on the next quorum. If the error names a dead
        peer (:class:`~torchft_tpu.collectives.PeerGoneError`), its
        replica is reported to the lighthouse for immediate eviction so
        the re-quorum doesn't wait out the heartbeat lease."""
        self._errored = e
        self._errored_epoch = self._quorum_id
        self._maybe_evict(e)

    def _on_peer_death(self, ring_rank: int, plane_gen: Optional[int] = None) -> None:
        """Death-watch callback (runs on the collectives monitor thread):
        a peer's socket hit EOF/error mid-epoch. Report the eviction NOW
        (liveness-probe-guarded at the lighthouse, so a false positive is
        harmless) and, if no quorum RPC is in flight, start one — by the
        time the trainer finishes the doomed step, the shrunken quorum is
        usually already delivered and the plane reconfigured, so the
        survivor pays ~one step instead of detection+quorum+reconfigure
        serialized after it.

        ``plane_gen`` tags the ring the rank belongs to: a late POLLHUP
        delivered while ``_async_quorum`` replaces membership would
        otherwise map an OLD ring rank through the NEW participant list
        and accuse a live replica (burning a lighthouse liveness probe
        and delaying the real re-quorum)."""
        from torchft_tpu.collectives import PeerGoneError

        if self._shutting_down:
            return
        snap = self._death_watch_snapshot
        if plane_gen is not None and snap is not None:
            snap_gen, snap_ids = snap
            if plane_gen != snap_gen:
                self._logger.info(
                    f"dropping stale death-watch callback for ring rank "
                    f"{ring_rank} (plane gen {plane_gen} != armed {snap_gen})"
                )
                return
        else:
            snap_ids = None
        err = PeerGoneError(
            ring_rank, f"death watch: peer {ring_rank} socket closed"
        )
        if snap_ids is not None:
            # map the ring rank through the SNAPSHOT for this generation,
            # never through whatever _participant_ids holds right now
            err._tft_participants = list(snap_ids)
        self._maybe_evict(err)
        with self._qf_lock:
            if self._shutting_down:
                return
            fut = self._quorum_future
            if fut is None or not fut.done():
                # a quorum RPC is already in flight; it observes the
                # eviction when the lighthouse re-forms the quorum
                return
            # Only pre-quorum when the SURVIVING membership can form a
            # quorum without waiting for a restart: otherwise the early
            # long-poll parks the trainer's wait_quorum on a quorum that
            # cannot form until the victim respawns — strictly worse than
            # the old fail-fast-then-retry path.
            with self._evict_lock:
                alive = len(
                    [p for p in self._participant_ids if p not in self._evicted]
                )
            if alive < max(1, self._min_replica_size):
                return
            _, shrink_only, timeout = self._last_quorum_args
            self._logger.info(
                f"death watch: peer {ring_rank} gone; starting early re-quorum"
            )
            # allow_heal=False: this quorum exists ONLY to shrink
            # membership and rebuild the plane under the doomed step.
            # Serving a heal here would read user state on a thread the
            # trainer doesn't synchronize with (it may be mid-optimizer-
            # update after a commit) — rejoiners heal one step later on
            # the regular start_quorum cadence, where checkpoint staging
            # is trainer-synchronized.
            self._quorum_future = self._executor.submit(
                self._async_quorum,
                allow_heal=False,
                shrink_only=shrink_only,
                quorum_timeout=timeout or self._quorum_timeout,
            )

    def _maybe_evict(self, e: BaseException) -> None:
        """Fire-and-forget lh.evict for a PeerGoneError's peer. Runs on a
        daemon thread: the report is an optimization (the lease still
        expires passively) and must never block or fail the training
        thread."""
        peer: Optional[int] = None
        participants = None
        seen = 0
        cause: Optional[BaseException] = e
        while cause is not None and seen < 8:  # unwrap chained causes
            if participants is None:
                participants = getattr(cause, "_tft_participants", None)
            peer = getattr(cause, "peer_rank", None)
            if peer is not None:
                break
            cause = cause.__cause__ or cause.__context__
            seen += 1
        with self._evict_lock:
            if participants is None:
                participants = list(self._participant_ids)
            if peer is None or not (0 <= peer < len(participants)):
                return
            victim = participants[peer]
            if victim in self._evicted:
                # already reported this epoch — the check-and-add must be
                # one atomic step: report_error (main/op-callback threads)
                # and the death watch race into here for the same victim
                return
            self._evicted.add(victim)
        # the trail's detection record lives HERE, not in the death-watch
        # callback: a dead peer can also surface as a PeerGoneError from a
        # failed collective/p2p op (report_error path) without the poll
        # thread ever firing — both roads converge on this dedup point
        telemetry.PEER_DEATHS.inc()
        telemetry.emit(
            "peer_death", ring_rank=peer, replica=victim, step=self._step
        )

        def _report() -> None:
            # Fresh client: self._client serializes calls on one socket, so
            # the report would otherwise park behind an in-flight long-poll
            # quorum call — the exact wait eviction exists to skip.
            try:
                client = ManagerClient(
                    self._manager_addr, connect_timeout=timedelta(seconds=5)
                )
                try:
                    evicted = client.evict(victim, timeout=timedelta(seconds=5))
                finally:
                    client.close()
                telemetry.EVICTIONS_REPORTED.labels(
                    result="evicted" if evicted else "rejected"
                ).inc()
                telemetry.emit("eviction", victim=victim, evicted=evicted)
                self._logger.info(
                    f"reported dead peer {victim}: evicted={evicted}"
                )
            except Exception as ex:  # noqa: BLE001 — best effort
                telemetry.EVICTIONS_REPORTED.labels(result="failed").inc()
                self._logger.warn(f"evict report for {victim} failed: {ex}")

        threading.Thread(target=_report, daemon=True, name="tft_evict").start()

    def errored(self) -> Optional[Exception]:
        return self._errored

    def wrap_future(
        self, fut: Future, default: Any, timeout: Optional[timedelta] = None
    ) -> Future:
        """Deadline + error-swallowing wrapper: failures complete the future
        with ``default`` and latch the error on the manager
        (manager.py:327-364)."""
        fut = future_timeout(fut, timeout or self._timeout)

        def callback(f: Future) -> Any:
            try:
                return f.value()
            except Exception as e:  # noqa: BLE001
                self._logger.exception(f"exception in future, skipping remaining: {e}")
                self.report_error(e)
                return default

        out = fut.then(callback)
        self._pending_work.append(out)
        return out

    # ------------------------------------------------------------------
    # commit
    # ------------------------------------------------------------------

    def should_commit(self, timeout: Optional[timedelta] = None) -> bool:
        """Per-step commit barrier: True iff every rank in the group had a
        clean step. Call after backward, step the optimizer only on True."""
        # keep the commit path loud on misuse: the pre-quorum guards on the
        # read-only participation queries must not turn a missing
        # start_quorum into a silent quorum-wide veto
        assert (
            self._quorum_future is not None
        ), "must call start_quorum before should_commit"
        import time as _time

        t_commit = _time.perf_counter()
        # the drain of the step's pending work, apart from the vote RPC
        with telemetry.TRACER.span("commit.prepare", trace_id=self._trace_id()):
            # injection window the ROADMAP flake lives in: workers observed
            # dying silently right AFTER the commit barrier's drain — a kill
            # scheduled here reproduces that timing on demand
            fault_point("commit.vote", match="prepare", step=self._step)
            for work in self._pending_work:
                if self._errored is not None:
                    break
                try:
                    work.wait()
                except Exception:
                    # wrap_future already latched it
                    pass
            self._pending_work = []

            if self._healing:
                self._apply_pending_state_dict()

            step = self._step
            # membership as of the step's OPS (issue-time snapshot), not of
            # a death-watch re-quorum that may have landed after them
            n_step = (
                self._step_n
                if self._step_n is not None
                else self.num_participants()
            )
            enough_replicas = n_step >= self._min_replica_size
            # a step whose collectives spanned two plane epochs (death-watch
            # re-quorum mid-step) mixed normalization denominators. The span
            # is a LOCAL observation — the re-quorum can land between ops on
            # one rank and entirely after another's — but
            # client.should_commit is a global conjunction, so one rank's
            # veto aborts the step group-wide
            mixed_epochs = len(self._step_epochs) > 1
            errored = self._errored
            local_vote = enough_replicas and errored is None and not mixed_epochs
            # divergence sentinel: fold the step's ordered per-op digests
            # into ONE step digest (delta.py's tree fold) and clear for the
            # next step; the vote RPC piggybacks it to the lighthouse's
            # (epoch, step) cohort compare. A step that is not committing
            # cleanly here (error latched / incomplete digest coverage)
            # ABSTAINS ("-"): it still completes the cohort so peers' fence
            # waits never stall on an aborting group, but its partial digest
            # never enters the comparison — only committing states must
            # agree, and an aborting step commits nothing to diverge.
            digest: Optional[str] = None
            epoch = self._quorum_id
            if self._divergence_sentinel:
                digest = "-"
                if local_vote and self._step_digests:
                    try:
                        from torchft_tpu.checkpointing import delta as _delta

                        digest = _delta.tree_digest(self._step_digests)
                    except Exception:  # noqa: BLE001 — degrade to abstain
                        digest = "-"
            self._step_digests = []

            if errored is not None and self._errored_epoch == self._quorum_id:
                # the data plane is suspect: request a flush so the next
                # quorum reconfigures every group into a fresh rendezvous
                # epoch. An error from a PREVIOUS epoch's plane needs no
                # flush — the death-watch re-quorum already rebuilt
                # connectivity.
                self._commit_failures += 1

        with telemetry.TRACER.span(
            "should_commit",
            trace_id=self._trace_id(),
            vote=local_vote,
        ) as sc_span:
            should_commit = self._client.should_commit(
                self._rank,
                step,
                local_vote,
                timeout=timeout or self._timeout,
                digest=digest,
                epoch=epoch,
                fence=self._divergence_fence,
            )
            sc_span.set(decision=should_commit)
        # getattr: duck-typed test managers may predate the sentinel
        if getattr(self._client, "last_divergence", False) is True:
            self._note_divergence(step)
        barrier_s = _time.perf_counter() - t_commit

        self._watchdog.disarm()
        telemetry.COMMIT_BARRIER.observe(barrier_s)
        self._logger.info(
            f"should_commit={should_commit} "
            f"enough_replicas={enough_replicas} errored={errored}"
        )

        # close the checkpoint-serving window: after the commit the staged
        # state is stale
        self._checkpoint_transport.disallow_checkpoint()

        # trail step number is the step that ran (pre-increment) — every
        # lifecycle record of one step (quorum_start, commit/abort,
        # step_outlier) joins on the same step value
        if should_commit:
            telemetry.COMMITS_TOTAL.labels(outcome="committed").inc()
            telemetry.emit("commit", step=step, participants=n_step)
            self._step += 1
            self._batches_committed += n_step
            telemetry.CURRENT_STEP.set(self._step)
        else:
            telemetry.COMMITS_TOTAL.labels(outcome="aborted").inc()
            telemetry.emit(
                "abort",
                step=step,
                enough_replicas=enough_replicas,
                mixed_epochs=mixed_epochs,
                errored=str(errored) if errored else None,
            )
        # step boundary for the rolling rate: quorum-reconfigure/heal steps
        # are tagged as outliers, so the recovery cost of an FT event is
        # readable from the trail instead of denting the headline rate
        dur = self.step_timer.tick()
        if dur is not None and self.step_timer.last_tags:
            telemetry.emit(
                "step_outlier",
                step=step,
                duration_s=round(dur, 4),
                tags=list(self.step_timer.last_tags),
                committed=should_commit,
            )
        # step-anatomy boundary: the barrier cost joins this step's row,
        # then the row is assembled (idle = wall minus attributed phases)
        # and the SLO evaluators see the step's wall/rejoin durations
        telemetry.LEDGER.record("commit_barrier", barrier_s)
        row = telemetry.LEDGER.tick(step=step)
        if row is not None:
            self._slo.observe_step(row["wall_s"])
        if should_commit and self._rejoin_t0 is not None:
            self._slo.observe_rejoin(_time.perf_counter() - self._rejoin_t0)
            self._rejoin_t0 = None
        return should_commit

    # ------------------------------------------------------------------
    # state
    # ------------------------------------------------------------------

    def load_state_dict(self, state_dict: Dict[str, int]) -> None:
        """Restore manager progress counters (pair with the user's periodic
        checkpoint of model/optimizer/dataloader state)."""
        self._step = state_dict["step"]
        self._batches_committed = state_dict["batches_committed"]

    def _manager_state_dict(self) -> Dict[str, object]:
        assert self._user_state_dict is not None, "user state_dict not set"
        return {"user": self._user_state_dict(), "torchft": self.state_dict()}

    def state_dict(self) -> Dict[str, int]:
        return {"step": self._step, "batches_committed": self._batches_committed}

    def current_step(self) -> int:
        """Current step count; incremented only on committed steps, so all
        participants agree on it."""
        return self._step

    def batches_committed(self) -> int:
        """Total batches committed across all replica groups and steps."""
        return self._batches_committed

    def num_participants(self) -> int:
        """Replica groups participating in the current step; 0 before the
        first ``start_quorum`` (no assert-crash — reference parity gap noted
        in round-1 review)."""
        if self._quorum_future is None:
            return 0
        self.wait_quorum()
        assert self._participating_world_size >= 0
        return self._participating_world_size

    def participating_rank(self) -> Optional[int]:
        """This group's rank among the participating groups, or None for
        spectators (spares, healing replicas) and before the first
        ``start_quorum``."""
        if self._quorum_future is None:
            return None
        self.wait_quorum()
        return self._participating_rank

    def is_participating(self) -> bool:
        """Whether this replica's contributions count this step; False
        before the first ``start_quorum``."""
        if self._quorum_future is None:
            return False
        self.wait_quorum()
        if self._participating_rank is None:
            return False
        if self._healing or self._group_healing:
            assert self._use_async_quorum
            return False
        return True
