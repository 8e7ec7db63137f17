"""Low-level coordination API: Lighthouse / Manager servers and clients.

Public surface mirrors the reference's pyo3 module ``torchft._torchft``
(type stubs at /root/reference/torchft/_torchft.pyi:1-61, re-exported by
torchft/coordination.py:17-23) — same classes, same methods, same timeout
semantics (CANCELLED / DEADLINE_EXCEEDED become ``TimeoutError``). The
servers themselves run in the C++ core (``native/coord.cc``).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from datetime import timedelta
from typing import Any, Dict, List, Optional

from torchft_tpu import _native

__all__ = [
    "LighthouseServer",
    "ManagerServer",
    "ManagerClient",
    "LighthouseClient",
    "QuorumResult",
]


def _ms(t: timedelta) -> int:
    return max(1, int(t.total_seconds() * 1000))


@dataclass
class QuorumResult:
    """Per-rank quorum outcome (ManagerQuorumResponse analogue,
    proto/torchft.proto:79-93 / src/lib.rs:240-273)."""

    quorum_id: int = 0
    replica_rank: int = 0
    replica_world_size: int = 1
    recover_src_manager_address: str = ""
    recover_src_rank: Optional[int] = None
    recover_dst_ranks: List[int] = field(default_factory=list)
    store_address: str = ""
    max_step: int = 0
    max_rank: Optional[int] = None
    max_world_size: int = 1
    heal: bool = False
    # any local rank of this group heals → the group contributes zeros on
    # every rank plane (participation must be plane-consistent)
    group_heal: bool = False
    # quorum members' replica_ids in replica_rank order — lets the data
    # plane map a failed peer's ring rank to a replica_id for evict reports
    participant_ids: List[str] = field(default_factory=list)
    # striped multi-source heal (docs/heal_plane.md): manager addresses of
    # the whole max-step cohort (single bootstrap source at max_step == 0)
    recover_src_addresses: List[str] = field(default_factory=list)
    # someone heals this round — every up-to-date member stages a
    # checkpoint so all of them can serve stripes
    heal_pending: bool = False
    # telemetry-delta ack (ISSUE 16): lighthouse's last-applied delta
    # version per encoder incarnation, {inc_hex: {"ver": int, "resync":
    # bool}}. The manager feeds it to its DeltaEncoder so steady-state
    # piggybacks stay O(changed fields); None when the lighthouse has
    # not acked anything yet (or telemetry is off)
    telemetry_ack: Optional[Dict[str, Any]] = None

    @staticmethod
    def _from_wire(d: Dict[str, Any]) -> "QuorumResult":
        return QuorumResult(
            quorum_id=d.get("quorum_id", 0),
            replica_rank=d.get("replica_rank", 0),
            replica_world_size=d.get("replica_world_size", 1),
            recover_src_manager_address=d.get("recover_src_manager_address", ""),
            recover_src_rank=d.get("recover_src_rank"),
            recover_dst_ranks=list(d.get("recover_dst_ranks", [])),
            store_address=d.get("store_address", ""),
            max_step=d.get("max_step", 0),
            max_rank=d.get("max_rank"),
            max_world_size=d.get("max_world_size", 1),
            heal=d.get("heal", False),
            group_heal=d.get("group_heal", d.get("heal", False)),
            participant_ids=[
                s if isinstance(s, str) else s.decode()
                for s in d.get("participant_ids", [])
            ],
            recover_src_addresses=[
                s if isinstance(s, str) else s.decode()
                for s in d.get("recover_src_addresses", [])
            ],
            heal_pending=d.get("heal_pending", False),
            telemetry_ack=d.get("tack") or None,
        )


class LighthouseServer:
    """Global quorum coordinator across replica groups.

    C++ server (native/coord.cc Lighthouse) re-implementing
    src/lighthouse.rs: heartbeat-based health, fast quorum, split-brain
    guard, shrink-only membership, join-timeout straggler wait, and an HTTP
    dashboard on the same port. Defaults match the Python binding defaults
    (src/lib.rs:339-341): join=100ms, tick=100ms, heartbeat timeout=5s.
    """

    def __init__(
        self,
        bind: str,
        min_replicas: int,
        join_timeout_ms: Optional[int] = None,
        quorum_tick_ms: Optional[int] = None,
        heartbeat_timeout_ms: Optional[int] = None,
        evict_probe_ms: Optional[int] = None,
    ) -> None:
        self._handle, self._address = _native.lighthouse_create(
            bind,
            min_replicas,
            join_timeout_ms if join_timeout_ms is not None else 100,
            quorum_tick_ms if quorum_tick_ms is not None else 100,
            heartbeat_timeout_ms if heartbeat_timeout_ms is not None else 5000,
            evict_probe_ms if evict_probe_ms is not None else 100,
        )

    def address(self) -> str:
        return self._address

    def shutdown(self) -> None:
        if self._handle:
            _native.lighthouse_shutdown(self._handle)
            self._handle = 0

    def __del__(self) -> None:
        try:
            self.shutdown()
        except Exception:
            pass


class ManagerServer:
    """Per-replica-group coordinator (src/manager.rs analogue): aggregates the
    group's local ranks, proxies quorum to the lighthouse, computes per-rank
    recovery assignments, and arbitrates the commit vote."""

    def __init__(
        self,
        replica_id: str,
        lighthouse_addr: str,
        hostname: str,
        bind: str,
        store_addr: str,
        world_size: int,
        heartbeat_interval: timedelta = timedelta(milliseconds=100),
        connect_timeout: timedelta = timedelta(seconds=60),
    ) -> None:
        self._handle, self._address = _native.manager_create(
            replica_id,
            lighthouse_addr,
            hostname,
            bind,
            store_addr,
            world_size,
            _ms(heartbeat_interval),
            _ms(connect_timeout),
        )

    def address(self) -> str:
        return self._address

    def shutdown(self) -> None:
        if self._handle:
            _native.manager_shutdown(self._handle)
            self._handle = 0

    def __del__(self) -> None:
        try:
            self.shutdown()
        except Exception:
            pass


class ManagerClient:
    """Client for a ManagerServer (src/lib.rs:115-238 analogue). Timeouts
    travel in-band and are enforced server-side (grpc-timeout parity)."""

    # divergence flag of the most recent should_commit reply (class-level
    # default so spec'd test doubles expose the attribute too)
    last_divergence = False

    def __init__(self, addr: str, connect_timeout: timedelta) -> None:
        self._client = _native.NativeClient(addr, _ms(connect_timeout))

    def _quorum(
        self,
        rank: int,
        step: int,
        checkpoint_metadata: str,
        shrink_only: bool,
        timeout: timedelta,
        commit_failures: int = 0,
        plane: str = "",
        telemetry_payload: Optional[Dict[str, Any]] = None,
    ) -> QuorumResult:
        """``commit_failures > 0`` requests a data-plane flush: the
        lighthouse bumps quorum_id even without membership change, forcing
        every group to re-rendezvous its collectives (extension beyond the
        reference, which needs a process restart for this). ``plane`` is
        this group's data-plane transport label, surfaced on the
        lighthouse dashboard/metrics. ``telemetry_payload`` piggybacks a
        compact per-replica telemetry summary (counters digest + recent
        span batch) on this existing RPC; the manager server forwards it
        to the lighthouse, which aggregates per replica for
        ``GET /cluster.json`` and the merged ``GET /trace`` timeline —
        zero extra control-plane round trips."""
        import time

        from torchft_tpu import telemetry

        req: Dict[str, Any] = {
            "rank": rank,
            "step": step,
            "checkpoint_metadata": checkpoint_metadata,
            "shrink_only": shrink_only,
            "commit_failures": commit_failures,
            "plane": plane,
            # trace context rides the RPC metadata. The C++ server does
            # not consume it today (it keeps no spans) — it is there for
            # wire-level debugging (a packet capture names the caller's
            # span) and for future server-side correlation; the live
            # cross-replica span linking is the checkpoint transport's
            # X-TFT-Trace header plus the shared trace_id coordinates.
            "trace": telemetry.TRACER.inject(),
        }
        if telemetry_payload:
            req["telemetry"] = telemetry_payload
        t0 = time.perf_counter()
        with telemetry.TRACER.span("quorum_rpc", rank=rank, step=step):
            resp = self._client.call("mgr.quorum", req, _ms(timeout))
        # the RPC long-polls until the lighthouse forms the quorum, so
        # this duration IS quorum-formation latency as this rank saw it
        telemetry.QUORUM_LATENCY.observe(time.perf_counter() - t0)
        telemetry.QUORUMS_TOTAL.inc()
        # reply-side injection: a delay here stretches the window between
        # the quorum landing and the plane reconfigure; an error makes
        # this rank treat a DELIVERED quorum as failed (retried next step)
        from torchft_tpu.faultinject.core import fault_point

        fault_point("quorum.reply", match="", rank=rank, step=step)
        return QuorumResult._from_wire(resp)

    def _checkpoint_metadata(self, rank: int, timeout: timedelta) -> str:
        resp = self._client.call(
            "mgr.checkpoint_metadata", {"rank": rank}, _ms(timeout)
        )
        return resp["checkpoint_metadata"]

    def should_commit(
        self,
        rank: int,
        step: int,
        should_commit: bool,
        timeout: timedelta,
        digest: Optional[str] = None,
        epoch: int = -1,
        fence: bool = False,
    ) -> bool:
        """``digest`` piggybacks the divergence sentinel's post-reduce
        state digest on this existing vote RPC (zero extra round trips);
        the manager server folds the group's rank digests and reports
        them to the lighthouse's (epoch, step) cohort compare. With
        ``fence`` the lighthouse arbitrates BEFORE the decision
        publishes — a digest mismatch vetoes the commit. The reply's
        divergence flag is latched on :attr:`last_divergence` (the
        Manager reads it after the call; a tuple return would break the
        bool contract every existing caller relies on)."""
        from torchft_tpu import telemetry
        from torchft_tpu.faultinject.core import fault_point

        # vote-RPC injection: `delay` is a synthetic commit-barrier RTT,
        # `error` a lost vote
        fault_point(
            "commit.vote", match="rpc", rank=rank, step=step,
        )
        req: Dict[str, Any] = {
            "rank": rank,
            "step": step,
            "should_commit": should_commit,
            "trace": telemetry.TRACER.inject(),
        }
        if digest is not None:
            req["digest"] = digest
            req["epoch"] = epoch
            req["fence"] = fence
        with telemetry.TRACER.span(
            "should_commit_rpc", rank=rank, step=step, vote=should_commit
        ):
            resp = self._client.call(
                "mgr.should_commit", req, _ms(timeout)
            )
        self.last_divergence = bool(resp.get("divergence", False))
        return resp["should_commit"]

    def kill(self, msg: str = "", timeout: timedelta = timedelta(seconds=10)) -> None:
        self._client.call("mgr.kill", {"msg": msg}, _ms(timeout))

    def evict(
        self, victim: str, timeout: timedelta = timedelta(seconds=5)
    ) -> bool:
        """Report ``victim`` (a replica_id seen dead on the data plane) for
        immediate eviction. The manager forwards to the lighthouse, which
        probes the victim's manager address before expiring its heartbeat —
        a false report about a live peer is a no-op. Returns whether the
        victim was actually evicted."""
        resp = self._client.call("mgr.evict", {"victim": victim}, _ms(timeout))
        return bool(resp.get("evicted", False))

    def close(self) -> None:
        self._client.close()


class LighthouseClient:
    """Direct lighthouse client — heartbeat + quorum (LighthouseService
    analogue). The Manager server normally does this for you; exposed for
    tests and tooling."""

    def __init__(self, addr: str, connect_timeout: timedelta) -> None:
        self._client = _native.NativeClient(addr, _ms(connect_timeout))

    def heartbeat(
        self,
        replica_id: str,
        timeout: timedelta = timedelta(seconds=5),
        telemetry_payload: Optional[Dict[str, Any]] = None,
    ) -> None:
        """Heartbeat; ``telemetry_payload`` optionally piggybacks a
        per-replica telemetry summary for the lighthouse's cluster
        aggregation (same shape the Manager sends on quorum traffic)."""
        req: Dict[str, Any] = {"replica_id": replica_id}
        if telemetry_payload:
            req["telemetry"] = telemetry_payload
        self._client.call("lh.heartbeat", req, _ms(timeout))

    def quorum(
        self,
        requester: Dict[str, Any],
        timeout: timedelta,
    ) -> Dict[str, Any]:
        resp = self._client.call("lh.quorum", {"requester": requester}, _ms(timeout))
        return resp["quorum"]

    def evict(
        self,
        reporter: str,
        victim: str,
        timeout: timedelta = timedelta(seconds=5),
    ) -> bool:
        """Direct eviction report (see :meth:`ManagerClient.evict`)."""
        resp = self._client.call(
            "lh.evict", {"reporter": reporter, "victim": victim}, _ms(timeout)
        )
        return bool(resp.get("evicted", False))

    def digest(
        self,
        replica_id: str,
        epoch: int,
        step: int,
        digest: str,
        wait: bool = False,
        cohort: int = 0,
        timeout: timedelta = timedelta(seconds=10),
    ) -> Dict[str, Any]:
        """Report one replica's commit-time state digest to the
        lighthouse's (epoch, step) cohort compare (the divergence
        sentinel's RPC — normally the manager server does this from the
        vote barrier). ``wait`` long-polls until the full cohort
        reported (``cohort`` overrides the quorum size for tooling);
        returns ``{"match", "divergence", "reports"}``."""
        req: Dict[str, Any] = {
            "replica_id": replica_id,
            "epoch": epoch,
            "step": step,
            "digest": digest,
            "wait": wait,
        }
        if cohort:
            req["cohort"] = cohort
        return self._client.call("lh.digest", req, _ms(timeout))

    def close(self) -> None:
        self._client.close()
