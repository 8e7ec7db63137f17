"""Kill harness: SIGKILL one of N replica groups, respawn it, and keep the
books on the survivor's blackout and the rejoiner's time to first commit.

The product's claim is "lose at most one step when a group dies"; the
reference never measures it (its envelope lives in test assertions,
lighthouse_test.py:44-47, manager_integ_test.py:325-368). This harness
exercises it with real process kills, for the soak tests
(tests/test_recovery.py, tests/test_telemetry.py); the benchmark's kill
cell (ROADMAP S6/R3) will take its bookkeeping from here:

* the replica groups run as **subprocesses** (numpy data plane over
  ``CollectivesTcp`` — hardware-independent, jax-free workers),
* at a chosen step, the last group takes SIGKILL (no cleanup, no goodbye —
  its manager server and heartbeats die with it),
* it is respawned fresh and heals from a survivor.

Bookkeeping (seconds on this host's clock — a CPU run: counts and
ordering are the result, the seconds are only bounded loosely by tests):

* ``survivor_blackout_s`` — last commit before the kill → first commit
  after it, on the surviving group. Covers dead-peer detection (socket
  deadline), the latched-error flush re-quorum, and the split-brain
  guard's wait for the victim's heartbeat lease to lapse.
* ``rejoin_to_commit_s`` — respawn exec → the rejoiner's first committed
  step, covering store bootstrap, quorum join, live checkpoint heal, and
  one training step.
* ``survivor_failed_attempts`` — the product's claim as a count: the
  step attempts of the surviving group that did not commit (a vote that
  came back "no", or a deadline the worker retried after), from its last
  commit before the kill to its first commit of a step the victim can
  have had no part in (two past the victim's last). A count says how
  many steps went, not how long one blocked: run with a lease and a join
  wait longer than ``timeout_s`` and only an eviction lets the run end,
  and ``evictions_total`` / ``requorum_without_victim`` say it happened.
* ``steady_step_s`` — median healthy step time, so the blackout can also
  be read in step units (``blackout_steps``).

The detection cadence is configurable; the defaults here use aggressive
1 s leases (the reference's defaults — 5 s heartbeat timeout, 60 s op
timeout — bound the same path, just slower).
"""

from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import tempfile
import time
from dataclasses import dataclass
from typing import Dict, List, Optional

__all__ = ["measure_recovery", "RecoveryResult"]


# ---------------------------------------------------------------------------
# worker (subprocess entry: python -m torchft_tpu.faultinject.recovery)
# ---------------------------------------------------------------------------


def _emit(log, **event) -> None:
    event["t"] = time.time()
    log.write(json.dumps(event) + "\n")
    log.flush()


def _go_file(event_log: str) -> str:
    """The file the orchestrator makes, beside the event logs, once every
    group has started."""
    return os.path.join(os.path.dirname(event_log), "go")


def _worker() -> None:
    """Numpy-only FT training loop; commits are timestamped to the event
    log. Deliberately jax-free so killing it never disturbs an
    accelerator held by the parent process."""
    from datetime import timedelta

    import numpy as np

    from torchft_tpu.collectives import CollectivesTcp
    from torchft_tpu.manager import Manager

    if os.environ.get("TORCHFT_BENCH_DEBUG"):
        import logging

        logging.basicConfig(
            level=logging.DEBUG,
            format="%(asctime)s.%(msecs)03d %(name)s: %(message)s",
            datefmt="%H:%M:%S",
        )

    gid = int(os.environ["REPLICA_GROUP_ID"])
    num_groups = int(os.environ["NUM_REPLICA_GROUPS"])
    total_steps = int(os.environ["TORCHFT_BENCH_STEPS"])
    step_sleep = float(os.environ.get("TORCHFT_BENCH_STEP_SLEEP", "0.05"))
    op_timeout = float(os.environ.get("TORCHFT_BENCH_OP_TIMEOUT", "1.0"))
    log = open(os.environ["TORCHFT_EVENT_LOG"], "a")

    # Pre-import jax.tree_util on a side thread: the heal decode path
    # (serialization._tree_util) pays this import on first use, and for
    # this numpy-only worker that lands INSIDE rejoin-to-commit — the
    # heal-stage ledger named it as the dominant decode cost. Starting
    # the import now overlaps it with store bootstrap + quorum join
    # (network waits release the GIL), pulling it off the rejoin
    # serial path.
    import threading

    threading.Thread(
        target=lambda: __import__("jax.tree_util"),
        daemon=True,
        name="tft_prewarm_tree",
    ).start()

    params = {"w": np.zeros((256, 256), np.float32), "steps_seen": 0}

    def state_dict() -> Dict[str, object]:
        return {"w": params["w"].copy(), "steps_seen": params["steps_seen"]}

    def load_state_dict(state) -> None:
        params["w"] = np.asarray(state["w"]).copy()
        params["steps_seen"] = int(state["steps_seen"])

    manager = Manager(
        collectives=CollectivesTcp(timeout=timedelta(seconds=op_timeout)),
        load_state_dict=load_state_dict,
        state_dict=state_dict,
        min_replica_size=1,
        replica_id=f"group{gid}_",
        rank=0,
        world_size=1,
        timeout=timedelta(seconds=op_timeout),
        quorum_timeout=timedelta(seconds=10),
        connect_timeout=timedelta(seconds=10),
    )
    _emit(log, event="start", gid=gid, pid=os.getpid())
    # no step before every group heartbeats: a group that ran ahead alone
    # would be killed with no peer holding a socket to it, to evict it
    while not os.path.exists(_go_file(os.environ["TORCHFT_EVENT_LOG"])):
        time.sleep(0.01)
    rng = np.random.default_rng(gid)
    heal_stats_seen: Dict[str, object] = {}
    try:
        # past ``total_steps`` the groups step on until all of them reduce
        # together again: survivors that ran out of steps while a respawn
        # was still starting would leave it nobody to rejoin
        while (
            manager.current_step() < total_steps
            or manager.num_participants() < num_groups
        ):
            try:
                manager.start_quorum()
                time.sleep(step_sleep)  # the "forward/backward" of the toy step
                grad = rng.standard_normal(params["w"].shape).astype(np.float32)
                manager.allreduce(grad).wait()
                committed = manager.should_commit()
            except TimeoutError as e:
                # a loaded host can blow the aggressive 1 s deadlines past
                # even the quorum timeout; a real trainer retries the step
                # rather than crashing — so does this worker (the
                # orchestrator's own deadline still bounds a true wedge)
                _emit(log, event="timeout_retry", gid=gid, err=str(e)[:120])
                continue
            if not committed:
                _emit(log, event="abort", gid=gid, step=manager.current_step())
            else:
                params["w"] -= 0.01 * grad
                params["steps_seen"] += 1
                # latch this worker's most recent heal attribution (the
                # multi-source transport fills it; empty pre-heal)
                stats = getattr(
                    manager._checkpoint_transport, "last_heal_stats", None
                )
                if isinstance(stats, dict) and stats.get("stages"):
                    heal_stats_seen = stats
                _emit(
                    log,
                    event="commit",
                    gid=gid,
                    step=manager.current_step(),
                    participants=manager.num_participants(),
                    pid=os.getpid(),
                )
    finally:
        # rejoin-SLO + heal-stage attribution (ISSUE 9): the orchestrator
        # reads these from the rejoiner's log so the envelope numbers
        # come with their per-stage explanation
        try:
            from torchft_tpu import telemetry

            slo = manager._slo.rejoin
            _emit(
                log,
                event="slo",
                gid=gid,
                rejoin_threshold_s=(slo.threshold_s if slo else 0.0),
                rejoin_breached=bool(slo.breached) if slo else False,
                rejoin_breaches=int(slo.breaches) if slo else 0,
            )
            _emit(
                log,
                event="heal_stats",
                gid=gid,
                stats=heal_stats_seen,
                stages=telemetry.LEDGER.heal_stage_snapshot(),
            )
        except Exception:  # noqa: BLE001 — attribution must not fail the run
            pass
        manager.shutdown(wait=False)
        _emit(log, event="exit", gid=gid, pid=os.getpid())
        log.close()


# ---------------------------------------------------------------------------
# orchestrator
# ---------------------------------------------------------------------------


@dataclass
class RecoveryResult:
    survivor_blackout_s: float
    rejoin_to_commit_s: float
    steady_step_s: float
    survivor_failed_attempts: int
    total_steps: int
    # the lighthouse's count of evictions it accepted, and whether the
    # survivor's trail holds a quorum after the kill that the killed
    # incarnation is no member of
    evictions_total: int = 0
    requorum_without_victim: bool = False
    # FT event-trail digest (event kind -> count across all groups) plus
    # the raw per-group trail paths, so the envelope numbers above can be
    # cross-checked against the recorded quorum/heal/peer-death sequence
    ft_events: Optional[Dict[str, int]] = None
    trail_paths: Optional[List[str]] = None
    # unix timestamps of the SIGKILL and the respawn exec — anchors for
    # correlating trail records with the induced failure
    t_kill_unix: float = 0.0
    t_respawn_unix: float = 0.0
    # PR 2: the lighthouse's cluster aggregation captured before teardown —
    # the merged Chrome trace (all replicas, one timeline; open in
    # Perfetto) and the /cluster.json per-replica health snapshot
    merged_trace_path: Optional[str] = None
    cluster: Optional[Dict] = None
    # ISSUE 9: rejoin-to-commit SLO verdict (TORCHFT_SLO_REJOIN_S wired
    # into the workers) + the rejoiner's heal attribution (per-source
    # stripe throughput, meta/recv/decode/device_put stage split)
    rejoin_slo: Optional[Dict] = None
    rejoin_heal: Optional[Dict] = None

    def as_dict(self) -> Dict[str, object]:
        out: Dict[str, object] = {
            "survivor_blackout_s": round(self.survivor_blackout_s, 3),
            "rejoin_to_commit_s": round(self.rejoin_to_commit_s, 3),
            "steady_step_s": round(self.steady_step_s, 4),
            "blackout_steps": round(
                self.survivor_blackout_s / max(self.steady_step_s, 1e-9), 1
            ),
            "survivor_failed_attempts": self.survivor_failed_attempts,
            "evictions_total": self.evictions_total,
            "requorum_without_victim": self.requorum_without_victim,
        }
        if self.rejoin_slo is not None:
            out["rejoin_slo_s"] = self.rejoin_slo.get("rejoin_threshold_s")
            out["slo_breach"] = bool(self.rejoin_slo.get("rejoin_breached"))
        if self.rejoin_heal is not None:
            out["rejoin_heal"] = self.rejoin_heal
        if self.ft_events is not None:
            out["ft_events"] = self.ft_events
        return out


def _spawn(
    gid: int, env_extra: Dict[str, str], num_groups: int = 2
) -> subprocess.Popen:
    from torchft_tpu.store import StoreServer

    store = StoreServer()
    env = dict(os.environ)
    env.update(env_extra)
    # the package may be run from a checkout (no pip install): make it
    # importable in the child no matter the parent's cwd
    pkg_parent = os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (pkg_parent, env.get("PYTHONPATH")) if p
    )
    env.update(
        TORCHFT_STORE_ADDR=store.address(),
        REPLICA_GROUP_ID=str(gid),
        NUM_REPLICA_GROUPS=str(num_groups),
        RANK="0",
        WORLD_SIZE="1",
        # keep children off any accelerator the parent owns
        JAX_PLATFORMS="cpu",
    )
    if os.environ.get("TORCHFT_BENCH_DEBUG"):
        stderr_f = open(env["TORCHFT_EVENT_LOG"] + ".stderr", "ab")
    else:
        stderr_f = subprocess.DEVNULL
    try:
        proc = subprocess.Popen(
            [sys.executable, "-m", "torchft_tpu.faultinject.recovery"],
            env=env,
            stdout=subprocess.DEVNULL,
            stderr=stderr_f,
        )
    finally:
        if stderr_f is not subprocess.DEVNULL:
            stderr_f.close()  # the child keeps its inherited copy
    proc._torchft_store = store  # keep the store alive with the proc
    return proc


def _read_events(path: str) -> List[Dict]:
    # same JSONL contract as the telemetry trail, including tolerance for
    # the torn line a SIGKILLed writer leaves behind — share the parser
    from torchft_tpu.telemetry import read_trail

    return read_trail(path)


def _wait_for(path: str, pred, timeout_s: float, procs=()) -> Dict:
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        for e in _read_events(path):
            if pred(e):
                return e
        for p in procs:
            if p.poll() not in (None, 0):
                raise RuntimeError(f"worker died early (rc={p.poll()})")
        time.sleep(0.02)
    raise TimeoutError("recovery harness: expected event never arrived")


def measure_recovery(
    total_steps: int = 30,
    kill_at_step: int = 8,
    step_sleep: float = 0.05,
    op_timeout: float = 1.0,
    heartbeat_timeout_ms: int = 1000,
    timeout_s: float = 120.0,
    num_groups: int = 2,
    rejoin_slo_s: float = 1.0,
    join_timeout_ms: int = 100,
) -> RecoveryResult:
    """Kill 1 of ``num_groups`` replica groups and measure the envelope
    (``num_groups=4`` is the north-star shape: survive killing 1-of-4
    and re-quorum in < 1 step)."""
    from torchft_tpu.coordination import LighthouseServer

    victim_gid = num_groups - 1
    tmp = tempfile.mkdtemp(prefix="tft_recovery_")
    logs = [os.path.join(tmp, f"g{g}.jsonl") for g in range(num_groups)]
    # each worker's Manager writes its FT event trail here (telemetry
    # module, TORCHFT_EVENT_TRAIL env) — the flight-recorder view of the
    # same kill the wall-clock numbers summarize
    trails = [os.path.join(tmp, f"g{g}.trail.jsonl") for g in range(num_groups)]
    lighthouse = LighthouseServer(
        bind="[::]:0",
        min_replicas=1,
        join_timeout_ms=join_timeout_ms,
        heartbeat_timeout_ms=heartbeat_timeout_ms,
    )
    addr = lighthouse.address().split("//", 1)[-1]
    common = {
        "TORCHFT_LIGHTHOUSE": addr,
        "TORCHFT_BENCH_STEPS": str(total_steps),
        "TORCHFT_BENCH_STEP_SLEEP": str(step_sleep),
        "TORCHFT_BENCH_OP_TIMEOUT": str(op_timeout),
        # hang forensics land next to the trails (flight dumps per pid)
        "TORCHFT_FLIGHT_DIR": tmp,
        # rejoin-to-commit SLO (telemetry/slo.py BurnRateSlo): the
        # rejoiner's Manager evaluates it live; the result reports the
        # latch state next to the measured wall-clock
        "TORCHFT_SLO_REJOIN_S": str(rejoin_slo_s),
    }
    procs: List[Optional[subprocess.Popen]] = [None] * num_groups
    try:
        for g in range(num_groups):
            procs[g] = _spawn(
                g,
                {
                    **common,
                    "TORCHFT_EVENT_LOG": logs[g],
                    "TORCHFT_EVENT_TRAIL": trails[g],
                },
                num_groups,
            )

        for g in range(num_groups):
            _wait_for(
                logs[g], lambda e: e["event"] == "start", timeout_s, procs=procs
            )
        open(_go_file(logs[0]), "w").close()
        # let the victim reach the kill step, in a quorum of all the groups
        _wait_for(
            logs[victim_gid],
            lambda e: e["event"] == "commit"
            and e["step"] >= kill_at_step
            and e["participants"] == num_groups,
            timeout_s,
            procs=procs,
        )
        victim = procs[victim_gid]
        t_kill = time.time()
        victim.send_signal(signal.SIGKILL)
        victim.wait()
        victim._torchft_store.shutdown()

        # respawn the victim fresh (the launcher's restart, done by hand so
        # the respawn time is known exactly)
        t_respawn = time.time()
        procs[victim_gid] = _spawn(
            victim_gid,
            {
                **common,
                "TORCHFT_EVENT_LOG": logs[victim_gid],
                "TORCHFT_EVENT_TRAIL": trails[victim_gid],
            },
            num_groups,
        )

        # survivor's first commit after the kill
        post = _wait_for(
            logs[0],
            lambda e: e["event"] == "commit" and e["t"] > t_kill,
            timeout_s,
            procs=[p for p in procs if p],
        )
        # rejoiner's first commit after respawn
        rejoin = _wait_for(
            logs[victim_gid],
            lambda e: e["event"] == "commit" and e["t"] > t_respawn,
            timeout_s,
            procs=[p for p in procs if p],
        )

        for g, p in enumerate(procs):
            rc = p.wait(timeout=timeout_s)
            if rc != 0:
                # a survivor crashing after the measured commits would
                # otherwise go unnoticed and falsify the envelope
                raise RuntimeError(f"group {g} exited rc={rc}")

        g0_events = _read_events(logs[0])
        g0 = [e for e in g0_events if e["event"] == "commit"]
        pre = [e for e in g0 if e["t"] <= t_kill]
        steady = [b["t"] - a["t"] for a, b in zip(pre, pre[1:])]
        steady_step = sorted(steady)[len(steady) // 2] if steady else step_sleep
        last_pre_t = pre[-1]["t"] if pre else t_kill
        blackout = post["t"] - last_pre_t
        # the "< 1 step" envelope: the survivor's attempts that did not
        # commit, up to its first commit of a step the victim cannot have
        # reduced into (the timestamps only order one file's records)
        victim_last = max(
            (
                e["step"]
                for e in _read_events(logs[victim_gid])
                if e["event"] == "commit" and e["t"] <= t_kill
            ),
            default=kill_at_step,
        )
        clear_t = next(
            (e["t"] for e in g0 if e["step"] >= victim_last + 2), float("inf")
        )
        lost = sum(
            1
            for e in g0_events
            if e["event"] in ("abort", "timeout_retry")
            and last_pre_t < e["t"] < clear_t
        )
        from torchft_tpu.telemetry import read_trail

        ft_events: Dict[str, int] = {}
        for path in trails:
            for rec in read_trail(path):
                kind = rec.get("event", "?")
                ft_events[kind] = ft_events.get(kind, 0) + 1
        g0_quorums = [
            r for r in read_trail(trails[0]) if r.get("event") == "quorum_ready"
        ]
        killed = {
            p
            for r in g0_quorums
            if r["ts"] <= t_kill
            for p in r["participants"]
            if p.startswith(f"group{victim_gid}_")
        }
        requorum_without_victim = any(
            r["ts"] > t_kill and not killed & set(r["participants"])
            for r in g0_quorums
        )
        # snapshot the cluster aggregation while the lighthouse is alive:
        # the merged trace IS the incident timeline (kill -> eviction ->
        # re-quorum -> heal) across every replica
        from torchft_tpu.telemetry.native import (
            fetch_merged_trace,
            poll_cluster,
            poll_lighthouse,
        )

        merged_trace_path = os.path.join(tmp, "cluster_trace.json")
        if fetch_merged_trace(lighthouse.address(), path=merged_trace_path) is None:
            merged_trace_path = None
        cluster = poll_cluster(lighthouse.address())
        status = poll_lighthouse(lighthouse.address()) or {}
        # the rejoiner's SLO verdict + heal attribution: take the LAST
        # slo/heal_stats records in its log — those are the respawned
        # incarnation's (the killed one's records, if any, precede them)
        rejoin_events = _read_events(logs[victim_gid])
        rejoin_slo = next(
            (e for e in reversed(rejoin_events) if e["event"] == "slo"), None
        )
        rejoin_heal = None
        hs = next(
            (
                e
                for e in reversed(rejoin_events)
                if e["event"] == "heal_stats" and e.get("t", 0) > t_respawn
            ),
            None,
        )
        if hs is not None and (hs.get("stats") or hs.get("stages")):
            stats = hs.get("stats") or {}
            rejoin_heal = {
                "mode": stats.get("mode"),
                "bytes": stats.get("bytes"),
                "nsources": stats.get("nsources"),
                "per_source_gbps": {
                    src: s.get("gb_per_sec")
                    for src, s in (stats.get("sources") or {}).items()
                },
                "stages_s": hs.get("stages") or stats.get("stages"),
            }
        return RecoveryResult(
            survivor_blackout_s=blackout,
            rejoin_to_commit_s=rejoin["t"] - t_respawn,
            steady_step_s=steady_step,
            survivor_failed_attempts=lost,
            total_steps=total_steps,
            evictions_total=int(status.get("evictions_total", 0)),
            requorum_without_victim=requorum_without_victim,
            ft_events=ft_events,
            trail_paths=list(trails),
            t_kill_unix=t_kill,
            t_respawn_unix=t_respawn,
            merged_trace_path=merged_trace_path,
            cluster=cluster,
            rejoin_slo=rejoin_slo,
            rejoin_heal=rejoin_heal,
        )
    finally:
        for p in procs:
            if p is not None and p.poll() is None:
                p.kill()
                p.wait()
            if p is not None:
                p._torchft_store.shutdown()
        lighthouse.shutdown()


if __name__ == "__main__":
    _worker()
