"""Job launcher — the TorchX/torchrun analogue.

Reference: torchft/torchx.py:11-76 (N replica-group roles, each under
``torchrun --max_restarts=10``) driven by .torchxconfig. TPU deployments
have no torchrun; this supervisor fills both roles for single-host runs
and documents the env contract for cluster schedulers:

    TORCHFT_LIGHTHOUSE   lighthouse host:port
    TORCHFT_STORE_ADDR   per-replica-group KV store host:port
    REPLICA_GROUP_ID     group index
    NUM_REPLICA_GROUPS   total groups
    RANK / WORLD_SIZE    rank within the group

Each replica group gets its own StoreServer and worker subprocesses; a
group whose worker dies is torn down and relaunched whole (the reference's
torchelastic restart, which its integration tests emulate with
``attempts=3``) up to ``--max-restarts`` times. The lighthouse is spawned
automatically unless an address is given.

A TPU chip belongs to one process at a time, so on a host with chips
every worker is handed its own disjoint set (:func:`chip_env`; a
respawned group gets the same set back). The supervisor itself never
touches JAX — a parent that had initialised a backend would hold the
chips its children need.

CLI::

    python -m torchft_tpu.launcher --groups 2 --nproc 1 -- \
        python examples/train_ddp.py
"""

from __future__ import annotations

import argparse
import logging
import os
import subprocess
import sys
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence

logger = logging.getLogger(__name__)

__all__ = ["launch", "launch_shared_runtime", "main", "chip_env", "host_chips"]

# PCI ids of TPU chips (the scan jax's own cloud_tpu_init does before it
# loads libtpu): vendor Google, devices v3 / v4 / v5p / v5e / v6e / 7x
_GOOGLE_PCI_VENDOR = "0x1ae0"
_TPU_PCI_DEVICES = frozenset(
    ("0x0027", "0x005e", "0x0062", "0x0063", "0x006f", "0x0076")
)
# libtpu's TPU_CHIPS_PER_PROCESS_BOUNDS (x,y,z) for a process holding n
# consecutive chips. Found on a v5e 2x2 host (libtpu 0.0.34): chips 0,1
# and 2,3 are neighbours along y — "1,2,1" starts, "2,1,1" dies at
# backend init. The 8-chip shape is by extension, not run.
_CHIP_BOUNDS = {1: "1,1,1", 2: "1,2,1", 4: "2,2,1", 8: "2,4,1"}
_TPU_BASE_PORT = 8476  # libtpu's default TPU_PROCESS_PORT


def host_chips(env: Optional[Dict[str, str]] = None) -> List[int]:
    """Ids of the TPU chips this launcher may hand out — without loading
    libtpu or JAX. ``JAX_PLATFORMS`` naming no tpu → none; an inherited
    ``TPU_VISIBLE_CHIPS`` → that subset (an outer scheduler already
    split the host); else the TPU functions on the PCI bus that have a
    device node."""
    import glob

    env = os.environ if env is None else env
    platforms = env.get("JAX_PLATFORMS", "")
    if platforms and "tpu" not in platforms.split(","):
        return []
    visible = env.get("TPU_VISIBLE_CHIPS")
    if visible:
        return [int(c) for c in visible.split(",") if c.strip()]
    on_bus = 0
    for vendor_path in glob.glob("/sys/bus/pci/devices/*/vendor"):
        try:
            with open(vendor_path) as f:
                if f.read().strip() != _GOOGLE_PCI_VENDOR:
                    continue
            with open(os.path.join(os.path.dirname(vendor_path), "device")) as f:
                on_bus += f.read().strip() in _TPU_PCI_DEVICES
        except OSError:
            continue
    # the bus can list more chips than this machine was given (a one-chip
    # share of a four-chip host shows four): only those with a device node
    # can be opened
    nodes = glob.glob("/dev/accel[0-9]*") or glob.glob("/dev/vfio/[0-9]*")
    return list(range(min(on_bus, len(nodes))))


def chip_env(
    chips: Sequence[int], num_procs: int, procs_per_runtime: int = 1
) -> List[Dict[str, str]]:
    """Per-process libtpu environment giving each of ``num_procs`` workers
    a disjoint, equal set of ``chips``. Pure: no chips → empty dicts (CPU
    runs), one process → empty (it owns the host, libtpu's default).

    ``procs_per_runtime`` consecutive workers form ONE libtpu runtime (a
    ``jax.distributed`` group, ``--nproc`` / ``--shared-runtime``): they
    share ``TPU_PROCESS_BOUNDS`` / ``TPU_PROCESS_ADDRESSES`` and differ in
    ``CLOUD_TPU_TASK_ID``. Otherwise every worker is a runtime of its
    own. Each worker gets its own ``TPU_PROCESS_PORT``; the ports are a
    function of the worker's index so a respawn reuses its predecessor's.
    """
    if not chips or num_procs <= 1:
        return [{} for _ in range(num_procs)]
    if num_procs % procs_per_runtime:
        raise ValueError(
            f"{num_procs} workers do not divide into runtimes of "
            f"{procs_per_runtime}"
        )
    per = max(
        (n for n in _CHIP_BOUNDS if n <= len(chips) // num_procs), default=0
    )
    if per == 0:
        raise ValueError(
            f"{num_procs} workers need a TPU chip each but this host offers "
            f"{len(chips)} ({list(chips)}): a chip belongs to one process"
        )
    if per * procs_per_runtime not in _CHIP_BOUNDS:
        raise ValueError(
            f"no TPU process layout for {procs_per_runtime} workers of "
            f"{per} chips in one runtime"
        )
    envs: List[Dict[str, str]] = []
    for i in range(num_procs):
        first = i - i % procs_per_runtime
        ports = [_TPU_BASE_PORT + first + k for k in range(procs_per_runtime)]
        envs.append(
            {
                "TPU_VISIBLE_CHIPS": ",".join(
                    str(c) for c in chips[i * per : (i + 1) * per]
                ),
                "TPU_CHIPS_PER_PROCESS_BOUNDS": _CHIP_BOUNDS[per],
                "TPU_PROCESS_BOUNDS": _process_bounds(per, procs_per_runtime),
                "TPU_PROCESS_ADDRESSES": ",".join(
                    f"localhost:{p}" for p in ports
                ),
                "TPU_PROCESS_PORT": str(ports[i - first]),
                "CLOUD_TPU_TASK_ID": str(i - first),
            }
        )
    return envs


def _process_bounds(chips_per_proc: int, procs: int) -> str:
    """TPU_PROCESS_BOUNDS: how ``procs`` blocks of ``chips_per_proc`` chips
    tile the block that holds them all."""
    cx, cy, _ = (int(v) for v in _CHIP_BOUNDS[chips_per_proc].split(","))
    tx, ty, _ = (int(v) for v in _CHIP_BOUNDS[chips_per_proc * procs].split(","))
    return f"{tx // cx},{ty // cy},1"


@dataclass
class _Group:
    gid: int
    store: object
    procs: List[subprocess.Popen] = field(default_factory=list)
    restarts: int = 0


def _free_port() -> int:
    # NOTE: bind/close races another process onto the port before rank 0's
    # jax coordinator binds it — rare, and self-healing: the group dies at
    # startup and the supervisor loop respawns it with a fresh port.
    import socket

    with socket.socket() as s:
        s.bind(("", 0))
        return s.getsockname()[1]


def _spawn_group(
    gid: int,
    cmd: Sequence[str],
    num_groups: int,
    nproc: int,
    lighthouse_addr: str,
    base_env: Dict[str, str],
    chip_envs: Sequence[Dict[str, str]],
    cohort_env: Optional[Dict[str, str]] = None,
) -> _Group:
    """``chip_envs``: :func:`chip_env`'s entry for each rank of this group
    (empty dicts off the TPU)."""
    from torchft_tpu.store import StoreServer

    store = StoreServer()
    group = _Group(gid=gid, store=store)
    # multi-process group: hand out a fresh jax coordinator endpoint so the
    # workers form one multi-controller JAX runtime (a group-wide mesh)
    # via parallel.multihost.initialize_group. Single-host launcher →
    # localhost; a cluster scheduler sets TORCHFT_JAX_COORDINATOR to the
    # group's rank-0 host itself.
    coordinator = f"localhost:{_free_port()}" if nproc > 1 else None
    for rank in range(nproc):
        env = dict(base_env)
        env.update(
            TORCHFT_LIGHTHOUSE=lighthouse_addr,
            TORCHFT_STORE_ADDR=store.address(),
            REPLICA_GROUP_ID=str(gid),
            NUM_REPLICA_GROUPS=str(num_groups),
            RANK=str(rank),
            WORLD_SIZE=str(nproc),
        )
        if coordinator is not None:
            env["TORCHFT_JAX_COORDINATOR"] = coordinator
        if cohort_env:
            env.update(cohort_env)
        env.update(chip_envs[rank])
        proc = subprocess.Popen(list(cmd), env=env)
        group.procs.append(proc)
        logger.info(
            "group %d rank %d: pid %d, TPU chips %s",
            gid, rank, proc.pid,
            env.get("TPU_VISIBLE_CHIPS")
            or "not assigned (whatever the process finds)",
        )
    return group


def _teardown_group(group: _Group) -> None:
    for p in group.procs:
        if p.poll() is None:
            p.terminate()
    deadline = time.monotonic() + 5
    for p in group.procs:
        try:
            p.wait(timeout=max(0.1, deadline - time.monotonic()))
        except subprocess.TimeoutExpired:
            p.kill()
    group.store.shutdown()


def launch_shared_runtime(
    cmd: Sequence[str],
    num_groups: int = 2,
    lighthouse_addr: Optional[str] = None,
    max_restarts: int = 10,
    restart_backoff_s: float = 6.0,
) -> int:
    """Run ``cmd`` as ``num_groups`` single-process replica groups joined
    to ONE multi-controller JAX runtime (``CollectivesDeviceDist``: the
    cross-group psum rides ICI). The cohort's membership is static —
    multi-controller JAX cannot lose a member — so failure handling is
    COHORT-grained: any worker death tears down and respawns the whole
    cohort with a fresh coordinator (the k8s Job restart pattern), up to
    ``max_restarts`` cohort restarts. Workers receive
    ``TORCHFT_COHORT_COORDINATOR`` / ``TORCHFT_COHORT_SIZE`` /
    ``TORCHFT_COHORT_ID`` and call
    ``collectives_device_dist.init_from_env()`` before first jax use."""
    lighthouse, lighthouse_addr = _maybe_spawn_lighthouse(
        lighthouse_addr, num_groups
    )
    base_env = dict(os.environ)
    # the cohort is ONE jax runtime: its processes tile the host's chips
    chip_envs = chip_env(host_chips(), num_groups, procs_per_runtime=num_groups)
    groups: List[_Group] = []

    def spawn_cohort() -> None:
        # appends into the shared list so a spawn failure mid-cohort
        # leaves every already-started group visible to the finally block
        coordinator = f"localhost:{_free_port()}"
        cohort_env = {
            "TORCHFT_COHORT_COORDINATOR": coordinator,
            "TORCHFT_COHORT_SIZE": str(num_groups),
        }
        for g in range(num_groups):
            groups.append(
                _spawn_group(
                    g, cmd, num_groups, 1, lighthouse_addr, base_env,
                    chip_envs[g : g + 1],
                    {**cohort_env, "TORCHFT_COHORT_ID": str(g)},
                )
            )

    restarts = 0
    exit_code = 0
    try:
        spawn_cohort()
        while True:
            time.sleep(0.5)
            codes = [p.poll() for g in groups for p in g.procs]
            if all(c == 0 for c in codes):
                logger.info("cohort finished clean")
                break
            if any(c is not None and c != 0 for c in codes):
                logger.warning("cohort worker died (codes %s)", codes)
                for g in groups:
                    _teardown_group(g)
                groups.clear()
                if restarts >= max_restarts:
                    logger.error("cohort exhausted restarts")
                    exit_code = 1
                    break
                restarts += 1
                # let the dead incarnation's heartbeat leases lapse at the
                # lighthouse before the new cohort joins: an immediate
                # respawn forms a quorum that still contains the stale
                # replica_ids, the device-dist plane refuses the cohort
                # mismatch (quorum N+stale vs runtime N), the fresh
                # workers die, and each cycle re-arms the race — the
                # restart budget burns without ever converging. Default
                # sits just above the lighthouse's 5 s default lease.
                logger.info(
                    "restarting cohort (restart %d/%d) after %.1fs lease "
                    "backoff", restarts, max_restarts, restart_backoff_s,
                )
                time.sleep(restart_backoff_s)
                spawn_cohort()
    except KeyboardInterrupt:
        exit_code = 130
    finally:
        for g in groups:
            _teardown_group(g)
        if lighthouse is not None:
            lighthouse.shutdown()
    return exit_code


def _maybe_spawn_lighthouse(lighthouse_addr: Optional[str], min_replicas: int):
    """Launcher-owned lighthouse when no external address was given;
    returns (server_or_None, host:port)."""
    if lighthouse_addr is not None:
        return None, lighthouse_addr
    from torchft_tpu.coordination import LighthouseServer

    lighthouse = LighthouseServer(bind="[::]:0", min_replicas=min_replicas)
    # address() is http://host:port — the env var carries host:port
    addr = lighthouse.address().split("//", 1)[-1]
    logger.info("spawned lighthouse at %s", addr)
    return lighthouse, addr


def launch(
    cmd: Sequence[str],
    num_groups: int = 2,
    nproc: int = 1,
    lighthouse_addr: Optional[str] = None,
    max_restarts: int = 10,
    min_replicas: Optional[int] = None,
) -> int:
    """Run ``cmd`` as ``num_groups`` fault-tolerant replica groups of
    ``nproc`` workers. Returns the exit code (0 iff every group finished
    clean)."""
    lighthouse, lighthouse_addr = _maybe_spawn_lighthouse(
        lighthouse_addr, min_replicas or num_groups
    )

    base_env = dict(os.environ)
    chip_envs = chip_env(
        host_chips(), num_groups * nproc, procs_per_runtime=nproc
    )

    def spawn(gid: int) -> _Group:
        return _spawn_group(
            gid, cmd, num_groups, nproc, lighthouse_addr, base_env,
            chip_envs[gid * nproc : (gid + 1) * nproc],
        )

    groups = [spawn(g) for g in range(num_groups)]
    exit_code = 0
    min_needed = min_replicas or num_groups
    try:
        while groups:
            time.sleep(0.5)
            for group in list(groups):
                codes = [p.poll() for p in group.procs]
                if all(c == 0 for c in codes):
                    logger.info("group %d finished clean", group.gid)
                    _teardown_group(group)
                    groups.remove(group)
                elif any(c is not None and c != 0 for c in codes):
                    logger.warning(
                        "group %d worker died (codes %s)", group.gid, codes
                    )
                    _teardown_group(group)
                    groups.remove(group)
                    if lighthouse is not None and len(groups) + 1 < min_needed:
                        # this launcher owns the quorum and a respawn plus
                        # every still-running group cannot reach
                        # min_replicas (the peers finished and left): the
                        # respawn could never re-quorum and would hang to
                        # max_restarts. The peers could only finish with
                        # this group in their quorums, so the cohort's
                        # work is complete. (With an external lighthouse,
                        # other launchers' groups may keep the quorum
                        # alive — always respawn then.)
                        logger.info(
                            "group %d died with too few peers left to ever "
                            "re-quorum (%d alive < min_replicas %d); job "
                            "complete, not respawning",
                            group.gid,
                            len(groups) + 1,
                            min_needed,
                        )
                        # the peers finished clean but THIS group crashed at
                        # the tail (e.g. during its final step/checkpoint):
                        # the launcher's 0-iff-every-group-finished-clean
                        # contract still holds (round-2 advisor finding)
                        exit_code = 1
                        continue
                    if group.restarts < max_restarts:
                        fresh = spawn(group.gid)
                        fresh.restarts = group.restarts + 1
                        groups.append(fresh)
                        logger.info(
                            "restarted group %d (restart %d/%d)",
                            group.gid, fresh.restarts, max_restarts,
                        )
                    else:
                        logger.error(
                            "group %d exhausted restarts", group.gid
                        )
                        exit_code = 1
    except KeyboardInterrupt:
        exit_code = 130
    finally:
        for group in groups:
            _teardown_group(group)
        if lighthouse is not None:
            lighthouse.shutdown()
    return exit_code


def k8s_worker(cmd: Sequence[str]) -> int:
    """In-cluster per-pod bootstrap (the pod command the --emit-k8s
    manifests render). Completes the env contract a scheduler can't:

    * pod index 0 hosts the replica group's KV store (and names itself
      the jax coordinator for multi-host groups);
    * every pod resolves both through the index-0 pod's stable DNS
      (``TORCHFT_GROUP_HOST0``, set by the manifest) and execs the
      training command with ``TORCHFT_STORE_ADDR`` /
      ``TORCHFT_JAX_COORDINATOR`` filled in.
    """
    import signal

    from torchft_tpu.k8s import COORD_PORT, STORE_PORT

    rank = int(os.environ.get("RANK", "0") or "0")
    world = int(os.environ.get("WORLD_SIZE", "1"))
    host0 = os.environ.get("TORCHFT_GROUP_HOST0", "localhost")
    # TORCHFT_STORE_PORT=0 → ephemeral (tests / single-pod runs only:
    # peer pods can't guess an ephemeral port)
    port = int(os.environ.get("TORCHFT_STORE_PORT", STORE_PORT))

    env = dict(os.environ)
    env["RANK"] = str(rank)
    store = None
    if rank == 0:
        from torchft_tpu.store import StoreServer

        store = StoreServer(bind=f"[::]:{port}")
        port = store.port
    env["TORCHFT_STORE_ADDR"] = f"{host0}:{port}"
    if world > 1:
        env["TORCHFT_JAX_COORDINATOR"] = f"{host0}:{COORD_PORT}"

    proc = subprocess.Popen(list(cmd), env=env)

    # this bootstrap is container PID 1: forward termination signals so the
    # trainer gets its graceful-shutdown window (checkpoint flush, clean
    # quorum leave) before kubelet's grace period expires
    def _forward(signum, frame):  # noqa: ARG001
        try:
            proc.send_signal(signum)
        except OSError:
            pass

    for sig in (signal.SIGTERM, signal.SIGINT):
        signal.signal(sig, _forward)

    try:
        return proc.wait()
    finally:
        if store is not None:
            store.shutdown()


def main(argv: Optional[Sequence[str]] = None) -> None:
    parser = argparse.ArgumentParser(
        description="Launch N fault-tolerant replica groups of a training script"
    )
    parser.add_argument("--groups", type=int, default=2)
    parser.add_argument("--nproc", type=int, default=1, help="workers per group")
    parser.add_argument("--lighthouse", default=None, help="existing host:port")
    parser.add_argument("--max-restarts", type=int, default=10)
    parser.add_argument("--min-replicas", type=int, default=None)
    parser.add_argument(
        "--shared-runtime",
        action="store_true",
        help="join all groups to ONE multi-controller jax runtime "
        "(CollectivesDeviceDist: cross-group psum rides ICI). Cohort-"
        "grained restarts; requires --nproc 1",
    )
    parser.add_argument(
        "--emit-k8s",
        action="store_true",
        help="print Kubernetes manifests for this topology instead of "
        "launching locally (the TorchX-component analogue, "
        "reference torchx.py:11-76)",
    )
    parser.add_argument(
        "--k8s-worker",
        action="store_true",
        help="internal: in-cluster per-pod bootstrap (store/coordinator "
        "hosting + env completion); used by the emitted manifests",
    )
    parser.add_argument(
        "--k8s-apply",
        action="store_true",
        help="render the manifests and kubectl-apply them (torchx run "
        "analogue; kubectl owns auth/context)",
    )
    parser.add_argument(
        "--k8s-status",
        action="store_true",
        help="print the session's Job/lighthouse status as JSON "
        "(selects on the torchft-session label; use --name)",
    )
    parser.add_argument(
        "--k8s-down",
        action="store_true",
        help="delete every object of the session (label-selected)",
    )
    parser.add_argument(
        "--kubectl", default="kubectl", help="kubectl binary to shell to"
    )
    parser.add_argument("--image", default="IMAGE", help="--emit-k8s: container image")
    parser.add_argument("--name", default="torchft", help="--emit-k8s: resource prefix")
    parser.add_argument("--namespace", default="default")
    parser.add_argument(
        "--tpu-accelerator", default=None, help="--emit-k8s: GKE nodeSelector"
    )
    parser.add_argument(
        "--tpu-topology", default=None, help="--emit-k8s: GKE TPU topology"
    )
    parser.add_argument("cmd", nargs=argparse.REMAINDER)
    args = parser.parse_args(argv)
    cmd = args.cmd[1:] if args.cmd and args.cmd[0] == "--" else args.cmd
    logging.basicConfig(level=logging.INFO)
    if args.k8s_status or args.k8s_down:
        # cmd-less verbs: operate on an existing session by name
        import json as _json

        from torchft_tpu.k8s import status, teardown

        if args.k8s_status:
            print(
                _json.dumps(
                    status(
                        args.name,
                        namespace=args.namespace,
                        kubectl=args.kubectl,
                    ),
                    indent=1,
                )
            )
        if args.k8s_down:
            teardown(
                args.name, namespace=args.namespace, kubectl=args.kubectl
            )
        return
    if not cmd:
        parser.error("no command given (use: launcher [opts] -- cmd ...)")
    if args.emit_k8s or args.k8s_apply:
        if args.shared_runtime:
            parser.error("--emit-k8s/--k8s-apply do not support --shared-runtime yet: "
                         "the manifests would lack the TORCHFT_COHORT_* "
                         "wiring and workers would silently fall back to "
                         "per-group runtimes")
        from torchft_tpu.k8s import emit_manifests, submit

        manifests = emit_manifests(
            cmd,
            name=args.name,
            image=args.image,
            num_groups=args.groups,
            nproc=args.nproc,
            min_replicas=args.min_replicas,
            max_restarts=args.max_restarts,
            namespace=args.namespace,
            tpu_accelerator=args.tpu_accelerator,
            tpu_topology=args.tpu_topology,
        )
        if args.k8s_apply:
            submit(manifests, namespace=args.namespace, kubectl=args.kubectl)
        else:
            print(manifests, end="")
        return
    if args.k8s_worker:
        sys.exit(k8s_worker(cmd))
    if args.shared_runtime:
        if args.nproc != 1:
            parser.error("--shared-runtime requires --nproc 1 (one jax "
                         "runtime per process)")
        if args.min_replicas is not None:
            parser.error("--shared-runtime is cohort-grained: membership "
                         "is static, --min-replicas does not apply")
        sys.exit(
            launch_shared_runtime(
                cmd,
                num_groups=args.groups,
                lighthouse_addr=args.lighthouse,
                max_restarts=args.max_restarts,
            )
        )
    sys.exit(
        launch(
            cmd,
            num_groups=args.groups,
            nproc=args.nproc,
            lighthouse_addr=args.lighthouse,
            max_restarts=args.max_restarts,
            min_replicas=args.min_replicas,
        )
    )


if __name__ == "__main__":
    main()
