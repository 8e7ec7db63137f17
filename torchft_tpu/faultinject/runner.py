"""Fault-injection scenario runner — drives the 2-group example trainer
through a deterministic failure matrix and asserts the end-to-end safety
invariant:

    **no committed step may carry corrupt averages** — survivor parameter
    checksums stay finite and bit-identical across groups, or the step
    must abort/veto/heal instead of committing.

Scenarios (each = one 2-group ``examples/train_bytes.py`` run with a
seeded schedule and/or native env knobs on a designated victim):

* ``kill_allreduce_{cma,tcp,python}`` — the victim dies MID-allreduce on
  each data plane the host path can select (CMA descriptor window /
  striped-TCP hop / python-ring frame send); the runner respawns it and
  the cohort must converge bit-identical.
* ``torn_stripe_tcp`` — a stripe's TCP frame is cut halfway (torn write);
  the victim survives, the step must latch + flush-re-quorum.
* ``torn_cma_pull`` — a CMA pull stops partway (torn read, the ROADMAP
  divergence hypothesis); the partial buffer must never average in.
* ``commit_vote_delay`` — every 3rd should_commit vote delayed 150 ms
  (a slow commit barrier must cost time, never a step).
* ``ckpt_serve_death`` — the victim is killed, and the survivor's first
  checkpoint serve to the healer is cut mid-stream; the heal must retry,
  never stage torn state.

Workers that die WITH injection evidence (``TORCHFT_FAULT_EVIDENCE_DIR``)
are the scenario — they are respawned. A worker death carrying the
documented environmental-corruption signature but NO evidence marks the
scenario ``environmental`` (recorded, not a failure — see ROADMAP open
item). Anything else fails the run.

``--sanitize[=asan|tsan]`` rebuilds the native plane under the named
sanitizer (``make -C native asan``/``tsan``), runs a short matrix with
the sanitized core LD_PRELOAD-loaded into every worker, and fails on any
sanitizer report — ASan is the repeatable form of the ROADMAP's
heap-corruption hunt; TSan is its concurrency complement (the dynamic
side of ``python -m torchft_tpu.analysis``'s static lock rules).

Usage::

    python -m torchft_tpu.faultinject.runner --quick
    python -m torchft_tpu.faultinject.runner --scenario torn_cma_pull
    python -m torchft_tpu.faultinject.runner --sanitize --quick
    python -m torchft_tpu.faultinject.runner --sanitize=tsan --quick
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import shutil
import subprocess
import sys
import tempfile
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

REPO = os.path.normpath(
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", "..")
)
_EXAMPLE = os.path.join(REPO, "examples", "train_bytes.py")

# environmental-corruption catalog — shared with tests/conftest.py via
# the package (running `-m torchft_tpu.faultinject.runner` imports the
# parent package anyway, so this adds no import cost)
from torchft_tpu.faultinject.core import (  # noqa: E402
    CORRUPTION_SIGNAL_RCS,
    ENV_CORRUPTION_SIGNATURES,
    read_evidence,
)


@dataclass
class Scenario:
    name: str
    description: str
    victim_env: Dict[str, str] = field(default_factory=dict)
    survivor_env: Dict[str, str] = field(default_factory=dict)
    common_env: Dict[str, str] = field(default_factory=dict)
    victim_schedule: Optional[dict] = None
    survivor_schedule: Optional[dict] = None
    expect_victim_death: bool = False
    quick: bool = True  # include in the --quick / --sanitize subset


SCENARIOS: List[Scenario] = [
    Scenario(
        name="kill_allreduce_cma",
        description="victim SIGKILLed after publishing a CMA pull "
        "descriptor (peer holds a descriptor into dying memory)",
        victim_env={"TORCHFT_FI_CMA_KILL": "3"},
        expect_victim_death=True,
    ),
    Scenario(
        name="kill_allreduce_tcp",
        description="victim SIGKILLed entering a striped-TCP hop "
        "mid-allreduce",
        common_env={"TORCHFT_DP_CMA": "0"},
        victim_env={"TORCHFT_FI_DP_KILL": "3"},
        expect_victim_death=True,
        quick=False,
    ),
    Scenario(
        name="kill_allreduce_python",
        description="victim SIGKILLed mid-frame-send on the python-ring "
        "plane",
        common_env={"TORCHFT_NATIVE_PLANE": "0"},
        victim_schedule={
            "seed": 1,
            "rules": [
                {"site": "rpc.send", "nth": 4, "action": "kill", "sig": 9}
            ],
        },
        expect_victim_death=True,
        quick=False,
    ),
    Scenario(
        name="torn_stripe_tcp",
        description="a striped-TCP hop is cut after half the payload "
        "(torn write); step must latch + flush, victim survives",
        common_env={"TORCHFT_DP_CMA": "0"},
        victim_env={"TORCHFT_FI_DP_CUT": "3:0.5"},
    ),
    Scenario(
        name="kill_streamed_bucket",
        description="victim SIGKILLed entering a striped hop while the "
        "int8-compressed streamed buckets are in flight — the survivor's "
        "step must latch+flush, and the error-feedback residuals staged "
        "for the doomed step must roll back with the commit lineage "
        "(asserted via final cross-group checksum bit-identity: a leaked "
        "residual would diverge the next committed average)",
        common_env={"TORCHFT_DP_CMA": "0", "TORCHFT_WIRE_CODEC": "int8"},
        victim_env={"TORCHFT_FI_DP_KILL": "3"},
        expect_victim_death=True,
        quick=False,
    ),
    Scenario(
        name="torn_compressed_frame",
        description="a striped hop carrying an int8-compressed frame is "
        "cut after half the payload (torn quantized wire): the receiver "
        "must surface a mid-frame EOF — a partial scale+payload must "
        "never dequantize into a committed average — and the aborted "
        "step's error-feedback residuals must not leak",
        common_env={"TORCHFT_DP_CMA": "0", "TORCHFT_WIRE_CODEC": "int8"},
        victim_env={"TORCHFT_FI_DP_CUT": "3:0.5"},
    ),
    Scenario(
        name="torn_cma_pull",
        description="a CMA pull stops halfway (torn read — the ROADMAP "
        "checksum-divergence hypothesis); partial bytes must never "
        "average into a committed step",
        victim_env={"TORCHFT_FI_CMA_TORN": "3:0.5"},
        # the divergence sentinel rides along: abstain semantics must
        # hold through torn-op aborts (no false latch), and the quick/
        # sanitizer matrix then drives the new lh.digest native path
        # under ASan/TSan (ISSUE 10 acceptance)
        common_env={"TORCHFT_DIVERGENCE_SENTINEL": "1"},
    ),
    Scenario(
        name="postmortem_kill_allreduce",
        description="victim SIGKILLed mid-allreduce (the CMA kill site); "
        "the postmortem tool — from the crash-durable black boxes ALONE — "
        "must name the victim replica, its last in-flight op (allreduce) "
        "and the quorum epoch, with checksums bit-identical after heal "
        "(custom runner: run_postmortem_scenario)",
        victim_env={"TORCHFT_FI_CMA_KILL": "3"},
        expect_victim_death=True,
    ),
    Scenario(
        name="corrupt_divergence",
        description="corrupt(frac) perturbs one replica's finished "
        "allreduce output (collective.complete) — silent, finite, no "
        "error raised: the PR 2 corrupt-commit hole. Three legs (custom "
        "runner run_divergence_scenario): sentinel-only must latch "
        "divergence within one commit of the injection; under "
        "TORCHFT_DIVERGENCE_FENCE=1 the commit must ABORT instead "
        "(checksums stay bit-identical); an equal-length control soak "
        "must latch nothing (digests are bit-identical by construction)",
        common_env={"TORCHFT_DIVERGENCE_SENTINEL": "1"},
        victim_schedule={
            "seed": 6,
            "rules": [
                {
                    "site": "collective.complete",
                    "match": "allreduce",
                    "nth": 5,
                    "action": "corrupt",
                    "frac": 0.05,
                }
            ],
        },
    ),
    Scenario(
        name="commit_vote_delay",
        description="every 3rd commit vote delayed 150ms",
        victim_schedule={
            "seed": 2,
            "rules": [
                {
                    "site": "commit.vote",
                    "match": "rpc",
                    "every": 3,
                    "action": "delay",
                    "ms": 150,
                }
            ],
        },
        quick=False,
    ),
    Scenario(
        name="straggler_group",
        description="+200ms skew injected into group 1's collective "
        "submissions (collective.issue delay); the fleet straggler "
        "detector (local-step p50s piggybacked to the lighthouse, "
        "leave-one-out fleet median baseline) must latch exactly that "
        "group within K fresh observations and emit exactly one latched "
        "straggler_detected event, a no-injection control soak of equal "
        "length must produce zero false positives, and checksums must "
        "stay bit-identical through the skew (custom runner: "
        "run_straggler_scenario)",
        victim_schedule={
            "seed": 4,
            "rules": [
                {
                    "site": "collective.issue",
                    "match": "allreduce",
                    "every": 1,
                    "action": "delay",
                    "ms": 200,
                }
            ],
        },
        quick=False,
    ),
    Scenario(
        name="diagnose_straggler",
        description="+200ms collective.issue delay on group 1 (the "
        "straggler_group signal) plus a 60ms native dp-hop delay on the "
        "same victim: the victim's OWN straggler latch (it hosts a "
        "FleetMonitor under TORCHFT_STRAGGLER_MONITOR=1) must auto-"
        "capture exactly ONE diagnosis bundle into TORCHFT_DIAG_DIR "
        "whose native collapsed stacks show the injected-delay frame "
        "(fi::sleep_ms) dominant in the victim's dp.pump hot stack; the "
        "survivor's engine must capture nothing (remote-subject filter); "
        "an equal-length control soak captures ZERO bundles; checksums "
        "bit-identical through the capture; the bundle round-trips "
        "through `postmortem --bundles` (custom runner: "
        "run_diagnose_scenario; --sanitize runs the same legs with the "
        "jax-free worker to prove the new profiler ASan/TSan-clean)",
        victim_schedule={
            "seed": 8,
            "rules": [
                {
                    "site": "collective.issue",
                    "match": "allreduce",
                    "every": 1,
                    "action": "delay",
                    "ms": 200,
                }
            ],
        },
        # native-layer delay on the same victim: lands inside the dp pump
        # threads, which is exactly where the native sampler must find it
        victim_env={"TORCHFT_FI_DP_DELAY_MS": "60"},
        # forced tcp-striped so the dp plane (and its pump threads) runs
        common_env={"TORCHFT_DP_CMA": "0"},
        quick=False,
    ),
    Scenario(
        name="stripe_heal_peer_death",
        description="3 groups (custom runner): the victim g2 is "
        "SIGKILLed mid-run and respawns into a striped multi-source heal "
        "from the two survivors; survivor g1 is SIGKILLed by the native "
        "blob plane on its first stripe serve (TORCHFT_FI_BLOB_KILL) — "
        "the healer must re-stripe g1's pending ranges over g0 and "
        "complete the heal (composing with the PR 4 ckpt_serve_death "
        "retry), g1 respawns and heals striped itself, and all THREE "
        "groups' final checksums must be finite and bit-identical",
        victim_schedule={
            "seed": 5,
            "rules": [
                {
                    "site": "collective.issue",
                    "match": "allreduce",
                    "nth": 6,
                    "action": "kill",
                    "sig": 9,
                }
            ],
        },
        # forced tcp-striped on every group: a victim death on the CMA
        # plane latches broken-CMA (TCP fallback) on SOME survivors only,
        # and mixed planes mean mixed error-feedback enablement — the
        # state TREES then legitimately differ and the digest check
        # (correctly) excludes the odd source, defeating the scenario's
        # two-source premise
        common_env={"TORCHFT_DP_CMA": "0"},
        # g1 = the stripe-serving survivor: its first blob range serve is
        # during g2's re-heal (bootstrap heals are single-source from the
        # sorted-first group, g0, so g1 serves nothing before the kill)
        survivor_env={"TORCHFT_FI_BLOB_KILL": "1"},
        expect_victim_death=True,
    ),
    Scenario(
        name="ckpt_serve_death",
        description="victim killed mid-run; the survivor's first "
        "checkpoint serve to the healer is cut mid-stream (serve death "
        "mid-heal) — the heal must retry, never stage torn state",
        victim_schedule={
            "seed": 3,
            "rules": [
                {
                    "site": "collective.issue",
                    "match": "allreduce",
                    "nth": 6,
                    "action": "kill",
                    "sig": 9,
                }
            ],
        },
        survivor_schedule={
            "seed": 3,
            "rules": [{"site": "ckpt.serve", "nth": 1, "action": "drop"}],
        },
        expect_victim_death=True,
    ),
]


@dataclass
class Result:
    scenario: str
    status: str  # passed | environmental | failed
    detail: str = ""
    fired: int = 0
    respawns: int = 0
    checksums: Optional[List[str]] = None


# descriptors compiled from model-checker traces by
# `python -m torchft_tpu.analysis.protocol.compile` (ISSUE 20): the
# bare `--compiled` flag replays this checked-in set
COMPILED_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "compiled")


def load_compiled_scenarios(compiled_dir: str) -> List[Scenario]:
    """Compiled-schedule descriptors → scenarios. Non-runnable
    descriptors (unlowered HA coordinates awaiting the Raft wiring) are
    skipped loudly — silently dropping them would read as coverage."""
    out: List[Scenario] = []
    for path in sorted(glob.glob(os.path.join(compiled_dir, "*.json"))):
        with open(path, encoding="utf-8") as f:
            doc = json.load(f)
        if not doc.get("runnable"):
            print(f"--- {doc.get('name', path)}: SKIPPED (not runnable: "
                  f"{len(doc.get('unlowered', []))} unlowered HA "
                  "action(s) — pending the Raft wiring)")
            continue
        out.append(Scenario(
            name=doc["name"],
            description=doc.get("description", ""),
            common_env=dict(doc.get("common_env", {})),
            victim_schedule=doc.get("victim_schedule"),
            survivor_schedule=doc.get("survivor_schedule"),
            expect_victim_death=bool(doc.get("expect_victim_death")),
            quick=False,
        ))
    return out


def _env_signature(text: str) -> Optional[str]:
    for sig in ENV_CORRUPTION_SIGNATURES:
        if sig in text:
            return sig
    return None


def _spawn(gid: int, lighthouse_addr: str, workdir: str, steps: int,
           env_extra: Dict[str, str],
           argv: Optional[List[str]] = None,
           num_groups: int = 2) -> subprocess.Popen:
    env = dict(os.environ)
    env.update(
        REPLICA_GROUP_ID=str(gid),
        NUM_REPLICA_GROUPS=str(num_groups),
        STEPS=str(steps),
        BATCH="4",
        DATA_PATH=os.path.join(workdir, "corpus.bin"),
        TRACE_PATH=os.path.join(workdir, f"trace{gid}.jsonl"),
        TORCHFT_LIGHTHOUSE=lighthouse_addr,
        JAX_PLATFORMS="cpu",
        TORCHFT_FAULT_EVIDENCE_DIR=os.path.join(workdir, "evidence"),
        TORCHFT_EVENT_TRAIL=os.path.join(workdir, f"trail{gid}.jsonl"),
        # every worker keeps a crash-durable black box: scenario failures
        # auto-collect them into a postmortem report (ISSUE 10), and the
        # postmortem_kill_allreduce scenario asserts on them directly
        TORCHFT_BLACKBOX_DIR=os.path.join(workdir, "blackbox"),
    )
    env.update(env_extra)
    log = open(
        os.path.join(workdir, f"g{gid}.log"), "ab", buffering=0
    )
    return subprocess.Popen(
        argv or [sys.executable, _EXAMPLE],
        env=env,
        stdout=log,
        stderr=subprocess.STDOUT,
        cwd=REPO,
    )


def _read_log(workdir: str, gid: int) -> str:
    try:
        with open(os.path.join(workdir, f"g{gid}.log"), "rb") as f:
            return f.read().decode(errors="replace")
    except OSError:
        return ""


def _worker_env(scn: Scenario, gid: int, respawn: bool = False
                ) -> Dict[str, str]:
    env = dict(scn.common_env)
    schedule = scn.survivor_schedule if gid == 0 else scn.victim_schedule
    env.update(scn.survivor_env if gid == 0 else scn.victim_env)
    if schedule is not None:
        env["TORCHFT_FAULT_SCHEDULE"] = json.dumps(schedule)
    if respawn:
        # injections fire in the FIRST incarnation only: occurrence
        # counters are per-process, so a respawned victim would re-arm
        # the same nth coordinates and die at the same point forever.
        # Plane-selection env (TORCHFT_DP_CMA etc.) stays.
        env.pop("TORCHFT_FAULT_SCHEDULE", None)
        for k in [k for k in env if k.startswith("TORCHFT_FI_")]:
            env.pop(k)
    return env


def run_scenario(scn: Scenario, workdir: str, steps: int = 16,
                 timeout_s: float = 600.0,
                 extra_env: Optional[Dict[str, str]] = None,
                 worker_argv: Optional[List[str]] = None) -> Result:
    """One 2-group run under the scenario's schedule; victim = group 1.

    ``extra_env``/``worker_argv`` are the sanitize hooks: the ASan env
    (TORCHFT_NATIVE_LIB + LD_PRELOAD) must reach ONLY the workers — the
    runner process itself is uninstrumented, and dlopen'ing the ASan
    core without its preloaded runtime aborts — and the workers must be
    the jax-free ``_san_worker`` (ASan's ``__cxa_throw`` interceptor is
    incompatible with jaxlib's jit tracing)."""
    from torchft_tpu.coordination import LighthouseServer

    os.makedirs(workdir, exist_ok=True)
    evidence_dir = os.path.join(workdir, "evidence")
    os.makedirs(evidence_dir, exist_ok=True)
    # deterministic toy corpus (no numpy needed: repeatable byte pattern)
    with open(os.path.join(workdir, "corpus.bin"), "wb") as f:
        f.write(bytes(range(256)) * 24)

    def worker_env(gid: int, respawn: bool = False) -> Dict[str, str]:
        env = dict(extra_env or {})
        env.update(_worker_env(scn, gid, respawn=respawn))
        return env

    lighthouse = LighthouseServer(bind="[::]:0", min_replicas=2)
    addr = lighthouse.address().split("//", 1)[-1]
    procs = {
        0: _spawn(0, addr, workdir, steps, worker_env(0), worker_argv),
        1: _spawn(1, addr, workdir, steps, worker_env(1), worker_argv),
    }
    respawns = 0
    consumed_kill_pids: set = set()  # evidence already honored by a respawn
    deadline = time.monotonic() + timeout_s
    try:
        while True:
            # classify finished workers BEFORE the all-dead break: a
            # victim whose scheduled kill lands in the same 0.5s poll
            # window the survivor exits in must still be respawned
            for gid, p in list(procs.items()):
                if p.poll() is None or p.returncode == 0:
                    continue
                text = _read_log(workdir, gid)
                kills = [
                    r for r in read_evidence(evidence_dir)
                    if r.get("action") == "kill"
                    and r.get("pid") == p.pid
                    and p.pid not in consumed_kill_pids
                ]
                if kills and respawns < 4:
                    # a scheduled death (kill evidence written by THIS
                    # pid): the respawn IS the scenario. The respawned
                    # worker runs a scrubbed env — see _worker_env — so it
                    # rejoins, heals, and finishes.
                    consumed_kill_pids.add(p.pid)
                    respawns += 1
                    procs[gid] = _spawn(
                        gid, addr, workdir, steps,
                        worker_env(gid, respawn=True), worker_argv,
                    )
                elif _env_signature(text) \
                        or p.returncode in CORRUPTION_SIGNAL_RCS:
                    return Result(
                        scn.name, "environmental",
                        f"g{gid} rc={p.returncode} "
                        f"sig={_env_signature(text)!r} (documented "
                        "pre-existing corruption, no injection evidence)",
                        fired=len(read_evidence(evidence_dir)),
                        respawns=respawns,
                    )
                else:
                    return Result(
                        scn.name, "failed",
                        f"g{gid} rc={p.returncode} not explained by "
                        f"new injection evidence; log tail: "
                        f"{text[-1500:]}",
                        fired=len(read_evidence(evidence_dir)),
                        respawns=respawns,
                    )
            if all(p.poll() is not None for p in procs.values()):
                break  # every worker exited 0 (nonzero handled above)
            if time.monotonic() > deadline:
                return Result(
                    scn.name, "failed",
                    f"timeout after {timeout_s}s "
                    f"(alive: {sorted(g for g, p in procs.items() if p.poll() is None)}, "
                    f"done: { {g: p.returncode for g, p in procs.items() if p.poll() is not None} })",
                    respawns=respawns,
                )
            time.sleep(0.5)
    finally:
        for p in procs.values():
            if p.poll() is None:
                p.kill()
        lighthouse.shutdown()

    fired = read_evidence(evidence_dir)
    sums = []
    for gid in (0, 1):
        text = _read_log(workdir, gid)
        m = re.findall(r"param_checksum=(-?[\d.]+|nan|inf)", text)
        if not m:
            return Result(
                scn.name, "failed",
                f"g{gid} exited 0 but printed no param_checksum; "
                f"log tail: {text[-800:]}",
                fired=len(fired), respawns=respawns,
            )
        sums.append(m[-1])

    # THE invariant: finite and bit-identical across groups — a torn or
    # killed transfer never leaked into a committed average
    if any(s in ("nan", "inf") for s in sums):
        return Result(
            scn.name, "failed",
            f"non-finite committed checksums {sums} — corrupt averages "
            "committed (the divergence mode)",
            fired=len(fired), respawns=respawns, checksums=sums,
        )
    if sums[0] != sums[1]:
        return Result(
            scn.name, "failed",
            f"checksum divergence across groups: {sums}",
            fired=len(fired), respawns=respawns, checksums=sums,
        )
    if (scn.victim_schedule or scn.survivor_schedule or scn.victim_env) \
            and not fired:
        return Result(
            scn.name, "failed",
            "scenario completed but NO injection fired (schedule "
            "coordinates never hit — tighten nth/site)",
            respawns=respawns, checksums=sums,
        )
    if scn.expect_victim_death and respawns == 0:
        return Result(
            scn.name, "failed",
            "expected an injected victim death + respawn; none happened",
            fired=len(fired), checksums=sums,
        )
    return Result(
        scn.name, "passed", f"checksums {sums[0]} == {sums[1]}",
        fired=len(fired), respawns=respawns, checksums=sums,
    )


def run_stripe_heal_scenario(
    scn: Scenario, workdir: str, steps: int = 16, timeout_s: float = 600.0,
    extra_env: Optional[Dict[str, str]] = None,
    worker_argv: Optional[List[str]] = None,
) -> Result:
    """The ``stripe_heal_peer_death`` scenario (ISSUE 9): THREE groups so
    a striped heal has two sources to lose one of.

    Roles: g0 runs clean; g2 (victim) is SIGKILLed mid-allreduce by its
    schedule and respawned (scrubbed env) into a striped heal from
    {g0, g1}; g1 carries ``TORCHFT_FI_BLOB_KILL=1`` — its first native
    blob range serve (which is a stripe of g2's re-heal; bootstrap heals
    are single-source from the sorted-first group g0) SIGKILLs it
    mid-serve. The healer must re-stripe g1's pending ranges over g0 and
    complete the heal; g1 is respawned and heals striped itself. PASS =
    both deaths carry injection evidence, both victims respawned, and all
    three groups exit 0 with finite, bit-identical final checksums.
    Supports ``--sanitize`` (the jax-free numpy worker drives the same
    refactored native stripe/blob layer).

    The lighthouse runs ``min_replicas=3`` (all groups): with the default
    2, the two survivors finish the whole run and EXIT while the
    respawned victim is still booting (a few seconds of interpreter/jax
    import), leaving it alone with an unformable quorum — gating quorum
    formation on the full fleet keeps survivors parked (no commits)
    during each absence, which is also the configuration under which the
    striped heal deterministically has two sources."""
    from torchft_tpu.coordination import LighthouseServer

    os.makedirs(workdir, exist_ok=True)
    evidence_dir = os.path.join(workdir, "evidence")
    os.makedirs(evidence_dir, exist_ok=True)
    with open(os.path.join(workdir, "corpus.bin"), "wb") as f:
        f.write(bytes(range(256)) * 24)

    def worker_env(gid: int, respawn: bool = False) -> Dict[str, str]:
        env = dict(extra_env or {})
        env.update(scn.common_env)
        if gid == 1:
            env.update(scn.survivor_env)
            if scn.survivor_schedule is not None:
                env["TORCHFT_FAULT_SCHEDULE"] = json.dumps(
                    scn.survivor_schedule
                )
        elif gid == 2:
            env.update(scn.victim_env)
            if scn.victim_schedule is not None:
                env["TORCHFT_FAULT_SCHEDULE"] = json.dumps(scn.victim_schedule)
        if respawn:
            env.pop("TORCHFT_FAULT_SCHEDULE", None)
            for k in [k for k in env if k.startswith("TORCHFT_FI_")]:
                env.pop(k)
        return env

    lighthouse = LighthouseServer(bind="[::]:0", min_replicas=3)
    addr = lighthouse.address().split("//", 1)[-1]
    procs = {
        g: _spawn(g, addr, workdir, steps, worker_env(g), worker_argv,
                  num_groups=3)
        for g in (0, 1, 2)
    }
    respawns = 0
    consumed_kill_pids: set = set()
    deadline = time.monotonic() + timeout_s
    try:
        while True:
            for gid, p in list(procs.items()):
                if p.poll() is None or p.returncode == 0:
                    continue
                text = _read_log(workdir, gid)
                kills = [
                    r for r in read_evidence(evidence_dir)
                    if r.get("action") == "kill"
                    and r.get("pid") == p.pid
                    and p.pid not in consumed_kill_pids
                ]
                if kills and respawns < 4:
                    consumed_kill_pids.add(p.pid)
                    respawns += 1
                    procs[gid] = _spawn(
                        gid, addr, workdir, steps,
                        worker_env(gid, respawn=True), worker_argv,
                        num_groups=3,
                    )
                elif _env_signature(text) \
                        or p.returncode in CORRUPTION_SIGNAL_RCS:
                    return Result(
                        scn.name, "environmental",
                        f"g{gid} rc={p.returncode} "
                        f"sig={_env_signature(text)!r}",
                        fired=len(read_evidence(evidence_dir)),
                        respawns=respawns,
                    )
                else:
                    return Result(
                        scn.name, "failed",
                        f"g{gid} rc={p.returncode} not explained by new "
                        f"injection evidence; log tail: {text[-1500:]}",
                        fired=len(read_evidence(evidence_dir)),
                        respawns=respawns,
                    )
            if all(p.poll() is not None for p in procs.values()):
                break
            if time.monotonic() > deadline:
                return Result(
                    scn.name, "failed",
                    f"timeout after {timeout_s}s (alive: "
                    f"{sorted(g for g, p in procs.items() if p.poll() is None)})",
                    respawns=respawns,
                )
            time.sleep(0.5)
    finally:
        for p in procs.values():
            if p.poll() is None:
                p.kill()
        lighthouse.shutdown()

    fired = read_evidence(evidence_dir)
    blob_kills = [
        r for r in fired
        if r.get("action") == "kill" and r.get("site") == "blob.serve"
    ]
    sums = []
    for gid in (0, 1, 2):
        text = _read_log(workdir, gid)
        m = re.findall(r"param_checksum=(-?[\d.]+|nan|inf)", text)
        if not m:
            return Result(
                scn.name, "failed",
                f"g{gid} exited 0 but printed no param_checksum; "
                f"log tail: {text[-800:]}",
                fired=len(fired), respawns=respawns,
            )
        sums.append(m[-1])
    if any(s in ("nan", "inf") for s in sums):
        return Result(
            scn.name, "failed",
            f"non-finite committed checksums {sums}",
            fired=len(fired), respawns=respawns, checksums=sums,
        )
    if len(set(sums)) != 1:
        return Result(
            scn.name, "failed",
            f"checksum divergence across 3 groups: {sums}",
            fired=len(fired), respawns=respawns, checksums=sums,
        )
    if not blob_kills:
        return Result(
            scn.name, "failed",
            "no blob.serve kill evidence — the stripe-serving survivor "
            "was never killed mid-serve (heal too early/late?)",
            fired=len(fired), respawns=respawns, checksums=sums,
        )
    if respawns < 2:
        return Result(
            scn.name, "failed",
            f"expected BOTH the victim and the stripe-serving survivor "
            f"to die+respawn; respawns={respawns}",
            fired=len(fired), respawns=respawns, checksums=sums,
        )
    return Result(
        scn.name, "passed",
        f"3-way checksums identical ({sums[0]}); blob-serve kill + "
        f"re-stripe survived",
        fired=len(fired), respawns=respawns, checksums=sums,
    )


def _final_checksums(workdir: str) -> "tuple[Optional[str], List[str]]":
    """Collect each group's final param_checksum; returns (error, sums) —
    error is a human-readable failure reason or None."""
    sums: List[str] = []
    for gid in (0, 1):
        text = _read_log(workdir, gid)
        m = re.findall(r"param_checksum=(-?[\d.]+|nan|inf)", text)
        if not m:
            return (
                f"g{gid} printed no param_checksum; log tail: {text[-800:]}",
                sums,
            )
        sums.append(m[-1])
    if any(s in ("nan", "inf") for s in sums):
        return (f"non-finite committed checksums {sums}", sums)
    if sums[0] != sums[1]:
        return (f"checksum divergence across groups: {sums}", sums)
    return (None, sums)


def run_straggler_scenario(
    scn: Scenario, workdir: str, steps: int = 16, timeout_s: float = 600.0,
) -> Result:
    """The straggler_group scenario (ISSUE 8 satellite): two legs.

    **Control leg** (runs first) — the soak with no injection; the
    detector must produce ZERO events (the false-positive gate the
    ROADMAP elastic-fleet item needs before staleness-bounded async
    commits can trust the signal). Its final per-replica local-step
    p50s also size the injected leg's skew: the factor-2.0 gate is on
    the *ratio* to the fleet median, so the skew must scale with
    whatever the host's steady step time happens to be that run.

    **Injected leg** — group 1 submits every allreduce ``2x`` the
    measured steady p50 late (floor 200 ms; the ``collective.issue``
    delay site). The runner hosts the fleet detector: a
    :class:`~torchft_tpu.telemetry.slo.FleetMonitor` polls the
    lighthouse's ``/cluster.json`` for the piggybacked local-step p50s
    and feeds a :class:`StragglerDetector` (factor 2.0, K=3 — tight
    enough to latch within the 16-step run, wide enough that scheduler
    jitter between two identical groups can't reach it). Asserts: the
    detector names exactly ``train_bytes_1``, emits exactly ONE latched
    ``straggler_detected`` event, and the final checksums are finite and
    bit-identical across groups (a delay must never corrupt averages).
    """
    from torchft_tpu.coordination import LighthouseServer
    from torchft_tpu.telemetry.slo import FleetMonitor, StragglerDetector

    victim_id = "train_bytes_1"
    detector_cfg = dict(factor=2.0, k=3)

    def leg(
        name: str, inject: bool, delay_ms: Optional[int] = None
    ) -> "tuple[Optional[str], List[Dict], int, Dict[str, float]]":
        """Run one 2-group soak; returns (error, detector_events, fired,
        final per-replica local-step p50s)."""
        wd = os.path.join(workdir, name)
        os.makedirs(wd, exist_ok=True)
        evidence_dir = os.path.join(wd, "evidence")
        os.makedirs(evidence_dir, exist_ok=True)
        with open(os.path.join(wd, "corpus.bin"), "wb") as f:
            f.write(bytes(range(256)) * 24)
        lighthouse = LighthouseServer(bind="[::]:0", min_replicas=2)
        addr = lighthouse.address().split("//", 1)[-1]
        monitor = FleetMonitor(
            lighthouse.address(),
            detector=StragglerDetector(**detector_cfg),
            poll_s=0.25,
        )
        events: List[Dict] = []
        env0 = _worker_env(scn, 0)
        env1 = _worker_env(scn, 1)
        if not inject:
            env1.pop("TORCHFT_FAULT_SCHEDULE", None)
        elif delay_ms is not None:
            # weather-sized skew (see the leg ordering below): patch the
            # schedule's delay in place of the spec's floor value
            sched = json.loads(env1["TORCHFT_FAULT_SCHEDULE"])
            sched["rules"][0]["ms"] = int(delay_ms)
            env1["TORCHFT_FAULT_SCHEDULE"] = json.dumps(sched)
        procs = {
            0: _spawn(0, addr, wd, steps, env0),
            1: _spawn(1, addr, wd, steps, env1),
        }
        deadline = time.monotonic() + timeout_s
        err: Optional[str] = None
        p50s: Dict[str, float] = {}
        try:
            while True:
                # the runner IS the fleet monitor: poll synchronously so
                # the detection sequence is deterministic per leg
                try:
                    events.extend(monitor.poll_once())
                except Exception:  # noqa: BLE001 — scrape races are fine
                    pass
                done = {g: p.poll() for g, p in procs.items()}
                for gid, rc in done.items():
                    if rc is not None and rc != 0:
                        err = (
                            f"{name}: g{gid} rc={rc}; log tail: "
                            f"{_read_log(wd, gid)[-1000:]}"
                        )
                        break
                if err or all(rc is not None for rc in done.values()):
                    break
                if time.monotonic() > deadline:
                    err = f"{name}: timeout after {timeout_s}s"
                    break
                time.sleep(0.25)
            # final per-replica p50s: the control leg's steady step time
            # is what sizes the injected leg's skew
            try:
                from torchft_tpu.telemetry.native import poll_cluster

                cluster = poll_cluster(lighthouse.address()) or {}
                for rid, rec in (cluster.get("replicas") or {}).items():
                    try:
                        p50s[rid] = float(
                            rec.get("local_step_p50_s") or 0.0
                        )
                    except (TypeError, ValueError):
                        pass
            except Exception:  # noqa: BLE001 — best effort
                pass
        finally:
            for p in procs.values():
                if p.poll() is None:
                    p.kill()
            lighthouse.shutdown()
        if err is None:
            cs_err, _sums = _final_checksums(wd)
            if cs_err:
                err = f"{name}: {cs_err}"
        return err, events, len(read_evidence(evidence_dir)), p50s

    # Control leg FIRST: beyond the false-positive gate, it measures the
    # box's steady local-step p50 so the injected skew can be sized
    # RELATIVE to it. The factor-2.0 detector needs p50+skew >= 2x the
    # fleet median — a fixed 200 ms skew that dwarfs an idle box's
    # ~0.15 s steps never crosses the gate on a loaded box running
    # ~0.5 s steps (found as a full-suite-only flake: the detector
    # mathematically could not latch under that day's load).
    ctl_err, ctl_events, _cf, ctl_p50s = leg("control", inject=False)
    if ctl_err:
        return Result(scn.name, "failed", ctl_err)
    if ctl_events:
        return Result(
            scn.name, "failed",
            f"control soak emitted detector events (false positives): "
            f"{ctl_events}",
        )
    steady = sorted(v for v in ctl_p50s.values() if v > 0)
    delay_ms = 200
    if steady:
        # 2x the steady p50 puts the victim's p50 at ~3x the fleet
        # median — comfortably past factor 2.0, while two identical
        # groups' jitter stays far below it
        delay_ms = max(200, int(2000 * steady[len(steady) // 2]))

    err, events, fired, _p50s = leg("injected", inject=True,
                                    delay_ms=delay_ms)
    if err:
        return Result(scn.name, "failed", err, fired=fired)
    detected = [e for e in events if e["event"] == "straggler_detected"]
    if len(detected) != 1:
        return Result(
            scn.name, "failed",
            f"expected exactly one latched straggler_detected, got "
            f"{len(detected)}: {detected}", fired=fired,
        )
    # the Manager appends a uuid4 suffix to every replica_id, so match on
    # the stable example-chosen prefix (2 groups: train_bytes_0 / _1)
    if not detected[0]["group"].startswith(victim_id):
        return Result(
            scn.name, "failed",
            f"detector named {detected[0]['group']!r}, not the skewed "
            f"group {victim_id!r}* ({detected[0]})", fired=fired,
        )
    if fired == 0:
        return Result(
            scn.name, "failed",
            "no injection evidence recorded — the delay never fired",
        )

    return Result(
        scn.name, "passed",
        f"latched {victim_id} once (p50 {detected[0]['p50_s']}s vs "
        f"baseline {detected[0]['baseline_s']}s, {delay_ms}ms skew); "
        f"control soak clean",
        fired=fired,
    )


def run_diagnose_scenario(
    scn: Scenario, workdir: str, steps: int = 24, timeout_s: float = 600.0,
    extra_env: Optional[Dict[str, str]] = None,
    worker_argv: Optional[List[str]] = None,
) -> Result:
    """The ``diagnose_straggler`` scenario (ISSUE 12): detection →
    diagnosis, end to end, in the victim's own process.

    **Injected leg** — group 1 submits every allreduce 200 ms late
    (the straggler signal) AND delays every native dp hop 60 ms
    (``TORCHFT_FI_DP_DELAY_MS`` — the frame the profiler must find).
    BOTH workers host a FleetMonitor (``TORCHFT_STRAGGLER_MONITOR=1``,
    factor 2.0, K=3) and a DiagnosisEngine (``TORCHFT_DIAG_DIR`` →
    one shared fleet dir). The victim's own monitor latches
    ``straggler_detected`` naming itself → its engine captures; the
    survivor's monitor latches the SAME event naming the victim → its
    engine's remote-subject filter drops it. Asserts: exactly ONE
    bundle fleet-wide, written by the victim, whose ``native.folded``
    shows the injected-delay frame (``fi::sleep_ms`` / nanosleep)
    dominant in the victim's ``dp.pump`` hot stack (top stack by count,
    and a majority share of pump samples); the bundle round-trips
    through ``postmortem --bundles``; checksums stay bit-identical.

    **Control leg** — identical env, no injection: ZERO bundles (the
    false-capture gate — an autopilot attaching evidence to an eviction
    must never fire on a healthy fleet).

    Under ``--sanitize`` the same two legs run with the jax-free numpy
    worker and the native profiler at 97 Hz (sampling pressure on the
    SIGPROF handler/seqlock/drain paths under ASan/TSan). The numpy
    worker's raw ``allreduce().wait()`` is not ledger-attributed as a
    barrier phase, so the victim's delay inflates BOTH groups' local
    time and the straggler compare cannot discriminate — the sanitized
    legs trigger through the victim-only step-time SLO instead
    (``TORCHFT_SLO_STEP_S``), which exercises the identical
    latch→capture path; bundle capture is still asserted, but
    stack-dominance is only checked when a native snapshot exists —
    sanitizer scheduling skews sampling too much to gate on
    percentages."""
    from torchft_tpu.coordination import LighthouseServer

    sanitized = worker_argv is not None
    if sanitized:
        # the SLO evaluator's min_events floor (8) sets the earliest
        # possible latch; leave enough post-latch steps for the capture
        # window to finish before the worker exits
        steps = max(steps, 20)
    # the jax-free sanitize worker names its replicas san_worker_<gid>
    victim_id = "san_worker_1" if sanitized else "train_bytes_1"

    def leg(name: str, inject: bool) -> "tuple[Optional[str], str, int]":
        """One 2-group soak; returns (error, leg_diag_dir, fired)."""
        wd = os.path.join(workdir, name)
        os.makedirs(wd, exist_ok=True)
        evidence_dir = os.path.join(wd, "evidence")
        os.makedirs(evidence_dir, exist_ok=True)
        leg_diag = os.path.join(wd, "diag")
        os.makedirs(leg_diag, exist_ok=True)
        with open(os.path.join(wd, "corpus.bin"), "wb") as f:
            f.write(bytes(range(256)) * 24)
        lighthouse = LighthouseServer(bind="[::]:0", min_replicas=2)
        addr = lighthouse.address().split("//", 1)[-1]

        def env_for(gid: int) -> Dict[str, str]:
            env = dict(extra_env or {})
            env.update(_worker_env(scn, gid))
            env.update(
                # one shared fleet dir: "exactly one bundle" is a
                # fleet-wide claim, not a per-process one
                TORCHFT_DIAG_DIR=leg_diag,
                TORCHFT_DIAG_WINDOW_S="1.5",
                TORCHFT_PROF_BURST_HZ="97",
                # every group hosts the detector: the victim must latch
                # ITSELF for the self-capture path to fire
                TORCHFT_STRAGGLER_MONITOR="1",
                TORCHFT_STRAGGLER_FACTOR="2.0",
                TORCHFT_STRAGGLER_K="3",
                TORCHFT_STRAGGLER_POLL_S="0.25",
            )
            if sanitized:
                # sampling pressure on the new native paths is the point
                env["TORCHFT_PROF_HZ"] = "97"
                env["TORCHFT_DIAG_WINDOW_S"] = "0.75"
                # see docstring: the straggler compare can't discriminate
                # in the numpy worker — trigger via the victim-only SLO
                env.pop("TORCHFT_STRAGGLER_MONITOR", None)
                if gid == 1 and inject:
                    env["TORCHFT_SLO_STEP_S"] = "0.01"
            if not inject:
                env.pop("TORCHFT_FAULT_SCHEDULE", None)
                for k in [k for k in env if k.startswith("TORCHFT_FI_")]:
                    env.pop(k)
            return env

        procs = {
            0: _spawn(0, addr, wd, steps, env_for(0), worker_argv),
            1: _spawn(1, addr, wd, steps, env_for(1), worker_argv),
        }
        deadline = time.monotonic() + timeout_s
        err: Optional[str] = None
        try:
            while True:
                done = {g: p.poll() for g, p in procs.items()}
                for gid, rc in done.items():
                    if rc is not None and rc != 0:
                        err = (
                            f"{name}: g{gid} rc={rc}; log tail: "
                            f"{_read_log(wd, gid)[-1000:]}"
                        )
                        break
                if err or all(rc is not None for rc in done.values()):
                    break
                if time.monotonic() > deadline:
                    err = f"{name}: timeout after {timeout_s}s"
                    break
                time.sleep(0.25)
        finally:
            for p in procs.values():
                if p.poll() is None:
                    p.kill()
            lighthouse.shutdown()
        if err is None:
            cs_err, _sums = _final_checksums(wd)
            if cs_err:
                err = f"{name}: {cs_err}"
        return err, leg_diag, len(read_evidence(evidence_dir))

    err, diag_dir_inj, fired = leg("injected", inject=True)
    if err:
        return Result(scn.name, "failed", err, fired=fired)
    if fired == 0:
        return Result(
            scn.name, "failed",
            "no injection evidence recorded — the delay never fired",
        )
    from torchft_tpu.telemetry.diagnosis import read_bundles

    bundles = read_bundles(diag_dir_inj)
    expect_trigger = "slo_breach" if sanitized else "straggler_detected"
    if (len(bundles) != 1) if not sanitized else (len(bundles) < 1):
        return Result(
            scn.name, "failed",
            f"expected exactly one diagnosis bundle fleet-wide, got "
            f"{len(bundles)}: {[b.get('bundle') for b in bundles]}",
            fired=fired,
        )
    b = bundles[0]
    trig = (b.get("trigger") or {}).get("event")
    if trig != expect_trigger:
        return Result(
            scn.name, "failed",
            f"bundle trigger is {trig!r}, not {expect_trigger} ({b})",
            fired=fired,
        )
    replica = str(b.get("replica_id") or "")
    if not replica.startswith(victim_id):
        return Result(
            scn.name, "failed",
            f"bundle written by {replica!r}, not the victim "
            f"{victim_id!r}* — the remote-subject filter failed",
            fired=fired,
        )
    # the diagnosis claim itself: the victim's native hot stack names
    # the injected delay. "Dominant" = the single most-sampled dp.pump
    # stack carries the delay frame AND delay frames hold a majority of
    # the victim's pump samples during the burst window.
    try:
        with open(
            os.path.join(b["_dir"], "native.folded"), encoding="utf-8"
        ) as f:
            folded = f.read()
    except OSError:
        folded = ""
    pump = [
        (line.rpartition(" ")[0], int(line.rpartition(" ")[2]))
        for line in folded.splitlines()
        if line.startswith("dp.pump") and line.rpartition(" ")[2].isdigit()
    ]
    # the HOT stack = samples doing stripe work (run_stripe and below).
    # A wall-clock sampler also sees the pump threads PARKED in their
    # job cond-wait while the python-side issue delay holds the step
    # back — that idleness is ambient truth, not the hot stack, and a
    # flamegraph reader filters it the same way.
    active = [(s, c) for s, c in pump if "run_stripe" in s]
    if active:
        total = sum(c for _s, c in active)
        delayed = sum(
            c for s, c in active if "sleep_ms" in s or "nanosleep" in s
        )
        top_stack = max(active, key=lambda sc: sc[1])[0]
        top_has_delay = "sleep_ms" in top_stack or "nanosleep" in top_stack
        if not top_has_delay or delayed * 2 < total:
            return Result(
                scn.name, "failed",
                f"injected-delay frame not dominant in the victim's "
                f"native hot stack: {delayed}/{total} active pump "
                f"samples, top stack {top_stack[:200]!r}",
                fired=fired,
            )
        dominance = (
            f"{delayed}/{total} active pump samples in the delay frame"
        )
    elif not sanitized:
        return Result(
            scn.name, "failed",
            "bundle carries no active dp.pump native stacks — the burst "
            f"window sampled no stripe work (folded: {folded[:300]!r})",
            fired=fired,
        )
    else:
        dominance = "no active native stacks (sanitizer skew: ok)"
    # round-trip: the postmortem CLI folds the bundle into the causal
    # timeline (latch -> capture -> evidence) from disk alone
    from torchft_tpu.telemetry import postmortem

    report = postmortem.analyze(workdir, bundles_dir=diag_dir_inj)
    caps = [
        r for r in report["timeline"] if r.get("k") == "diagnosis_captured"
    ]
    if not report.get("bundles") or not caps:
        return Result(
            scn.name, "failed",
            "postmortem --bundles did not fold the bundle into the "
            f"timeline (bundles={report.get('bundles')})",
            fired=fired,
        )

    ctl_err, diag_dir_ctl, _ = leg("control", inject=False)
    if ctl_err:
        return Result(scn.name, "failed", ctl_err, fired=fired)
    ctl_bundles = read_bundles(diag_dir_ctl)
    if ctl_bundles:
        return Result(
            scn.name, "failed",
            f"control soak captured {len(ctl_bundles)} bundle(s) — "
            f"false captures: {[b.get('bundle') for b in ctl_bundles]}",
            fired=fired,
        )
    return Result(
        scn.name, "passed",
        f"one bundle by {replica} ({dominance}); postmortem round-trip "
        "ok; control soak captured zero",
        fired=fired,
    )


def run_postmortem_scenario(
    scn: Scenario, workdir: str, steps: int = 16, timeout_s: float = 600.0,
    extra_env: Optional[Dict[str, str]] = None,
    worker_argv: Optional[List[str]] = None,
) -> Result:
    """The ``postmortem_kill_allreduce`` scenario (ISSUE 10): the
    standard mid-allreduce SIGKILL run, then the forensic assertion —
    ``telemetry.postmortem`` pointed at the crash-durable black boxes
    ALONE (not the logs, not the evidence files) must name the victim
    replica, its last in-flight op, and the quorum epoch it died in."""
    res = run_scenario(scn, workdir, steps=steps, timeout_s=timeout_s,
                       extra_env=extra_env, worker_argv=worker_argv)
    if res.status != "passed":
        return res
    from torchft_tpu.telemetry import postmortem

    bb_dir = os.path.join(workdir, "blackbox")
    report = postmortem.analyze(bb_dir)
    victim = report.get("victim") or ""
    # the killed group is gid 1; its replica_id is the example-chosen
    # prefix + a uuid4 suffix — a bare "pid:N" means the boxes never
    # carried replica attribution, which is itself a failure
    if not victim.startswith(("train_bytes_1", "san_worker_1")):
        return Result(
            scn.name, "failed",
            f"postmortem (black boxes alone) named victim {victim!r}, "
            f"expected the killed group 1 replica; report: "
            f"{postmortem.render_text(report)}",
            fired=res.fired, respawns=res.respawns, checksums=res.checksums,
        )
    op = report.get("victim_inflight_op") or {}
    if op.get("op") != "allreduce":
        return Result(
            scn.name, "failed",
            f"postmortem named in-flight op {op!r}, expected an "
            "allreduce (the victim died mid-ring)",
            fired=res.fired, respawns=res.respawns, checksums=res.checksums,
        )
    if not isinstance(report.get("victim_epoch"), int) \
            or report["victim_epoch"] < 0:
        return Result(
            scn.name, "failed",
            f"postmortem recovered no quorum epoch for the victim "
            f"({report.get('victim_epoch')!r})",
            fired=res.fired, respawns=res.respawns, checksums=res.checksums,
        )
    with open(os.path.join(workdir, "evidence", "postmortem.json"),
              "w", encoding="utf-8") as f:
        json.dump(report, f, indent=1, default=str)
    return Result(
        scn.name, "passed",
        f"black boxes alone named victim={victim} inflight="
        f"{op.get('op')} epoch={report['victim_epoch']}; "
        f"checksums {res.checksums[0]} == {res.checksums[1]}",
        fired=res.fired, respawns=res.respawns, checksums=res.checksums,
    )


def run_divergence_scenario(
    scn: Scenario, workdir: str, steps: int = 16, timeout_s: float = 600.0,
    extra_env: Optional[Dict[str, str]] = None,
    worker_argv: Optional[List[str]] = None,
) -> Result:
    """The ``corrupt_divergence`` scenario (ISSUE 10): three legs.

    **sentinel leg** — ``corrupt(frac)`` silently perturbs group 1's
    finished allreduce output once. Nothing errors, the corrupt average
    COMMITS (this is the PR 2 hole) — so final checksums legitimately
    diverge; the assertion is that the lighthouse's commit-time digest
    compare latched (`divergence_total >= 1`) and that a worker trail
    records ``divergence_detected`` within one commit of the
    ``fault_injected`` record.

    **fence leg** — same injection under ``TORCHFT_DIVERGENCE_FENCE=1``:
    the lighthouse arbitrates BEFORE the decision publishes, the corrupt
    commit is vetoed on every group, and final checksums must be finite
    and bit-identical (the corruption never entered committed state).

    **control leg** — equal-length soak, sentinel + fence armed, no
    injection: ``divergence_total`` must be exactly 0 — committed state
    is bit-identical by construction, so any latch here is a false
    positive."""
    import urllib.request

    from torchft_tpu.coordination import LighthouseServer
    from torchft_tpu.telemetry.events import read_trail

    def leg(name: str, inject: bool, fence: bool):
        """Returns (error, lighthouse_status, trails, sums)."""
        wd = os.path.join(workdir, name)
        os.makedirs(wd, exist_ok=True)
        os.makedirs(os.path.join(wd, "evidence"), exist_ok=True)
        with open(os.path.join(wd, "corpus.bin"), "wb") as f:
            f.write(bytes(range(256)) * 24)
        lighthouse = LighthouseServer(bind="[::]:0", min_replicas=2)
        addr = lighthouse.address().split("//", 1)[-1]
        status: Dict = {}
        err: Optional[str] = None
        try:
            procs = {}
            for gid in (0, 1):
                env = dict(extra_env or {})
                env.update(_worker_env(scn, gid))
                if not inject:
                    env.pop("TORCHFT_FAULT_SCHEDULE", None)
                if fence:
                    env["TORCHFT_DIVERGENCE_FENCE"] = "1"
                procs[gid] = _spawn(gid, addr, wd, steps, env, worker_argv)
            deadline = time.monotonic() + timeout_s
            while True:
                done = {g: p.poll() for g, p in procs.items()}
                for gid, rc in done.items():
                    if rc is not None and rc != 0:
                        err = (f"{name}: g{gid} rc={rc}; log tail: "
                               f"{_read_log(wd, gid)[-1000:]}")
                if err or all(rc is not None for rc in done.values()):
                    break
                if time.monotonic() > deadline:
                    err = f"{name}: timeout after {timeout_s}s"
                    break
                time.sleep(0.5)
            # scrape the divergence latch BEFORE the lighthouse dies —
            # the counter lives in the coordinator, not the workers
            try:
                with urllib.request.urlopen(
                    f"http://{addr}/status.json", timeout=5
                ) as resp:
                    status = json.loads(resp.read().decode())
            except Exception as e:  # noqa: BLE001
                err = err or f"{name}: lighthouse scrape failed: {e}"
        finally:
            for p in procs.values():
                if p.poll() is None:
                    p.kill()
            lighthouse.shutdown()
        trails = {
            gid: read_trail(os.path.join(wd, f"trail{gid}.jsonl"))
            for gid in (0, 1)
        }
        sums: List[str] = []
        for gid in (0, 1):
            m = re.findall(
                r"param_checksum=(-?[\d.]+|nan|inf)", _read_log(wd, gid)
            )
            sums.append(m[-1] if m else "")
        return err, status, trails, sums

    # -- sentinel leg: the corrupt average commits, the latch must fire
    err, status, trails, sums = leg("sentinel", inject=True, fence=False)
    if err:
        return Result(scn.name, "failed", err)
    if int(status.get("divergence_total", 0)) < 1:
        return Result(
            scn.name, "failed",
            f"corrupt output committed but the sentinel never latched "
            f"(divergence_total={status.get('divergence_total')})",
        )
    all_events = [r for t in trails.values() for r in t]
    # the trail's fault_injected record carries no step (the plane is
    # step-agnostic), but its BLACK-BOX mirror is stamped with the
    # Manager's step context — read the injection's step coordinate
    # from the crash-durable ring, which is exactly what it is for
    from torchft_tpu.telemetry.postmortem import collect_boxes

    corrupt_steps = [
        r.get("st")
        for b in collect_boxes(os.path.join(workdir, "sentinel", "blackbox"))
        for r in b["records"]
        if r.get("k") == "fault_injected" and r.get("action") == "corrupt"
    ]
    injected_steps = [
        s for s in corrupt_steps if isinstance(s, int) and s >= 0
    ]
    detected = sorted(
        r.get("step", 10**9)
        for r in all_events
        if r.get("event") == "divergence_detected"
    )
    if not detected:
        return Result(
            scn.name, "failed",
            "lighthouse latched but no worker trail carries "
            "divergence_detected (reply flag never surfaced)",
        )
    # "within one commit": the injection fired on the 5th allreduce
    # (~step 4); the latch must be visible by the following commit
    corrupt_step = min(injected_steps) if injected_steps else None
    if corrupt_step is not None and detected[0] > corrupt_step + 1:
        return Result(
            scn.name, "failed",
            f"sentinel latched at step {detected[0]}, more than one "
            f"commit after the injection at step {corrupt_step}",
        )
    if any(s in ("nan", "inf", "") for s in sums):
        return Result(
            scn.name, "failed",
            f"sentinel leg produced non-finite/missing checksums {sums}",
        )

    # -- fence leg: the corrupt commit must abort; checksums identical
    err, status, trails, sums = leg("fence", inject=True, fence=True)
    if err:
        return Result(scn.name, "failed", err)
    if int(status.get("divergence_total", 0)) < 1:
        return Result(
            scn.name, "failed",
            f"fence leg: sentinel never latched "
            f"(divergence_total={status.get('divergence_total')})",
        )
    aborts = [
        r for t in trails.values() for r in t if r.get("event") == "abort"
    ]
    if not aborts:
        return Result(
            scn.name, "failed",
            "fence leg: divergence latched but no abort recorded — the "
            "fence did not veto the corrupt commit",
        )
    if any(s in ("nan", "inf", "") for s in sums) or sums[0] != sums[1]:
        return Result(
            scn.name, "failed",
            f"fence leg: checksums {sums} — the vetoed corruption still "
            "reached committed state",
        )

    # -- control leg: zero false positives (digests identical by
    # construction on every committed step)
    err, status, _trails, sums = leg("control", inject=False, fence=True)
    if err:
        return Result(scn.name, "failed", err)
    if int(status.get("divergence_total", 0)) != 0:
        return Result(
            scn.name, "failed",
            f"control soak FALSE POSITIVE: divergence_total="
            f"{status.get('divergence_total')} with no injection",
        )
    if any(s in ("nan", "inf", "") for s in sums) or sums[0] != sums[1]:
        return Result(
            scn.name, "failed",
            f"control leg checksums {sums}",
        )
    return Result(
        scn.name, "passed",
        f"sentinel latched at step {detected[0]} (corrupt at "
        f"{corrupt_step}); fence aborted with identical checksums "
        f"{sums[0]}; control soak clean",
    )


def check_conformance(workdir: str) -> Optional[str]:
    """Spec-conformance replay of a finished scenario's evidence
    (ISSUE 15): every trail and black box under ``workdir`` is replayed
    against the executable FT-protocol spec, and any illegal transition
    FAILS the scenario — every scenario doubles as a conformance proof.
    Returns the rendered findings (None = conformance-clean)."""
    try:
        from torchft_tpu.analysis.protocol import check_tree

        rep = check_tree(workdir)
    except Exception as e:  # noqa: BLE001 — a broken checker must be loud
        return f"conformance replay itself failed: {e}"
    if rep.ok:
        return None
    return rep.render()


def collect_postmortem(workdir: str, detail: str = "") -> Optional[str]:
    """Auto-forensics on scenario failure: merge the run's black boxes,
    trails and evidence into one postmortem report under the evidence
    dir. Returns the report path (None when nothing could be written) —
    best-effort by design, a broken postmortem must never mask the
    scenario's own failure."""
    try:
        from torchft_tpu.telemetry import postmortem

        evidence_dir = os.path.join(workdir, "evidence")
        os.makedirs(evidence_dir, exist_ok=True)
        logs = []
        for path in sorted(glob.glob(os.path.join(workdir, "g*.log"))):
            try:
                with open(path, errors="replace") as f:
                    logs.append(f.read()[-20000:])
            except OSError:
                pass
        report = postmortem.analyze(workdir, log_text="\n".join(logs))
        report["scenario_detail"] = detail
        out = os.path.join(evidence_dir, "postmortem.json")
        with open(out, "w", encoding="utf-8") as f:
            json.dump(report, f, indent=1, default=str)
        print(f"    postmortem ({report['classification']}): {out}")
        return out
    except Exception as e:  # noqa: BLE001 — forensics must not mask failures
        print(f"    postmortem collection failed: {e}")
        return None


# ---------------------------------------------------------------------------
# sanitizer mode
# ---------------------------------------------------------------------------


def _libsan_path(runtime: str) -> str:
    cxx = os.environ.get("CXX", "g++")
    name = f"lib{runtime}.so"
    out = subprocess.run(
        [cxx, "-print-file-name=" + name],
        capture_output=True, text=True, check=True,
    ).stdout.strip()
    if not out or out == name:
        raise RuntimeError(f"{name} not found (is gcc installed?)")
    return out


def build_sanitized(kind: str) -> str:
    """``make -C native <kind>``; returns the sanitized .so path."""
    subprocess.run(
        ["make", "-C", os.path.join(REPO, "native"), kind], check=True
    )
    lib = os.path.join(
        REPO, "torchft_tpu", "_native", f"libtftcore_{kind}.so"
    )
    assert os.path.exists(lib), lib
    return lib


def sanitize_env(outdir: str, kind: str) -> Dict[str, str]:
    lib = build_sanitized(kind)
    env = {
        "TORCHFT_NATIVE_LIB": lib,
        "LD_PRELOAD": _libsan_path(kind),
    }
    if kind == "asan":
        # leaks are expected from the interpreter itself; we hunt
        # corruption (use-after-free, overflow), not leaks
        env["ASAN_OPTIONS"] = (
            "detect_leaks=0:abort_on_error=1:handle_abort=1:"
            f"log_path={os.path.join(outdir, 'asan')}"
        )
    else:
        # exitcode=0: a report must not kill the worker mid-scenario (the
        # matrix's bit-identity invariant still has to be checked); the
        # gate is the log scan below. Only the native .so is instrumented
        # — the interpreter's own accesses are invisible to TSan, but its
        # pthread mutex/cond use IS intercepted via LD_PRELOAD, so
        # happens-before through the GIL and ctypes boundaries is tracked
        # and native-plane races attribute to instrumented frames.
        env["TSAN_OPTIONS"] = (
            "exitcode=0:report_thread_leaks=0:second_deadlock_stack=1:"
            f"log_path={os.path.join(outdir, 'tsan')}"
        )
    return env


_SAN_REPORT_MARKERS = (
    "ERROR: AddressSanitizer",
    "WARNING: ThreadSanitizer",
    "ERROR: ThreadSanitizer",
    "runtime error:",
)


def scan_san_reports(outdir: str, kind: str) -> List[str]:
    hits = []
    for path in sorted(glob.glob(os.path.join(outdir, f"{kind}.*"))):
        try:
            with open(path, errors="replace") as f:
                text = f.read()
        except OSError:
            continue
        if any(m in text for m in _SAN_REPORT_MARKERS):
            hits.append(path)
    return hits


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="faultinject-runner", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    ap.add_argument("--scenario", action="append", default=None,
                    help="run only these scenarios (repeatable)")
    ap.add_argument("--quick", action="store_true",
                    help="short matrix: the quick-subset scenarios, "
                    "fewer steps")
    ap.add_argument("--sanitize", nargs="?", const="asan", default=None,
                    choices=("asan", "tsan"), metavar="{asan,tsan}",
                    help="rebuild the native plane under the named "
                    "sanitizer (default asan) and fail on any report")
    ap.add_argument("--compiled", nargs="?", const=COMPILED_DIR,
                    default=None, metavar="DIR",
                    help="also run the compiled-schedule descriptors "
                    "under DIR (default: the shipped faultinject/"
                    "compiled set from the model checker); with no "
                    "--scenario/--quick, runs ONLY those")
    ap.add_argument("--steps", type=int, default=None)
    ap.add_argument("--timeout", type=float, default=600.0,
                    help="per-scenario wall-clock cap (seconds)")
    ap.add_argument("--outdir", default=None,
                    help="working dir (default: a fresh temp dir)")
    ap.add_argument("--list", action="store_true", help="list scenarios")
    args = ap.parse_args(argv)

    if args.list:
        for s in SCENARIOS:
            print(f"{s.name:28s} {'[quick] ' if s.quick else '':8s}"
                  f"{s.description}")
        return 0

    outdir = args.outdir or tempfile.mkdtemp(prefix="tft_faultmatrix_")
    os.makedirs(outdir, exist_ok=True)
    steps = args.steps or (10 if (args.quick or args.sanitize) else 16)

    compiled = (
        load_compiled_scenarios(args.compiled) if args.compiled else []
    )
    selected = SCENARIOS
    if args.scenario:
        by_name = {s.name: s for s in SCENARIOS}
        by_name.update({s.name: s for s in compiled})
        unknown = [n for n in args.scenario if n not in by_name]
        if unknown:
            ap.error(f"unknown scenario(s) {unknown}; see --list")
        selected = [by_name[n] for n in args.scenario]
    elif args.quick or args.sanitize:
        selected = [s for s in SCENARIOS if s.quick] + compiled
    elif args.compiled:
        # a bare --compiled runs exactly the compiled tier
        selected = compiled

    extra_env: Optional[Dict[str, str]] = None
    worker_argv: Optional[List[str]] = None
    if args.sanitize:
        # worker-only env: the runner process must NOT load the ASan core
        # (its in-process lighthouse dlopen would abort without the
        # preloaded runtime), and the workers must be jax-free (ASan's
        # __cxa_throw interceptor CHECK-fails in jaxlib's jit tracing) —
        # the numpy worker drives the identical native-plane/RPC/heal
        # path, which is where every corruption suspect lives
        extra_env = sanitize_env(outdir, args.sanitize)
        worker_argv = [
            sys.executable, "-m", "torchft_tpu.faultinject._san_worker"
        ]
        print(f"sanitizer armed ({args.sanitize}): "
              f"{extra_env['TORCHFT_NATIVE_LIB']} (jax-free numpy worker)")

    results: List[Result] = []
    for scn in selected:
        wd = os.path.join(outdir, scn.name)
        shutil.rmtree(wd, ignore_errors=True)
        print(f"--- {scn.name}: {scn.description}")
        t0 = time.monotonic()
        if scn.name == "straggler_group":
            if args.sanitize:
                # the custom runner spawns plain jax workers and does not
                # thread the sanitizer env/argv — claiming a sanitized
                # PASS here would be a lie, so refuse loudly
                ap.error(
                    "straggler_group is not wired for --sanitize (the "
                    "detection loop needs the jax trainer's anatomy "
                    "piggyback); run it unsanitized"
                )
            # custom two-leg runner (injected + control soak) with the
            # fleet detector hosted by the runner process itself
            res = run_straggler_scenario(
                scn, wd, steps=steps, timeout_s=args.timeout
            )
        elif scn.name == "diagnose_straggler":
            # custom two-leg runner (injected + control soak): detection
            # fires IN the victim (it hosts its own FleetMonitor) so the
            # capture path is the production one. Sanitize-capable: same
            # legs with the jax-free worker + the profiler at 97 Hz.
            res = run_diagnose_scenario(
                scn, wd, steps=steps, timeout_s=args.timeout,
                extra_env=extra_env, worker_argv=worker_argv,
            )
        elif scn.name == "stripe_heal_peer_death":
            # custom 3-group runner: a striped heal needs two sources so
            # one can die mid-serve (sanitize-capable — same worker argv)
            res = run_stripe_heal_scenario(
                scn, wd, steps=steps, timeout_s=args.timeout,
                extra_env=extra_env, worker_argv=worker_argv,
            )
        elif scn.name == "postmortem_kill_allreduce":
            # standard kill run + the forensic assertion on the black
            # boxes alone (sanitize-capable — same worker argv)
            res = run_postmortem_scenario(
                scn, wd, steps=steps, timeout_s=args.timeout,
                extra_env=extra_env, worker_argv=worker_argv,
            )
        elif scn.name == "corrupt_divergence":
            # three-leg sentinel/fence/control runner (sanitize-capable)
            res = run_divergence_scenario(
                scn, wd, steps=steps, timeout_s=args.timeout,
                extra_env=extra_env, worker_argv=worker_argv,
            )
        else:
            res = run_scenario(scn, wd, steps=steps, timeout_s=args.timeout,
                               extra_env=extra_env, worker_argv=worker_argv)
        if res.status == "passed":
            # conformance gate (ISSUE 15): a scenario that passed its
            # own assertions must ALSO have produced only protocol-legal
            # lifecycle transitions — an illegal one fails it from now on
            conf = check_conformance(wd)
            if conf is not None:
                res = Result(
                    res.scenario, "failed",
                    f"spec-conformance violation: {conf}",
                    fired=res.fired, respawns=res.respawns,
                    checksums=res.checksums,
                )
        res_s = time.monotonic() - t0
        print(
            f"    {res.status.upper()} in {res_s:.1f}s "
            f"(fired={res.fired} respawns={res.respawns}) {res.detail}"
        )
        if res.status != "passed":
            # auto-forensics (ISSUE 10): a failing or environmental run
            # leaves a merged postmortem report next to its evidence, so
            # triage starts from a reconstructed timeline instead of raw
            # logs — environmental skips become triaged artifacts
            collect_postmortem(wd, detail=res.detail)
        results.append(res)

    report = {
        "steps": steps,
        "sanitize": args.sanitize or False,
        "results": [r.__dict__ for r in results],
    }
    failed = [r for r in results if r.status == "failed"]
    if args.sanitize:
        hits = scan_san_reports(outdir, args.sanitize)
        report["sanitizer_reports"] = hits
        if hits:
            print(f"{args.sanitize.upper()} REPORTS ({len(hits)}):")
            for h in hits:
                print(f"  {h}")
                with open(h, errors="replace") as f:
                    head = f.read(2000)
                print("    " + "\n    ".join(head.splitlines()[:25]))
            failed.append(Result("sanitizer", "failed",
                                 f"{len(hits)} {args.sanitize} report(s)"))
        else:
            print("sanitizer: no reports")
    with open(os.path.join(outdir, "faultmatrix.json"), "w") as f:
        json.dump(report, f, indent=2)
    print(f"report: {os.path.join(outdir, 'faultmatrix.json')}")

    env_skips = [r for r in results if r.status == "environmental"]
    if env_skips:
        print(f"environmental (documented corruption, recorded): "
              f"{[r.scenario for r in env_skips]}")
    if failed:
        print(f"FAILED: {[r.scenario for r in failed]}")
        return 1
    print("fault matrix clean")
    return 0


if __name__ == "__main__":
    sys.exit(main())
