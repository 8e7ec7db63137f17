import collections
import os

import pytest

# Force JAX onto a virtual 8-device CPU mesh for all tests: multi-chip
# sharding is validated without TPU hardware (the driver separately
# dry-run-compiles the multi-chip path via __graft_entry__.dryrun_multichip).
os.environ["JAX_PLATFORMS"] = "cpu"

# Persistent XLA compilation cache, shared with every worker subprocess
# the soak tests spawn (they inherit the env): the chaos/recovery tiers
# respawn the same toy models dozens of times and each respawn otherwise
# recompiles from scratch — on the 2-core CI box that recompile tax alone
# pushes the full 'not slow' tier against its wall-clock budget.
# The driver's machine starts WITHOUT this directory: its run compiles every
# program once, where a builder's /tmp holds earlier sessions' (so a tier-1
# time is quoted with this variable on an empty directory, or not at all).
# A test's program runs once or a few times, so XLA does not optimise it
# (backend level 0, LLVM's expensive passes off): the lowered text and its
# digests are what they were, a cold run loses a quarter of its seconds. A
# test that pins what an OPTIMISED program computes turns the flag back on
# for itself (`jax_disable_most_optimizations`, read at each compile).
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", "/tmp/tft_jax_cache")
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0.5")
os.environ.setdefault("JAX_DISABLE_MOST_OPTIMIZATIONS", "1")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# Let in-process tests exercise the kill RPC without nuking pytest.
os.environ.setdefault("TORCHFT_TPU_SOFT_KILL", "1")

# Subprocess timeout scaling: caps tuned on a multi-core box flake on a
# 1-core one under contention (round-3 review weak #5 — a 240s example
# run hit TimeoutExpired while a bench ran). Scale by core count so red
# means bug, not busy box.
_CPUS = os.cpu_count() or 1
SUBPROC_TIMEOUT_SCALE = 1 if _CPUS >= 4 else (2 if _CPUS >= 2 else 4)


def scaled_timeout(seconds: float) -> float:
    return seconds * SUBPROC_TIMEOUT_SCALE


def _child_log(tmp, gid) -> str:
    return os.path.join(tmp, f"out{gid}.log")


def spawn_logged(cmd, env, tmp, gid):
    """Start a trainer whose output goes to a file, not a pipe: tests that
    poll a trace drain nothing until the end, and a child that says more
    than a pipe holds (XLA logs two long lines per executable it loads
    from a warm compile cache) would block in write() forever."""
    import subprocess

    with open(_child_log(tmp, gid), "ab") as out:
        return subprocess.Popen(cmd, env=env, stdout=out, stderr=subprocess.STDOUT)


def finish_logged(proc, tmp, gid) -> str:
    """Wait for a :func:`spawn_logged` trainer to exit 0; returns
    everything group ``gid`` printed (all its incarnations)."""
    proc.wait(timeout=scaled_timeout(300))
    with open(_child_log(tmp, gid), errors="replace") as f:
        out = f.read()
    assert proc.returncode == 0, out[-2000:]
    return out


# The environmental-corruption catalog (ROADMAP open item, PR 2
# post-mortem) lives in torchft_tpu/faultinject/core.py so the scenario
# runner and this test tier recognize the same signatures; multi-process
# soaks skip — not fail — on them, so red means NEW bug, not the
# documented one. Imported lazily: conftest must not pull the package
# (and its native auto-build) in before the env fixtures run.


def known_corruption_signature(text: str):
    """Return the matched known-corruption signature in ``text``, or None."""
    from torchft_tpu.faultinject.core import ENV_CORRUPTION_SIGNATURES

    for sig in ENV_CORRUPTION_SIGNATURES:
        if sig in text:
            return sig
    return None


def injected_kill_evidence(evidence_dir=None):
    """Fired kill/torn records from the fault-injection plane's evidence
    files (``TORCHFT_FAULT_EVIDENCE_DIR``). A worker that died because a
    SCHEDULED injection killed it writes this record before dying — both
    the Python engine (faultinject/core.py) and the native plane
    (native/faultinject.h) use the same directory and JSONL shape."""
    from torchft_tpu.faultinject.core import read_evidence

    return [
        r
        for r in read_evidence(evidence_dir)
        if r.get("action") in ("kill", "torn", "drop")
    ]


def skip_if_known_corruption(
    text: str, rcs=(), nan_checksums: bool = False, evidence_dir=None
):
    """One policy for every multi-process soak: ``pytest.skip`` when a
    failure carries the documented pre-existing corruption evidence — a
    known signature in ``text``, a signal-class return code in ``rcs``,
    or (opt-in) the all-nan-checksum divergence form. Returns normally
    when the failure looks like a NEW bug, so the caller re-raises.

    Injection evidence WINS over a signature match: a worker killed by a
    scheduled fault-injection (SIGKILL shows up as rc -9/-6-class noise
    and can segfault jit mid-step, mimicking the environmental signature)
    must never be laundered into a skip — the test scheduled that death
    and must handle or fail it explicitly."""
    import pytest

    from torchft_tpu.faultinject.core import CORRUPTION_SIGNAL_RCS

    if injected_kill_evidence(evidence_dir):
        return

    sig = known_corruption_signature(text)
    if sig is None and any(rc in CORRUPTION_SIGNAL_RCS for rc in rcs):
        sig = f"signal rc in {sorted(set(rcs))}"
    if sig is None and nan_checksums and "param_checksum=nan" in text:
        # the divergence mode of the same corruption: no crash, but the
        # data plane silently poisoned the averages on every worker
        sig = "param_checksum=nan"
    if sig is not None:
        # Triaged artifact instead of a bare skip (ISSUE 10): when the
        # soak ran with black boxes armed, reconstruct the incident and
        # record the postmortem classification next to the evidence —
        # an environmental-churn skip then leaves a timeline naming the
        # victim and its in-flight op, not just a signature string.
        pm = ""
        try:
            import json

            bb_dir = os.environ.get("TORCHFT_BLACKBOX_DIR") or evidence_dir
            if bb_dir and os.path.isdir(bb_dir):
                from torchft_tpu.telemetry import postmortem

                report = postmortem.analyze(bb_dir, log_text=text)
                out_dir = evidence_dir or bb_dir
                out = os.path.join(out_dir, "postmortem_skip.json")
                with open(out, "w", encoding="utf-8") as f:
                    json.dump(report, f, indent=1, default=str)
                pm = f"; postmortem={report['classification']} -> {out}"
        except Exception:  # noqa: BLE001 — forensics must not fail the skip
            pm = ""
        pytest.skip(
            f"known pre-existing native corruption in a worker ({sig!r})"
            f"{pm}; see ROADMAP open items"
        )


# ---------------------------------------------------------------------------
# One small fault-tolerant job, traced: tests/test_tft_spans.py reads its
# spans' structure, tests/test_telemetry_readers.py that every name the
# benchmark reads is written. Imports are lazy, as above.
# ---------------------------------------------------------------------------

# the smallest bucket the knob allows, so that one step has several
SPANS_BUCKET_BYTES = 1 << 16


def spans_cfg():
    import jax.numpy as jnp

    from torchft_tpu.models.transformer import TransformerConfig

    return TransformerConfig(
        vocab_size=256, d_model=64, n_layers=2, n_heads=2, head_dim=32, d_ff=128,
        dtype=jnp.float32,
    )


@pytest.fixture(scope="module")
def spans_train_step():
    import jax
    import optax

    from torchft_tpu.parallel.mesh import MeshConfig, make_mesh
    from torchft_tpu.parallel.train_step import TrainStep

    mesh = make_mesh(MeshConfig(), devices=jax.devices()[:1])
    return TrainStep(spans_cfg(), optax.adamw(1e-2), mesh)


def run_steps(ts, steps, monkeypatch, around=None, veto_step=None, hold_quorum=False):
    """``steps`` FT steps on a fresh one-group job; returns (losses, checksum).
    ``around(fn)`` runs the stepping inside whatever it sets up; in step
    ``veto_step`` this rank votes against the commit, as a rank whose step
    went wrong would; under ``hold_quorum`` the quorum is asked for only once
    the main thread waits for it, so that a step this small blocks on its
    quorum as a step at size does."""
    import threading
    from datetime import timedelta

    import jax
    import jax.numpy as jnp
    import numpy as np

    from torchft_tpu.collectives import CollectivesTcp
    from torchft_tpu.coordination import LighthouseServer
    from torchft_tpu.manager import Manager
    from torchft_tpu.parallel.ft import FTTrainer
    from torchft_tpu.store import StoreServer

    cfg = spans_cfg()
    monkeypatch.setenv("TORCHFT_WIRE_BUCKET_BYTES", str(SPANS_BUCKET_BYTES))
    lighthouse = LighthouseServer(bind="[::]:0", min_replicas=1)
    store = StoreServer()
    manager = Manager(
        collectives=CollectivesTcp(timeout=timedelta(seconds=10)),
        load_state_dict=None,
        state_dict=None,
        min_replica_size=1,
        replica_id="spans_0",
        store_addr=store.address(),
        lighthouse_addr=lighthouse.address(),
        rank=0,
        world_size=1,
        timeout=timedelta(seconds=10),
    )
    try:
        trainer = FTTrainer(manager, ts)
        trainer.init(jax.random.PRNGKey(0))
        rng = np.random.default_rng(0)
        batches = [
            jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 16)), jnp.int32)
            for _ in range(steps)
        ]

        vote = manager._client.should_commit
        if hold_quorum:
            wait, quorum = manager.wait_quorum, manager._client._quorum
            waited_for = threading.Event()

            def wait_quorum():
                # a step waits more than once; only its first wait blocks
                if not manager._quorum_future.done():
                    waited_for.set()
                return wait()

            def held_quorum(*args, **kwargs):
                assert waited_for.wait(timeout=60), "nobody waited for the quorum"
                waited_for.clear()
                return quorum(*args, **kwargs)

            manager.wait_quorum, manager._client._quorum = wait_quorum, held_quorum

        def drive():
            out = []
            for i, tokens in enumerate(batches):
                manager._client.should_commit = (
                    (lambda rank, step, _vote, **kw: vote(rank, step, False, **kw))
                    if i == veto_step else vote
                )
                loss, committed = trainer.step(tokens)
                assert committed == (i != veto_step)
                out.append(loss)
            jax.block_until_ready(trainer.params)
            return out

        losses = around(drive) if around else drive()
        checksum = sum(
            float(jnp.sum(l)) for l in jax.tree_util.tree_leaves(trainer.params)
        )
        return losses, checksum
    finally:
        manager.shutdown(wait=False)
        store.shutdown()
        lighthouse.shutdown()


Traced = collections.namedtuple(
    "Traced", "lines losses checksum ledger_rows ring_spans"
)


@pytest.fixture(scope="module")
def traced(spans_train_step, tmp_path_factory):
    """Three steps under a profiler session, the quorum beside the compute
    as in production: the host lines of the trace, the losses, the checksum
    and the Tracer's ``exchange`` spans of those steps. Then three more,
    untraced and with the quorum held, for the LEDGER's rows: a step this
    small books a ``quorum_wait`` only when it has to wait."""
    import glob

    import jax

    from torchft_tpu import telemetry
    from torchft_tpu.telemetry import tracing

    trace_dir = str(tmp_path_factory.mktemp("xplane"))
    mp = pytest.MonkeyPatch()

    def around(drive):
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        try:
            return drive()
        finally:
            jax.profiler.stop_trace()

    try:
        losses, checksum = run_steps(spans_train_step, 3, mp, around)
        ring_spans = telemetry.TRACER.recent("exchange", limit=3)
        run_steps(spans_train_step, 3, mp, hold_quorum=True)
        ledger_rows = telemetry.LEDGER.dump()["rows"][-3:]
    finally:
        mp.undo()
    (path,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    lines = []  # one dict per host thread: name -> [(start_ns, end_ns, stats)]
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for line in plane.lines:
            by_name = {}
            for ev in line.events:
                if ev.name.startswith(tracing.TRACE_PREFIX):
                    by_name.setdefault(ev.name[len(tracing.TRACE_PREFIX):], []).append(
                        (ev.start_ns, ev.start_ns + ev.duration_ns, dict(ev.stats))
                    )
            if by_name:
                lines.append(by_name)
    return Traced(lines, losses, checksum, ledger_rows, ring_spans)
