"""Resume-correctness proof for the real-data example (round-2 review
missing #2): kill a replica group mid-epoch, restart it (disk resume +
live heal), and verify from the committed-step traces that no sample was
double-trained and none skipped — the dataloader position really survives
failure.

Reference behavior being matched: train_ddp.py:34-80's stateful dataloader
(torchdata StatefulDataLoader) position checkpointing."""

import json
import os
import signal
import sys
import time

import numpy as np
import pytest

from torchft_tpu.coordination import LighthouseServer

# multi-process soak tier: excluded from the default run (pyproject
# addopts); execute with `pytest -m soak`
from conftest import finish_logged, spawn_logged

pytestmark = pytest.mark.soak

_EXAMPLES = os.path.join(os.path.dirname(__file__), "..", "examples")

STEPS = 24
BATCH = 8


def _spawn(gid, lighthouse_addr, tmp, env_extra=None):
    env = dict(os.environ)
    env.update(
        REPLICA_GROUP_ID=str(gid),
        NUM_REPLICA_GROUPS="2",
        STEPS=str(STEPS),
        BATCH=str(BATCH),
        DATA_PATH=os.path.join(tmp, "corpus.bin"),
        TRACE_PATH=os.path.join(tmp, f"trace{gid}.jsonl"),
        CKPT_DIR=os.path.join(tmp, "ckpt"),
        CKPT_EVERY="3",
        TORCHFT_LIGHTHOUSE=lighthouse_addr,
        JAX_PLATFORMS="cpu",
    )
    if env_extra:
        env.update(env_extra)
    return spawn_logged(
        [sys.executable, os.path.join(_EXAMPLES, "train_bytes.py")], env, tmp, gid
    )


def _trace_lines(path):
    if not os.path.exists(path):
        return []
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


def test_kill_restart_no_sample_skipped_or_repeated(tmp_path):
    tmp = str(tmp_path)
    # small real corpus on disk: epochs roll every 2 steps, so the kill is
    # always mid-epoch and resume crosses epoch boundaries repeatedly
    rng = np.random.default_rng(0)
    with open(os.path.join(tmp, "corpus.bin"), "wb") as f:
        f.write(rng.integers(0, 256, 4001, dtype=np.uint8).tobytes())

    lighthouse = LighthouseServer(bind="[::]:0", min_replicas=2)
    addr = lighthouse.address().split("//", 1)[-1]
    procs = {}
    try:
        for g in (0, 1):
            procs[g] = _spawn(g, addr, tmp)

        # wait until the victim has committed a few steps, then SIGKILL
        victim_trace = os.path.join(tmp, "trace1.jsonl")
        deadline = time.time() + 240
        while len(_trace_lines(victim_trace)) < 5:
            assert time.time() < deadline, "victim never made progress"
            assert procs[0].poll() is None and procs[1].poll() is None
            time.sleep(0.5)
        os.kill(procs[1].pid, signal.SIGKILL)
        procs[1].wait()

        # restart: disk-resume + live heal, then run to completion
        procs[1] = _spawn(1, addr, tmp)
        for g in (0, 1):
            finish_logged(procs[g], tmp, g)
    finally:
        for p in procs.values():
            if p.poll() is None:
                p.kill()
        lighthouse.shutdown()

    # ---- the proof ----
    sys.path.insert(0, _EXAMPLES)
    from train_bytes import SEQ, batch_indices  # noqa: E402

    from torchft_tpu.data import DistributedSampler

    corpus_len = os.path.getsize(os.path.join(tmp, "corpus.bin"))
    n_windows = (corpus_len - 1) // SEQ

    all_by_step = {}
    for g in (0, 1):
        lines = _trace_lines(os.path.join(tmp, f"trace{g}.jsonl"))
        assert lines, f"group {g} committed nothing"
        steps = [ln["step"] for ln in lines]
        # each committed step logged exactly once — a double-trained batch
        # (resume too early) would duplicate a step; a skipped position
        # would diverge from the oracle below
        assert len(steps) == len(set(steps)), f"group {g} double-trained: {steps}"
        assert steps == sorted(steps)
        sampler = DistributedSampler(
            n_windows, replica_group=g, num_replica_groups=2, shuffle=True, seed=0
        )
        for ln in lines:
            expect = batch_indices(sampler, ln["step"], BATCH)
            assert ln["ids"] == expect.tolist(), (
                f"group {g} step {ln['step']}: trained wrong samples after "
                f"kill/resume (position drift)"
            )
            all_by_step.setdefault(ln["step"], {})[g] = set(ln["ids"])

    # the survivor covered every step; the victim's only gap is its
    # blackout window (contiguous), never interior repeats
    g0_steps = {ln["step"] for ln in _trace_lines(os.path.join(tmp, "trace0.jsonl"))}
    assert g0_steps == set(range(STEPS))

    # same-epoch partitions are disjoint across groups (no cross-group
    # double-training): check every step both groups committed
    for step, by_group in all_by_step.items():
        if len(by_group) == 2:
            assert not (by_group[0] & by_group[1]), f"overlap at step {step}"
