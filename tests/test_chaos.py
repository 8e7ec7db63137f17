"""Chaos soak: the same replica group SIGKILLed and restarted repeatedly
mid-training; the cohort must keep making progress and end bit-identical.

This is the real-subprocess escalation of the reference's torchelastic
restart emulation (manager_integ_test.py attempts=3, in-thread): three
full process kills, disk resume + live heal each time, no step skipped or
double-trained (trace-verified like tests/test_data_example.py).

These soaks race wall clocks; for DETERMINISTIC failure placement (kill a
peer mid-allreduce on a chosen plane, tear a CMA pull at a chosen byte,
delay a chosen commit vote) use the seeded fault-injection plane instead:
``torchft_tpu/faultinject/`` + ``pytest -m faultmatrix`` +
``python -m torchft_tpu.faultinject.runner`` — see
``docs/fault_injection.md``."""

import json
import os
import re
import signal
import sys
import time
import pytest

import numpy as np

from torchft_tpu.coordination import LighthouseServer

# multi-process soak tier: excluded from the default run (pyproject
# addopts); execute with `pytest -m soak`
from conftest import finish_logged, spawn_logged

pytestmark = pytest.mark.soak

_EXAMPLES = os.path.join(os.path.dirname(__file__), "..", "examples")

STEPS = 36
BATCH = 8
KILLS = 3


# host-plane chaos matrix (round-4 review #10): the same randomized
# kill/restart schedule on every transport the host plane can select —
# CMA pulls (default on one host), the striped C++ TCP ring, and the
# pure-python ring fallback. The device plane (in-process) and the
# device-dist cohort-respawn path get their own soaks below.
_PLANES = {
    "native-cma": {},
    "native-tcp": {"TORCHFT_DP_CMA": "0"},
    "python-ring": {"TORCHFT_NATIVE_PLANE": "0"},
}


def _spawn(gid, lighthouse_addr, tmp, plane_env=None):
    env = dict(os.environ)
    env.update(
        REPLICA_GROUP_ID=str(gid),
        NUM_REPLICA_GROUPS="2",
        STEPS=str(STEPS),
        BATCH=str(BATCH),
        DATA_PATH=os.path.join(tmp, "corpus.bin"),
        TRACE_PATH=os.path.join(tmp, f"trace{gid}.jsonl"),
        CKPT_DIR=os.path.join(tmp, "ckpt"),
        CKPT_EVERY="2",
        TORCHFT_LIGHTHOUSE=lighthouse_addr,
        JAX_PLATFORMS="cpu",
    )
    env.update(plane_env or {})
    return spawn_logged(
        [sys.executable, os.path.join(_EXAMPLES, "train_bytes.py")], env, tmp, gid
    )


def _trace_steps(path):
    if not os.path.exists(path):
        return []
    with open(path) as f:
        return [json.loads(line)["step"] for line in f if line.strip()]


@pytest.mark.parametrize("plane", sorted(_PLANES))
def test_repeated_kill_restart_converges(tmp_path, plane):
    tmp = str(tmp_path)
    rng = np.random.default_rng(0)
    with open(os.path.join(tmp, "corpus.bin"), "wb") as f:
        f.write(rng.integers(0, 256, 4001, dtype=np.uint8).tobytes())

    plane_env = _PLANES[plane]
    lighthouse = LighthouseServer(bind="[::]:0", min_replicas=2)
    addr = lighthouse.address().split("//", 1)[-1]
    procs = {
        0: _spawn(0, addr, tmp, plane_env),
        1: _spawn(1, addr, tmp, plane_env),
    }
    victim_trace = os.path.join(tmp, "trace1.jsonl")
    try:
        for round_i in range(KILLS):
            # wait until the victim has committed a few more steps
            target = len(_trace_steps(victim_trace)) + 3
            deadline = time.time() + 240
            while len(_trace_steps(victim_trace)) < target:
                if procs[1].poll() is not None or procs[0].poll() is not None:
                    break  # someone finished early (tiny run): stop killing
                assert time.time() < deadline, f"no progress in round {round_i}"
                time.sleep(0.5)
            if procs[1].poll() is not None:
                break
            os.kill(procs[1].pid, signal.SIGKILL)
            procs[1].wait()
            procs[1] = _spawn(1, addr, tmp, plane_env)

        outs = {}
        for g in (0, 1):
            outs[g] = finish_logged(procs[g], tmp, g)
    finally:
        for p in procs.values():
            if p.poll() is None:
                p.kill()
        lighthouse.shutdown()

    sums = [
        re.search(r"param_checksum=(-?\d+\.\d+)", outs[g]).group(1)
        for g in (0, 1)
    ]
    assert sums[0] == sums[1], sums

    # the survivor committed every step exactly once; the victim never
    # double-trained (steps strictly increasing across all restarts)
    g0 = _trace_steps(os.path.join(tmp, "trace0.jsonl"))
    assert g0 == sorted(set(g0)) and set(g0) == set(range(STEPS))
    g1 = _trace_steps(victim_trace)
    assert g1 == sorted(set(g1)), "victim double-trained a step"


def test_chaos_device_plane_random_failures():
    """The device plane's chaos soak: 2 in-process groups over the 'ft'
    psum (virtual CPU mesh) with a RANDOMIZED failure schedule — a
    SIGKILL has no in-process analogue, so failures are injected
    exceptions + torchelastic-style restart, the reference's own chaos
    model (manager_integ_test.py). Both groups must end bit-identical
    and every scheduled failure must actually have fired."""
    from test_integration import (
        FailureInjector,
        _run_groups,
        assert_rank_states_equal,
    )

    rng = np.random.default_rng(1234)
    total_steps = 10
    # 2 random failures on each group at distinct steps (never the same
    # step on both groups at once: that would lose the step entirely,
    # which is the min_replicas=2 outage case, not the chaos case)
    steps_g1 = sorted(
        int(s) for s in rng.choice(range(1, total_steps - 1), 2, replace=False)
    )
    remaining = [s for s in range(1, total_steps - 1) if s not in steps_g1]
    steps_g0 = sorted(
        int(s) for s in rng.choice(remaining, 2, replace=False)
    )
    injectors = [FailureInjector(), FailureInjector()]
    for s in steps_g0:
        injectors[0].fail_at(0, int(s))
    for s in steps_g1:
        injectors[1].fail_at(0, int(s))

    lighthouse = LighthouseServer(bind="[::]:0", min_replicas=2)
    try:
        results = _run_groups(
            lighthouse,
            injectors,
            train_loop_args={"device_plane": True, "total_steps": total_steps},
        )
    finally:
        lighthouse.shutdown()
    assert_rank_states_equal(results)
    assert injectors[0].count == 2 and injectors[1].count == 2
    assert all(r["step"] >= total_steps for group in results for r in group)


_CHAOS_DD_WORKER = r"""
import logging, os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import jax
jax.config.update("jax_platforms", "cpu")
sys.path.insert(0, "__REPO__")
import json
from datetime import timedelta
import numpy as np
import optax
from torchft_tpu.checkpointing.collectives_transport import CollectivesTransport
from torchft_tpu.checkpointing.disk import DiskCheckpointer
from torchft_tpu.collectives_device_dist import CollectivesDeviceDist, init_from_env
from torchft_tpu.manager import Manager
from torchft_tpu.optim import ManagedOptimizer
from torchft_tpu.store import StoreServer

workdir = sys.argv[1]
gid = int(os.environ["REPLICA_GROUP_ID"])
logging.basicConfig(
    level=logging.INFO,
    filename=os.path.join(workdir, f"g{gid}.log"),
)
STEPS = 14
assert init_from_env(), "cohort env missing"
collectives = CollectivesDeviceDist(timeout=timedelta(seconds=30))
store = StoreServer()
manager = Manager(
    collectives=collectives,
    load_state_dict=None,
    state_dict=None,
    min_replica_size=2,
    replica_id=f"chaos_dd_{gid}",
    store_addr=store.address(),
    rank=0,
    world_size=1,
    timeout=timedelta(seconds=30),
    checkpoint_transport=CollectivesTransport(
        collectives, timeout=timedelta(seconds=30)
    ),
)
rng = np.random.default_rng(7)
x = rng.standard_normal((256, 16)).astype(np.float32)
y = (x.sum(axis=1) > 0).astype(np.int32)

def loss_fn(params, xb, yb):
    logits = xb @ params["w"] + params["b"]
    return optax.softmax_cross_entropy_with_integer_labels(logits, yb).mean()

opt = ManagedOptimizer(manager, optax.adam(1e-2))
opt.init({
    "w": np.zeros((16, 2), np.float32),
    "b": np.zeros(2, np.float32),
})
# BOTH groups persist: either can be the stale one after a respawn
ckpt = DiskCheckpointer(
    os.path.join(workdir, f"ckpt{gid}"),
    manager,
    state_dict=lambda: {"opt": opt.state_dict()},
    load_state_dict=lambda s: opt.load_state_dict(s["opt"]),
    every=3,
    tag=f"group{gid}",
    is_writer=True,
)
ckpt.restore()
# randomized cohort-kill schedule: incarnation k kills group k%2 at a
# seeded random step, two kills total, third incarnation runs clean
death_file = os.path.join(workdir, "deaths.txt")
deaths = 0
if os.path.exists(death_file):
    deaths = len(open(death_file).read().splitlines())
die_step = None
if deaths < 2 and gid == deaths % 2:
    die_step = int(np.random.default_rng(100 + deaths).integers(4, 10))
value_and_grad = jax.jit(jax.value_and_grad(loss_fn))
import time
prev = manager.current_step()
while manager.current_step() < STEPS:
    idx = rng.integers(0, len(x), 32)
    opt.begin_step()
    loss, grads = value_and_grad(opt.params, x[idx], y[idx])
    opt.step(grads)
    if manager.current_step() == prev:
        time.sleep(0.2)
    prev = manager.current_step()
    ckpt.maybe_save()
    if die_step is not None and manager.current_step() >= die_step:
        with open(death_file, "a") as f:
            f.write(f"g{gid}@{manager.current_step()}\n")
        os._exit(1)
checksum = float(
    sum(float(np.asarray(v).sum()) for v in opt.params.values())
)
with open(os.path.join(workdir, f"g{gid}.json"), "w") as f:
    json.dump({"step": manager.current_step(), "checksum": checksum}, f)
manager.shutdown(wait=False)
store.shutdown()
"""


def test_chaos_device_dist_cohort_respawn(tmp_path):
    """Device-dist chaos: randomized kills of ALTERNATING cohort members
    under --shared-runtime semantics. Each kill forces a whole-cohort
    respawn (static multi-controller membership); the staler group heals
    live over the plane's CollectivesTransport each time; the run must
    finish with bit-identical params after 2 kills."""
    from torchft_tpu.launcher import launch_shared_runtime

    REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    worker = tmp_path / "worker.py"
    worker.write_text(_CHAOS_DD_WORKER.replace("__REPO__", REPO))
    lighthouse = LighthouseServer(bind="[::]:0", min_replicas=2)
    env_save = dict(os.environ)
    os.environ["TORCHFT_LIGHTHOUSE"] = lighthouse.address()
    try:
        rc = launch_shared_runtime(
            [sys.executable, str(worker), str(tmp_path)],
            num_groups=2,
            max_restarts=3,
        )
    finally:
        os.environ.clear()
        os.environ.update(env_save)
        lighthouse.shutdown()
    assert rc == 0
    deaths = (tmp_path / "deaths.txt").read_text().splitlines()
    assert len(deaths) == 2, deaths
    # both victims were exercised (alternating schedule)
    assert {d.split("@")[0] for d in deaths} == {"g0", "g1"}, deaths
    r0, r1 = (
        json.load(open(tmp_path / f"g{g}.json")) for g in range(2)
    )
    assert r0["step"] == 14 and r1["step"] == 14, (r0, r1)
    assert r0["checksum"] == r1["checksum"], (r0, r1)
