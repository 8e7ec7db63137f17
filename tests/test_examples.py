"""End-to-end example configs: DiLoCo over 4 groups and the
HSDP composition, driven as real subprocesses against an in-process
lighthouse, asserting cross-group state convergence (the reference's
integ-test bar: state-dict equality across groups)."""

import os
import re
import subprocess
import sys
import pytest
from concurrent.futures import ThreadPoolExecutor

from torchft_tpu.coordination import LighthouseServer
from torchft_tpu.store import StoreServer

# multi-process soak tier: excluded from the default run (pyproject
# addopts); execute with `pytest -m soak`
from conftest import scaled_timeout, skip_if_known_corruption

pytestmark = pytest.mark.soak

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_groups(script: str, num_groups: int, extra_env: dict, min_replicas=None):
    lighthouse = LighthouseServer(
        bind="[::]:0", min_replicas=min_replicas or num_groups
    )
    stores = [StoreServer() for _ in range(num_groups)]
    try:

        def run(g):
            env = dict(os.environ)
            env.update(
                TORCHFT_LIGHTHOUSE=lighthouse.address(),
                TORCHFT_STORE_ADDR=stores[g].address(),
                REPLICA_GROUP_ID=str(g),
                NUM_REPLICA_GROUPS=str(num_groups),
                RANK="0",
                WORLD_SIZE="1",
                JAX_PLATFORMS="cpu",
            )
            env.update(extra_env)
            return subprocess.run(
                [sys.executable, os.path.join(REPO, "examples", script)],
                env=env,
                capture_output=True,
                text=True,
                timeout=scaled_timeout(240),
                cwd=REPO,
            )

        with ThreadPoolExecutor(max_workers=num_groups) as pool:
            procs = list(pool.map(run, range(num_groups)))
        if any(p.returncode != 0 for p in procs):
            # Gather ALL workers before judging: one worker dying of the
            # documented pre-existing native corruption (ROADMAP open
            # item) cascades into quorum timeouts on its peers, and only
            # the ROOT death carries the interesting evidence — shared
            # policy in conftest.skip_if_known_corruption.
            skip_if_known_corruption(
                "".join(p.stderr for p in procs),
                rcs=[p.returncode for p in procs],
            )
            bad = next(p for p in procs if p.returncode != 0)
            raise AssertionError(
                f"worker rc={bad.returncode}: {bad.stderr[-3000:]}"
            )
        return [p.stderr + p.stdout for p in procs]
    finally:
        for s in stores:
            s.shutdown()
        lighthouse.shutdown()


def _checksums(logs, pattern=r"param_checksum=(-?\d+\.\d+)"):
    sums = []
    for log in logs:
        m = re.search(pattern, log)
        assert m, log[-2000:]
        sums.append(m.group(1))
    return sums


def test_diloco_four_groups():
    logs = _run_groups(
        "train_diloco.py",
        num_groups=4,
        extra_env={"OUTER_STEPS": "2", "SYNC_EVERY": "2"},
    )
    sums = _checksums(logs)
    # outer steps averaged pseudogradients across all 4 groups: identical
    # outer state everywhere (bit-identical, reference integ-test bar)
    assert len(set(sums)) == 1, sums


def test_hsdp_example_two_groups():
    logs = _run_groups(
        "train_hsdp.py",
        num_groups=2,
        extra_env={
            "STEPS": "3",
            "FSDP": "2",
            "TP": "2",
            # one group = one process with its own devices: each gets a
            # 4-device virtual platform (on the TPU: its own chips)
            "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
        },
    )
    sums = _checksums(logs)
    assert len(set(sums)) == 1, sums


def test_resnet_cifar_two_groups(tmp_path):
    """ResNet-18 CIFAR-10 DDP over two groups — conv model family
    through the full FT loop, bit-identical params across groups."""
    logs = _run_groups(
        "train_cifar.py",
        num_groups=2,
        extra_env={
            "STEPS": "3",
            "BATCH": "8",
            "DATA_PATH": str(tmp_path / "cifar.npz"),
        },
    )
    sums = _checksums(logs)
    assert len(set(sums)) == 1, sums
