"""chip_smoke.py and the two pieces of the bring-up it leans on: the
launcher's chip split and the compile-cache placement. The smoke itself
only passes on a TPU; here its refusal and (slow tier) its orchestration
are checked on the CPU, calling the phase functions with the platform
expectation as an argument — the script's own command line has none."""

import json
import os
import subprocess
import sys

import pytest

from torchft_tpu.launcher import chip_env, host_chips
from torchft_tpu.utils import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)  # chip_smoke.py lives at the root


# -- the launcher's chip split (pure) --


def test_chip_env_no_chips_sets_nothing():
    assert chip_env([], 2, 1) == [{}, {}]
    assert chip_env([], 4, 2) == [{}, {}, {}, {}]


def test_chip_env_one_process_owns_the_host():
    assert chip_env([0, 1, 2, 3], 1, 1) == [{}]


def test_chip_env_two_groups_on_four_chips():
    a, b = chip_env([0, 1, 2, 3], 2, 1)
    assert (a["TPU_VISIBLE_CHIPS"], b["TPU_VISIBLE_CHIPS"]) == ("0,1", "2,3")
    for e in (a, b):
        # each process is a runtime of its own
        assert e["TPU_PROCESS_BOUNDS"] == "1,1,1"
        assert e["CLOUD_TPU_TASK_ID"] == "0"
        assert e["TPU_PROCESS_ADDRESSES"] == f"localhost:{e['TPU_PROCESS_PORT']}"
    assert a["TPU_PROCESS_PORT"] != b["TPU_PROCESS_PORT"]
    # found on the v5e 2x2 host: chips 0,1 / 2,3 pair along y ("2,1,1" dies)
    assert a["TPU_CHIPS_PER_PROCESS_BOUNDS"] == b["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,2,1"


def test_chip_env_is_disjoint_and_deterministic():
    envs = chip_env([0, 1, 2, 3], 4, 1)
    assert [e["TPU_VISIBLE_CHIPS"] for e in envs] == ["0", "1", "2", "3"]
    assert len({e["TPU_PROCESS_PORT"] for e in envs}) == 4
    # a respawned group must get exactly its predecessor's chips and port
    assert envs == chip_env([0, 1, 2, 3], 4, 1)
    # an outer scheduler's subset is split, not the whole host
    assert [e["TPU_VISIBLE_CHIPS"] for e in chip_env([2, 3], 2, 1)] == ["2", "3"]
    # three groups on four chips: one chip each, one idle
    assert [e["TPU_VISIBLE_CHIPS"] for e in chip_env([0, 1, 2, 3], 3, 1)] == ["0", "1", "2"]


def test_chip_env_processes_of_one_runtime_share_its_layout():
    # --nproc 2: two groups x two single-chip processes, each group ONE runtime
    envs = chip_env([0, 1, 2, 3], 4, procs_per_runtime=2)
    for first in (0, 2):
        pair = envs[first : first + 2]
        assert pair[0]["TPU_PROCESS_ADDRESSES"] == pair[1]["TPU_PROCESS_ADDRESSES"]
        assert pair[0]["TPU_PROCESS_ADDRESSES"].count(",") == 1
        assert [e["CLOUD_TPU_TASK_ID"] for e in pair] == ["0", "1"]
        assert pair[0]["TPU_PROCESS_BOUNDS"] == pair[1]["TPU_PROCESS_BOUNDS"] != "1,1,1"
    assert envs[0]["TPU_PROCESS_ADDRESSES"] != envs[2]["TPU_PROCESS_ADDRESSES"]
    # --shared-runtime: all four processes in one runtime
    cohort = chip_env([0, 1, 2, 3], 4, procs_per_runtime=4)
    assert {e["TPU_PROCESS_BOUNDS"] for e in cohort} == {"2,2,1"}
    assert [e["CLOUD_TPU_TASK_ID"] for e in cohort] == ["0", "1", "2", "3"]


def test_chip_env_more_processes_than_chips_is_an_error():
    with pytest.raises(ValueError, match="a chip belongs to one process"):
        chip_env([0], 2, 1)


def test_host_chips_respects_the_platform_and_an_outer_split():
    assert host_chips({"JAX_PLATFORMS": "cpu"}) == []
    assert host_chips({"TPU_VISIBLE_CHIPS": "2,3"}) == [2, 3]
    assert host_chips({"JAX_PLATFORMS": "cpu", "TPU_VISIBLE_CHIPS": "2,3"}) == []


# -- the compile cache resolver --


def test_cache_env_set_is_left_alone(monkeypatch, tmp_path):
    import jax

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    writes = []
    monkeypatch.setattr(
        jax.config, "update", lambda *a, **k: writes.append(a), raising=True
    )
    assert compile_cache.place_compile_cache() == str(tmp_path)
    assert os.environ["JAX_COMPILATION_CACHE_DIR"] == str(tmp_path)
    assert writes == []  # jax reads the variable itself; no code sets another


def test_cache_unset_is_the_fixed_in_checkout_path(monkeypatch):
    import jax

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    writes = []
    monkeypatch.setattr(
        jax.config, "update", lambda *a, **k: writes.append(a), raising=True
    )
    monkeypatch.delenv("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", raising=False)
    want = os.path.join(REPO, ".jax_cache")
    assert compile_cache.place_compile_cache() == want
    assert writes == [
        # a respawn must miss nothing: sub-second compiles are kept too
        ("jax_persistent_cache_min_compile_time_secs", 0.0),
        ("jax_compilation_cache_dir", want),
    ]
    # exported, so children (a respawned group) resolve the same directory
    assert os.environ["JAX_COMPILATION_CACHE_DIR"] == want
    assert os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] == "0"
    assert compile_cache.place_compile_cache() == want
    assert len(writes) == 2  # the second call found both variables set


def test_cache_path_is_the_same_in_another_process():
    env = {k: v for k, v in os.environ.items() if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = REPO
    code = (
        "import os, sys\n"
        "from torchft_tpu.utils.compile_cache import place_compile_cache\n"
        "print(place_compile_cache()); print('jax' in sys.modules)\n"
        "print(os.environ['JAX_COMPILATION_CACHE_DIR'])\n"
    )
    outs = [
        subprocess.run(
            [sys.executable, "-c", code], env=env, cwd=cwd, text=True,
            capture_output=True, timeout=120, check=True,
        ).stdout.split()
        for cwd in (REPO, "/")
    ]
    want = os.path.join(REPO, ".jax_cache")
    # a parent that stays off jax (launcher, chip_smoke.py) only exports it
    assert outs[0] == outs[1] == [want, "False", want]


def test_one_helper_names_the_cache():
    """Acceptance: grep over non-test code finds the single helper."""
    skip = {"tests", "chip_smoke_out", "chiprun_out", "__pycache__"}
    found = []
    for root, dirs, files in os.walk(REPO):
        dirs[:] = [d for d in dirs if d not in skip and not d.startswith(".")]
        for name in files:
            if name.endswith(".py"):
                path = os.path.join(root, name)
                with open(path, encoding="utf-8") as f:
                    if "compilation_cache_dir" in f.read().lower():
                        found.append(os.path.relpath(path, REPO))
    assert found == ["torchft_tpu/utils/compile_cache.py"]


# -- the smoke refuses to pass without a TPU --


def test_smoke_fails_without_a_tpu_and_says_so():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env=env, text=True, capture_output=True, timeout=300,
    )
    assert proc.returncode != 0
    assert "no tpu found" in proc.stdout
    assert "JAX_PLATFORMS='cpu'" in proc.stdout
    assert '"ok"' not in proc.stdout  # no result line


def test_smoke_alone_in_a_directory_fails_at_the_build(tmp_path):
    """Past the probe (a TPU machine), a directory that holds chip_smoke.py
    and nothing else of the repo stops at the native build, with make's
    own words."""
    import importlib.util
    import shutil

    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    spec = importlib.util.spec_from_file_location(
        "chip_smoke_alone", tmp_path / "chip_smoke.py"
    )
    alone = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(alone)
    assert alone.REPO == str(tmp_path)
    with pytest.raises(alone.PhaseFailed, match="(?s)native core build failed.*No such file"):
        alone.build_native()


# -- tiny-size CPU rehearsal of the orchestration (launcher, kill, parsing) --


@pytest.fixture
def smoke(monkeypatch, tmp_path):
    import chip_smoke

    monkeypatch.setattr(chip_smoke, "OUT", str(tmp_path))
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    # tiny programs compile in milliseconds; cache them all the same
    monkeypatch.setenv("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax_cache"))
    return chip_smoke


TINY = dict(model="tiny", batch=8, seq=16)


@pytest.mark.slow
def test_rehearse_probe_steady_cache_and_placement(smoke, monkeypatch, tmp_path):
    monkeypatch.setenv("XLA_FLAGS", "--xla_force_host_platform_device_count=4")
    info = smoke.probe("cpu")
    assert info["count"] == 4
    with pytest.raises(smoke.PhaseFailed, match="no tpu found"):
        smoke.probe("tpu")
    first = smoke.steady("2_steady", "cpu", 4, "cpu", steps=3, **TINY)
    assert first.aborted() == 0 and first.checksum() is not None
    second = smoke.cache_phase(
        first, str(tmp_path / "jax_cache"), "cpu",
        platform="cpu", n_devices=4, steps=3, **TINY,
    )
    assert second.checksum() == first.checksum()
    smoke.one_group_all_chips("cpu", 4, "cpu", steps=3, **TINY)


@pytest.mark.slow
def test_rehearse_kernels(smoke):
    checks = smoke.kernels(
        "cpu", [[1, 256, 2, 64], [1, 256, 4, 64, 64, 1]], [2, 256, 2, 64], [1, 256, 2, 128],
        mla_shape=[1, 256, 2, 24, 16], kda_shape=[1, 160, 2, 16], gdn_shape=[1, 160, 2, 4, 16],
        ssd_shape=[1, 160, 4, 8, 16],
    )
    assert [c["check"] for c in checks] == [
        "flash_d64", "flash_d64_grouped", "flash_cells", "chunked", "mla_cells", "kda_cells", "gdn_cells", "ssd_cells",
    ]
    assert all(c["ok"] and not c.get("mosaic_custom_call") for c in checks)


@pytest.mark.slow
def test_rehearse_kill_and_heal(smoke, monkeypatch):
    monkeypatch.setenv("XLA_FLAGS", "--xla_force_host_platform_device_count=2")
    obs = smoke.kill_and_heal("cpu", 2, "cpu", steps=200, kill_after=20, **TINY)
    assert obs["heal_bytes"] > 0
    # one pinned apply program: the respawn misses neither step program
    assert obs["respawn_cache_hits_misses"]["grads"][1] == 0
    assert obs["respawn_cache_hits_misses"]["apply"][1] == 0
    json.dumps(obs)
