"""BENCHMARK.json against the files it names, and run.py rehearsed on the
CPU: the last line's keys, and that the CPU is refused at the real size."""

import glob
import importlib.util
import json
import os
import re
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _metric_modules():
    mods = {}
    for path in sorted(glob.glob(os.path.join(BENCH, "layer_metrics", "*.py"))):
        spec = importlib.util.spec_from_file_location("m_" + os.path.basename(path)[:-3], path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        mods[mod.NAME] = mod
    return mods


def test_shape_of_the_file(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    assert 1 <= bench["run_seconds"] <= 51
    runs = 2 + 14 * 24  # a full check with all 24 cells must fit
    assert runs * (bench["run_seconds"] + 60) + 24 * 2 * 90 + 1200 <= 43200
    names = [e["name"] for k in ("configs", "workloads", "end_to_end", "per_layer") for e in bench[k]]
    assert all(NAME.match(n) for n in names), names
    for key in ("configs", "workloads"):
        assert len({e["name"] for e in bench[key]}) == len(bench[key])
    metric_names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(set(metric_names)) == len(metric_names)
    for e in bench["configs"] + bench["workloads"]:
        assert 1 <= len(e["why"]) <= 200 and "\n" not in e["why"] and "\t" not in e["why"], e["name"]
    four = sum(1 for w in bench["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(bench["workloads"]) // 4)
    assert any(m["name"] == "setup_s" and m["bound"] <= 0.1 for m in bench["end_to_end"])
    assert all(0.01 <= m["bound"] <= 0.1 and m["source"] in ("host_clock", "device_trace") for m in bench["end_to_end"])


def test_every_entry_finds_its_files(bench):
    configs = {c["name"]: c for c in bench["configs"]}
    assert len({c["file"] for c in configs.values()}) == len(configs)
    for c in configs.values():
        assert c["file"].startswith("benchmark/")
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["source"] == c["source"]
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        assert {"assumed", "layout", "program", "deployment"} <= set(cfg)
        # the program's sizes are the published ones, under the program's names
        tc = cfg["program"]["transformer_config"]
        assert (tc["d_model"], tc["d_ff"], tc["n_heads"], tc["vocab_size"], tc["n_layers"], tc["rope_theta"]) == (
            cfg["hidden_size"], cfg["intermediate_size"], cfg["num_attention_heads"],
            cfg["vocab_size"], cfg["num_hidden_layers"], cfg["rope_theta"],
        )
        assert tc["head_dim"] * tc["n_heads"] == cfg["hidden_size"]
        assert os.path.exists(os.path.join(BENCH, "reference", cfg["program"]["reference"] + ".py"))
    used = set()
    for w in bench["workloads"]:
        used.add(w["config"])
        with open(os.path.join(BENCH, "traffic", w["traffic"] + ".json")) as f:
            traffic = json.load(f)
        assert os.path.exists(os.path.join(BENCH, "loops", traffic["loop"] + ".py"))
        layout = json.load(open(os.path.join(ROOT, configs[w["config"]]["file"])))["layout"]
        assert layout["groups"] * layout["chips_per_group"] == w["chips"]
    assert used == set(configs)


def test_published_widths_are_untouched(bench):
    """OLMo-1B-hf's config.json; only the depth and the tie are changed."""
    published = dict(
        hidden_size=2048, intermediate_size=8192, num_attention_heads=16, num_key_value_heads=16,
        vocab_size=50304, max_position_embeddings=2048, rope_theta=10000.0, hidden_act="silu",
    )
    for c in bench["configs"]:
        cfg = json.load(open(os.path.join(ROOT, c["file"])))
        assert {k: cfg[k] for k in published} == published
        assert set(c["reduced"]) == {"num_hidden_layers", "tie_word_embeddings"}


def test_per_layer_entries_match_their_reader_files(bench):
    mods = _metric_modules()
    listed = {m["name"]: m for m in bench["per_layer"]}
    assert set(mods) == set(listed)
    end_to_end = {m["name"] for m in bench["end_to_end"]}
    cells = {w["name"] for w in bench["workloads"]}
    for name, entry in listed.items():
        mod = mods[name]
        assert (mod.UNIT, mod.LAYER, mod.MOVES, mod.SOURCE) == (
            entry["unit"], entry["layer"], entry["moves"], entry["source"]
        ), name
        assert entry["moves"] in end_to_end
        assert entry["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
        assert set(entry.get("workloads", cells)) <= cells
        assert set(entry) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
    # every cell has at least one per-layer metric
    for cell in cells:
        assert any(cell in e.get("workloads", cells) for e in listed.values())


def _run(*args, timeout=300):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), *args],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=timeout,
    )


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("workload", ["olmo1b-1g.fused", "olmo1b-1g.ft-steady"])
def test_rehearsal_prints_the_contracts_last_line(bench, workload, trace):
    proc = _run("--workload", workload, "--seed", "3", "--seconds", "2", "--trace", str(trace), "--rehearse")
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert set(line) == {"correct", "attempted", "failed", "metrics", "device"} | ({"breakdown"} if trace else set())
    assert line["correct"] is True and line["attempted"] > 0 and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"  # a rehearsal names its backend: no result
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(line["device"])
    if trace:
        assert line["device"]["busy_s"] > 0 and line["device"]["window_s"] > line["device"]["busy_s"]
        listed = {m["name"] for m in bench["per_layer"] if workload in m.get("workloads", [workload])}
        assert set(line["metrics"]) <= listed and len(line["metrics"]) >= 3
        assert len(line["breakdown"]["device_ops"]) <= 10 and len(line["breakdown"]["idle_gaps"]) <= 10
    else:
        assert set(line["metrics"]) == {"tokens_per_s", "setup_s"}
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0


def test_the_cpu_is_refused_at_the_real_size():
    proc = _run("--workload", "olmo1b-1g.fused", "--seed", "1", "--seconds", "2", "--trace", "0")
    assert proc.returncode != 0
    assert "no accelerator" in proc.stderr
    assert not proc.stdout.strip().splitlines()[-1].startswith("{")


def test_a_traffic_event_without_its_handler_is_refused():
    sys.path.insert(0, BENCH)
    import run as bench_run

    bench_run.check_events([])
    with pytest.raises(bench_run.Refused, match="not implemented"):
        bench_run.check_events([{"at_s": 10, "do": "kill", "group": 1}])
    with pytest.raises(bench_run.Refused, match="needs"):
        bench_run.check_events([{"do": "kill"}])
