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

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    # a new one-chip cell is rehearsed without an edit here
    ONE_CHIP_CELLS = [w["name"] for w in json.load(_f)["workloads"] if w["chips"] == 1]

# Keys of a source's config.json that are widths: never in ``reduced``, at the
# top level or inside a nested group. Hidden, feed-forward and expert widths,
# head counts and sizes, the vocabulary, expert counts and experts per token,
# latent ranks, window and state sizes, expansion factors — by ending, and by
# the names the catalog's ``config`` objects use where the ending does not say.
WIDTH_ENDINGS = (
    "_size", "_dim", "_rank", "_width", "_heads", "_experts", "_expert", "_window",
    "_expand", "_expansion", "_per_tok", "_per_token", "_top_k", "_topk", "_d_state",
    "_d_conv", "_d_head", "_d_ssm", "_nh", "_nkv", "_kv_heads", "_groups", "_channels",
)
WIDTH_NAMES = {
    "top_k", "moe_k", "expand", "sliding_window", "conv_kernel", "conv_L_cache", "idim", "odim",
    "n_group", "topk_group", "num_expert_group", "num_expert_groups", "num_limited_groups",
    "router_num_group", "router_topk_group", "num_query_groups", "num_attention_groups",
    "num_kv_heads_for_linear_attn", "mlp_expansion_factor", "zaya_mlp_expansion",
    "mhc_expansion_rate", "hc_mult", "hc_count", "mamba_headdim", "mamba_ngroups",
    "time_step_rank", "experts_top_k", "moe_router_topk", "mlp_dynamic_top_k",
    "num_local_experts", "moe_num_active_primary_experts", "sliding_windows", "mtp_sliding_windows",
    "mlp_dynamic_expert_num", "mlp_fixed_expert_num", "zero_expert_num", "ffn_hidden_size_list",
    "num_attention_heads_per_layer", "num_query_groups_list",
}


# What ``reduced`` MAY hold: a configuration is cut in depth and in nothing
# else. Keys that count layers or say which layer is of which kind (by name and
# by ending, as the catalog's ``config`` objects spell them), any list with one
# entry per published layer, and ``tie_word_embeddings`` (the program keeps two
# tables). An activation, a router's normalisation, a bias, a RoPE base or a
# context length changes the mathematics or the sequence, not the depth.
LAYER_COUNTS = ("num_hidden_layers", "num_layers", "n_layers", "n_layer")
DEPTH_ENDINGS = (
    "_layers", "_layer_ids", "_layer_id", "_layer_indices", "_layer_types", "_block_type", "_layer_pattern",
    "_override_pattern", "_window_pattern", "_layer_offset", "_layer_period", "_layer_freq", "_layer_interval",
    "_layer_step", "_layer_start_index", "_layer_end_index", "_layer_num_skipped", "_layers_enum", "_num_blocks",
)
DEPTH_NAMES = set(LAYER_COUNTS) | {
    "tie_word_embeddings", "layer_types", "layers_block_type", "first_k_dense_replace", "decoder_sparse_step",
    "moe_every_n_layer", "full_attention_interval", "gqa_interval", "num_mtp_modules", "mtp_num_layers",
    "layer_switch", "sliding_window_period", "order_of_interleaved_layers", "attn_type_list", "mixer_types", "dense_list", "dense_mlp_idx",
}


def is_width(key: str) -> bool:
    return key in WIDTH_NAMES or key.endswith(WIDTH_ENDINGS)


def is_depth(key: str, value=None, layers=None) -> bool:
    """Whether ``reduced`` may hold ``key``: a depth or layer-pattern key, or
    (``value``: as published) a list with one entry for each of ``layers``."""
    if is_width(key):
        return False
    per_layer = isinstance(value, (list, str)) and layers is not None and len(value) == layers
    return key in DEPTH_NAMES or key.endswith(DEPTH_ENDINGS) or per_layer


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
    # every cell reports setup_s and one more end-to-end metric, each with its function in measure.py
    sys.path.insert(0, BENCH)
    import measure

    cells = {w["name"] for w in bench["workloads"]}
    for m in bench["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"} and set(m.get("workloads", cells)) <= cells
        assert m["name"] == "setup_s" or measure.END_TO_END[m["name"]][1] == m["unit"]
    for cell in cells:
        mine = {m["name"] for m in bench["end_to_end"] if cell in m.get("workloads", cells)}
        assert "setup_s" in mine and len(mine) >= 2, cell


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
        # sparse experts, where the source has them (intermediate_size is then one expert's width)
        assert tc.get("n_experts", 0) == cfg.get("num_experts", 0)
        if "num_experts_per_tok" in cfg:
            assert tc["top_k"] == cfg["num_experts_per_tok"]
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


def _published():
    """The published files, by source: ``{"source": <url>, "config": {...}}``."""
    out = {}
    for path in sorted(glob.glob(os.path.join(BENCH, "published", "*.json"))):
        with open(path) as f:
            pub = json.load(f)
        assert set(pub) == {"source", "config"}, path
        assert pub["source"] not in out, f"two published files for {pub['source']}"
        out[pub["source"]] = pub["config"]
    return out


def _changes_inside(published, run, layers, path):
    """What differs inside a reduced nested group and may not: widths, and
    whatever is neither depth nor layer pattern, at any depth of the group."""
    out = []
    for key, value in published.items():
        if run.get(key) == value:
            continue
        if isinstance(value, dict) and isinstance(run.get(key), dict):
            out += _changes_inside(value, run[key], layers, path + key + ".")
        elif is_width(key):
            out.append(f"width {path + key!r} changed inside a reduced group")
        elif not is_depth(key, value, layers):
            out.append(f"{path + key!r} changed inside a reduced group and is neither depth nor layer pattern")
    return out


def violations(reduced, cfg, published):
    """What keeps a configuration file from being its source as published,
    cut only in depth and only where ``reduced`` says: the rule every
    ``configs`` entry is held to. An empty list is a pass."""
    layers = next((published[k] for k in LAYER_COUNTS if k in published), None)
    out = []
    for key in reduced:
        value = published.get(key)
        if key not in published:
            out.append(f"reduced key {key!r} is not a key of the published config")
        elif is_width(key):
            out.append(f"reduced key {key!r} is a width")
        elif isinstance(value, dict):
            out += _changes_inside(value, cfg.get(key) or {}, layers, key + ".")
        elif not is_depth(key, value, layers):
            out.append(f"reduced key {key!r} is neither depth nor layer pattern: only those are cut")
    for key, value in published.items():
        if key in reduced:
            continue
        if key not in cfg:
            out.append(f"published key {key!r} is left out and not in reduced")
        elif cfg[key] != value:
            out.append(f"published key {key!r} is {cfg[key]!r}, the source has {value!r}, and it is not in reduced")
    return out


def test_published_widths_are_untouched(bench):
    """Every configuration is its source's config.json (``published/``), cut
    only where ``reduced`` says, only in depth, and never in a width."""
    published = _published()
    for c in bench["configs"]:
        assert c["source"] in published, f"no benchmark/published/*.json with the source of {c['name']}"
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert violations(c["reduced"], cfg, published[c["source"]]) == [], c["name"]


OLMOE = dict(
    hidden_size=2048, intermediate_size=1024, num_attention_heads=16, num_experts=64,
    num_experts_per_tok=8, num_hidden_layers=16, vocab_size=50304, norm_topk_prob=False, hidden_act="silu",
    tie_word_embeddings=False, max_position_embeddings=4096, layer_types=["full_attention"] * 16,
    rope_parameters={"rope_theta": 10000, "partial_rotary_dim": 64},
    linear_attn_config={"full_attn_layers": [4, 8, 12, 16], "head_dim": 128, "short_conv_kernel_size": 4},
)
ONE = {"num_hidden_layers": 1}


@pytest.mark.parametrize("reduced, changes, refused", [
    (["num_hidden_layers"], ONE, None),
    (["num_hidden_layers", "layer_types"], {**ONE, "layer_types": ["full_attention"]}, None),
    (["num_hidden_layers", "tie_word_embeddings"], {**ONE, "tie_word_embeddings": True}, None),
    (["num_hidden_layers", "num_experts"], {**ONE, "num_experts": 8}, "'num_experts' is a width"),
    (["num_hidden_layers", "intermediate_size"], ONE, "'intermediate_size' is a width"),
    (["num_hidden_layers"], {**ONE, "num_experts_per_tok": 2}, "'num_experts_per_tok' is 2"),
    (["num_hidden_layers"], {**ONE, "norm_topk_prob": True}, "'norm_topk_prob' is True"),
    (["num_hidden_layers"], {**ONE, "vocab_size": None}, "'vocab_size' is left out"),
    (["num_hidden_layers", "depth"], ONE, "'depth' is not a key of the published config"),
    # listed in ``reduced`` and still refused: these change the mathematics or the sequence, not the depth
    (["num_hidden_layers", "hidden_act"], {**ONE, "hidden_act": "gelu"}, "'hidden_act' is neither depth nor layer pattern"),
    (["num_hidden_layers", "norm_topk_prob"], {**ONE, "norm_topk_prob": True}, "'norm_topk_prob' is neither depth"),
    (["num_hidden_layers", "max_position_embeddings"], {**ONE, "max_position_embeddings": 2048}, "'max_position_embeddings' is neither depth"),
    # a nested group is copied whole; listed, only depth and layer pattern may change inside it
    (["num_hidden_layers", "linear_attn_config"], {**ONE, "linear_attn_config": {"full_attn_layers": [1], "head_dim": 128, "short_conv_kernel_size": 4}}, None),
    (["num_hidden_layers", "linear_attn_config"], {**ONE, "linear_attn_config": {"full_attn_layers": [1], "head_dim": 64, "short_conv_kernel_size": 4}}, "width 'linear_attn_config.head_dim' changed inside"),
    (["num_hidden_layers", "rope_parameters"], {**ONE, "rope_parameters": {"rope_theta": 500000, "partial_rotary_dim": 64}}, "'rope_parameters.rope_theta' changed inside a reduced group and is neither"),
    (["num_hidden_layers", "rope_parameters"], {**ONE, "rope_parameters": {"rope_theta": 10000, "partial_rotary_dim": 32}}, "width 'rope_parameters.partial_rotary_dim' changed inside"),
])
def test_what_the_published_rule_refuses(reduced, changes, refused):
    cfg = {k: v for k, v in {**OLMOE, **changes}.items() if v is not None}
    found = violations(reduced, cfg, OLMOE)
    if refused is None:
        assert found == []
    else:
        assert len(found) == 1 and refused in found[0], found


def test_which_keys_are_widths_and_which_may_be_cut():
    widths = (
        "hidden_size intermediate_size moe_intermediate_size vocab_size head_dim num_attention_heads "
        "num_key_value_heads num_experts n_routed_experts n_shared_experts num_experts_per_tok kv_lora_rank "
        "q_lora_rank qk_rope_head_dim v_head_dim sliding_window ssm_state_size mamba_d_state mamba_expand "
        "linear_num_value_heads linear_key_head_dim moe_topk top_k shared_expert_intermediate_size "
        "num_attention_heads_per_layer ffn_hidden_size_list"
    ).split()
    depth = (
        "num_hidden_layers num_layers tie_word_embeddings first_k_dense_replace num_nextn_predict_layers layer_types "
        "mlp_only_layers moe_layer_freq decoder_sparse_step full_attention_interval hybrid_override_pattern "
        "layers_block_type attn_layer_indices attn_layer_period max_window_layers interleave_moe_layer_step "
        "global_attn_every_n_layers num_dense_layers kv_source_layer_ids sliding_window_pattern mtp_num_hidden_layers"
    ).split()
    neither = (
        "max_position_embeddings rope_theta rms_norm_eps layer_norm_eps hidden_act norm_topk_prob model_type "
        "attention_bias clip_qkv qk_layernorm rope_scaling partial_rotary_factor routed_scaling_factor scoring_func"
    ).split()
    assert [k for k in widths if not is_width(k) or is_depth(k)] == []
    assert [k for k in depth if is_width(k) or not is_depth(k)] == []
    assert [k for k in neither if is_width(k) or is_depth(k)] == []
    # a list with one entry per published layer is cut with the depth, whatever its name; a width never
    assert is_depth("swiglu_limits", [7.0] * 16, 16) and not is_depth("swiglu_limits", [7.0] * 4, 16)
    assert not is_depth("num_attention_heads_per_layer", [16] * 16, 16)


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
        # the metric it moves is reported in every cell where this one is
        moved = next(m for m in bench["end_to_end"] if m["name"] == entry["moves"])
        assert set(entry.get("workloads", cells)) <= set(moved.get("workloads", cells)), name
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
@pytest.mark.parametrize("workload", ONE_CHIP_CELLS)
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
        # what BENCHMARK.json lists for this cell, no more and no less
        assert set(line["metrics"]) == {m["name"] for m in bench["end_to_end"] if workload in m.get("workloads", [workload])}
        assert len(line["metrics"]) >= 2 and "setup_s" in line["metrics"]
    counters = {m["name"] for m in bench["per_layer"] if m["source"] == "program_counter"}
    for name, m in line["metrics"].items():
        assert set(m) == {"value", "unit"}
        assert m["value"] > 0 or name in counters and m["value"] == 0, name  # a counter may read 0


def test_the_cpu_is_refused_at_the_real_size():
    proc = _run("--workload", ONE_CHIP_CELLS[0], "--seed", "1", "--seconds", "2", "--trace", "0")
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
