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
# top level or inside a nested group (but SHARE_COUNTS, below, under a ``share``
# group). Hidden, feed-forward and expert widths, head counts and sizes, the vocabulary, expert counts and experts per token,
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


# What ``reduced`` MAY hold beside the counts under a share (SHARE_COUNTS,
# below): a configuration is cut in depth. Keys that count layers or say which
# layer is of which kind (by name and by ending, as the catalog's ``config`` objects spell them), any list with one
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

# A third class beside width (never cut) and depth (cut, listed): counts a
# deployment divides over the chips that share a layer. The keys that count
# ROUTED experts, as the catalog's ``config`` objects spell them, and the
# vocabulary's rows. Such a key may stand in ``reduced`` only under a ``share``
# group of the configuration file: ``chips_per_layer`` n, and for each such key
# its ``published`` and ``held`` counts (``violations``). To ``is_width`` they
# stay widths: without a ``share`` group they are refused as before. Experts per
# token, expert and router widths, shared-expert counts, ranks, head counts and
# sizes are widths, share or no share.
SHARE_COUNTS = {
    "num_experts", "n_routed_experts", "num_local_experts", "moe_num_experts", "moe_num_primary_experts", "vocab_size",
}
MIN_EXPERTS_HELD = 8        # routed experts in each layer that has them
MIN_VOCAB_FRACTION = 8      # at least an eighth of the vocabulary's rows
# the source's count of leading dense layers, by its spellings: at least
# MIN_LAYERS_AFTER_DENSE layers follow them in a configuration cut in depth
# (that those kept are a whole period of the pattern is the reviewer's to check)
LEADING_DENSE = ("first_k_dense_replace", "num_dense_layers", "n_dense_first_layers")
MIN_LAYERS_AFTER_DENSE = 4


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


def _at(obj, path: str):
    """The value at a dotted path of nested groups; KeyError where it leads nowhere."""
    for key in path.split("."):
        if not isinstance(obj, dict) or key not in obj:
            raise KeyError(path)
        obj = obj[key]
    return obj


def _numeric_widths(published, prefix=""):
    """Dotted paths of the published file's width keys that hold a number, at any depth."""
    for key, value in published.items():
        if isinstance(value, dict):
            yield from _numeric_widths(value, prefix + key + ".")
        elif is_width(key) and isinstance(value, (int, float)) and not isinstance(value, bool):
            yield prefix + key


def _tuple_violations(cfg):
    """The map of a configuration file without ``program.published_as``: the
    seven names every configuration had before the map moved into the file."""
    tc = cfg["program"]["transformer_config"]
    out = []
    ours = (tc["d_model"], tc["d_ff"], tc["n_heads"], tc["vocab_size"], tc["n_layers"], tc["rope_theta"])
    theirs = (cfg["hidden_size"], cfg["intermediate_size"], cfg["num_attention_heads"],
              cfg["vocab_size"], cfg["num_hidden_layers"], cfg["rope_theta"])
    if ours != theirs:
        out.append(f"transformer_config has {ours}, the published keys {theirs}")
    if tc["head_dim"] * tc["n_heads"] != cfg["hidden_size"]:
        out.append("head_dim x n_heads is not hidden_size")
    # sparse experts, where the source has them (intermediate_size is then one expert's width)
    if tc.get("n_experts", 0) != cfg.get("num_experts", 0):
        out.append("n_experts is not num_experts")
    if "num_experts_per_tok" in cfg and tc["top_k"] != cfg["num_experts_per_tok"]:
        out.append("top_k is not num_experts_per_tok")
    return out


def map_violations(cfg, published):
    """What keeps ``program.transformer_config`` from being the published
    sizes under the program's names. ``program.published_as`` maps a
    ``transformer_config`` key to a dotted path of the configuration file
    (``share.<key>.published`` included: a router keeps its published width
    over the experts held); every entry holds equal, and every numeric width
    of the published file (``is_width``, at any depth) is the target of an
    entry or stands in ``program.unmapped`` with a one-line reason — so a
    configuration cannot leave out the size that makes it different. A file
    without ``published_as`` is held to the tuple of before."""
    program = cfg["program"]
    mapping = program.get("published_as")
    if mapping is None:
        return _tuple_violations(cfg)
    tc, out, covered = program["transformer_config"], [], set()
    for name, path in mapping.items():
        parts = path.split(".")
        covered.add(parts[1] if parts[0] == "share" and len(parts) == 3 else path)
        try:
            value = _at(cfg, path)
        except KeyError:
            out.append(f"published_as sends {name!r} to {path!r}, which the configuration file lacks")
            continue
        if name not in tc:
            out.append(f"published_as names {name!r}, which transformer_config lacks")
        elif tc[name] != value:
            out.append(f"transformer_config.{name} is {tc[name]!r} and {path} is {value!r}")
    unmapped = program.get("unmapped", {})
    for key, reason in unmapped.items():
        if not (isinstance(reason, str) and 1 <= len(reason) <= 200 and "\n" not in reason):
            out.append(f"program.unmapped[{key!r}] gives no one-line reason")
    for width in _numeric_widths(published):
        if width not in covered and width not in unmapped:
            out.append(f"published width {width!r} is the target of no published_as entry and not in program.unmapped")
    return out


def test_every_entry_finds_its_files(bench):
    published = _published()
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
        assert map_violations(cfg, published[c["source"]]) == [], c["name"]
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


def _share_of(key, cfg, published):
    """What keeps the reduced count ``key`` from being the chip's share of the
    deployment the configuration's ``share`` group states."""
    n, entry = cfg["share"]["chips_per_layer"], cfg["share"].get(key)
    if not (isinstance(entry, dict) and set(entry) == {"published", "held"}):
        return [f"reduced key {key!r} is a width: the share group states no 'published' and 'held' count for it"]
    out, theirs, held = [], entry["published"], entry["held"]
    if theirs != published[key]:
        out.append(f"share.{key}.published is {theirs!r}, the source has {published[key]!r}")
    elif key == "vocab_size":
        if not (isinstance(held, int) and held >= 1 and theirs % held == 0):
            out.append(f"share.vocab_size.held is {held!r}: it does not divide the published {theirs}")
        elif held * MIN_VOCAB_FRACTION < theirs:
            out.append(f"share.vocab_size.held is {held}: under an eighth of the published {theirs}")
    elif not (isinstance(held, int) and held * n == theirs):
        out.append(f"share.{key}.held is {held!r}: not the published {theirs} over {n} chips")
    elif held < MIN_EXPERTS_HELD:
        out.append(f"share.{key}.held is {held}: fewer than {MIN_EXPERTS_HELD} experts a layer")
    if cfg.get(key) != held:
        out.append(f"{key!r} is {cfg.get(key)!r} and share.{key}.held is {held!r}: the key's own value is the held count")
    return out


def violations(reduced, cfg, published):
    """What keeps a configuration file from being its source as published,
    cut only where ``reduced`` says: in depth, and in the counts a deployment
    divides over the chips that share a layer (:data:`SHARE_COUNTS`) where the
    file's ``share`` group states that deployment — the rule every ``configs``
    entry is held to. An empty list is a pass."""
    count_key = next((k for k in LAYER_COUNTS if k in published), None)
    layers = published.get(count_key)
    share = cfg.get("share")
    out = []
    chips = (share or {}).get("chips_per_layer")
    chips_stated = isinstance(chips, int) and not isinstance(chips, bool) and chips >= 1
    if share is not None and not chips_stated:
        out.append(f"share.chips_per_layer is {chips!r}: it states how many chips share a layer")
    for key in reduced:
        value = published.get(key)
        if key not in published:
            out.append(f"reduced key {key!r} is not a key of the published config")
        elif key in SHARE_COUNTS and share is not None:
            out += _share_of(key, cfg, published) if chips_stated else []
        elif is_width(key):
            out.append(
                f"reduced key {key!r} is a width" + (", share or no share: only the routed experts held and "
                "the vocabulary's rows are a chip's share" if key in (share or {}) else "")
            )
        elif isinstance(value, dict):
            out += _changes_inside(value, cfg.get(key) or {}, layers, key + ".")
        elif not is_depth(key, value, layers):
            out.append(f"reduced key {key!r} is neither depth nor layer pattern: only those are cut")
    if share is not None:
        stale = [k for k in share if k != "chips_per_layer" and k not in reduced]
        if stale or len(share) < 2:
            out.append(f"a stale share group: {stale or 'it'} states a share and no reduced key uses it")
    for key, value in published.items():
        if key in reduced:
            continue
        if key not in cfg:
            out.append(f"published key {key!r} is left out and not in reduced")
        elif cfg[key] != value:
            out.append(f"published key {key!r} is {cfg[key]!r}, the source has {value!r}, and it is not in reduced")
    # the depth floor, as far as the keys say it: layers after the leading dense ones
    dense_key = next((k for k in LEADING_DENSE if published.get(k)), None)
    if dense_key and count_key in reduced and isinstance(cfg.get(count_key), int) and isinstance(cfg.get(dense_key), int):
        after = cfg[count_key] - cfg[dense_key]
        if after < MIN_LAYERS_AFTER_DENSE:
            out.append(
                f"{after} layers follow the {cfg[dense_key]} leading dense ({dense_key!r}): "
                f"at least {MIN_LAYERS_AFTER_DENSE} do, a whole period of the pattern"
            )
    return out


def test_published_widths_are_untouched(bench):
    """Every configuration is its source's config.json (``published/``), cut
    only where ``reduced`` says: in depth, in the experts held and the
    vocabulary's rows under a stated share, and never in a width."""
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


# The drawn row of ISSUE 34, the catalog's ``config`` of Kimi-Linear-48B-A3B-Instruct key for key: a fixture of
# this test and no configuration. Its cut: the leading dense layer and one whole period after it (KDA, KDA, MLA,
# KDA), and this chip's share as one of 32 that share each layer.
KIMI = {
    "first_k_dense_replace": 1, "head_dim": 72, "hidden_act": "silu", "hidden_size": 2304, "intermediate_size": 9216,
    "kv_lora_rank": 512,
    "linear_attn_config": {
        "full_attn_layers": [4, 8, 12, 16, 20, 24, 27], "head_dim": 128,
        "kda_layers": [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19, 21, 22, 23, 25, 26],
        "num_heads": 32, "short_conv_kernel_size": 4,
    },
    "mla_use_nope": True, "model_max_length": 1048576, "model_type": "kimi_linear", "moe_intermediate_size": 1024,
    "moe_layer_freq": 1, "moe_renormalize": True, "moe_router_activation_func": "sigmoid", "num_attention_heads": 32,
    "num_expert_group": 1, "num_experts": 256, "num_experts_per_token": 8, "num_hidden_layers": 27,
    "num_key_value_heads": 32, "num_nextn_predict_layers": 0, "num_shared_experts": 1, "q_lora_rank": None,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
    "routed_scaling_factor": 2.446, "tie_word_embeddings": False, "topk_group": 1, "use_grouped_topk": True,
    "v_head_dim": 128, "vocab_size": 163840,
}
KIMI_REDUCED = ["num_hidden_layers", "linear_attn_config", "num_experts", "vocab_size"]
KIMI_MIXERS = {"head_dim": 128, "num_heads": 32, "short_conv_kernel_size": 4}
KIMI_CUT = {
    "num_hidden_layers": 5, "linear_attn_config": {**KIMI_MIXERS, "kda_layers": [1, 2, 3, 5], "full_attn_layers": [4]},
    "num_experts": 8, "vocab_size": 20480,
    "share": {"chips_per_layer": 32, "num_experts": {"published": 256, "held": 8},
              "vocab_size": {"published": 163840, "held": 20480}},
}


def _share(**changes):
    return {**KIMI_CUT["share"], **changes}


@pytest.mark.parametrize("reduced, changes, refused", [
    (KIMI_REDUCED, {}, None),
    # the vocabulary whole and the experts alone under the share
    (KIMI_REDUCED[:3], {"vocab_size": 163840, "share": {"chips_per_layer": 32, "num_experts": {"published": 256, "held": 8}}}, None),
    (KIMI_REDUCED[:3], {"vocab_size": 163840, "share": None}, "'num_experts' is a width"),
    (KIMI_REDUCED, {"num_experts": 16, "share": _share(num_experts={"published": 256, "held": 16})}, "held is 16: not the published 256 over 32 chips"),
    (KIMI_REDUCED, {"num_experts": 4, "share": _share(chips_per_layer=64, num_experts={"published": 256, "held": 4})}, "held is 4: fewer than 8 experts"),
    (KIMI_REDUCED, {"share": _share(num_experts={"published": 128, "held": 8})}, "share.num_experts.published is 128, the source has 256"),
    (KIMI_REDUCED, {"num_experts": 16}, "'num_experts' is 16 and share.num_experts.held is 8"),
    (KIMI_REDUCED, {"vocab_size": 10240, "share": _share(vocab_size={"published": 163840, "held": 10240})}, "held is 10240: under an eighth"),
    (KIMI_REDUCED, {"vocab_size": 30000, "share": _share(vocab_size={"published": 163840, "held": 30000})}, "held is 30000: it does not divide"),
    (KIMI_REDUCED, {"share": _share(chips_per_layer=0)}, "share.chips_per_layer is 0"),
    # experts per token are a width, share or no share; so is a shared expert
    (KIMI_REDUCED + ["num_experts_per_token"], {"num_experts_per_token": 2, "share": _share(num_experts_per_token={"published": 8, "held": 2})},
     "'num_experts_per_token' is a width, share or no share"),
    (KIMI_REDUCED + ["num_shared_experts"], {"num_shared_experts": 0}, "'num_shared_experts' is a width"),
    # a share that no reduced key uses
    (KIMI_REDUCED[:3], {"vocab_size": 163840}, "a stale share group: ['vocab_size']"),
    (KIMI_REDUCED[:2], {"num_experts": 256, "vocab_size": 163840, "share": {"chips_per_layer": 32}}, "a stale share group"),
    # the depth floor: four layers after the leading dense one
    (KIMI_REDUCED, {"num_hidden_layers": 4, "linear_attn_config": {**KIMI_MIXERS, "kda_layers": [1, 2, 3], "full_attn_layers": [4]}},
     "3 layers follow the 1 leading dense"),
    # under the share every other rule holds as before
    (KIMI_REDUCED, {"moe_intermediate_size": 512}, "'moe_intermediate_size' is 512"),
    (KIMI_REDUCED, {"linear_attn_config": {**KIMI_MIXERS, "head_dim": 64, "kda_layers": [1, 2, 3, 5], "full_attn_layers": [4]}},
     "width 'linear_attn_config.head_dim' changed inside"),
])
def test_what_the_share_of_a_deployment_admits_and_refuses(reduced, changes, refused):
    cfg = {k: v for k, v in {**KIMI, **KIMI_CUT, **changes}.items() if v is not None or k in KIMI}
    found = violations(reduced, cfg, KIMI)
    if refused is None:
        assert found == []
    else:
        assert len(found) == 1 and refused in found[0], found


# The same cut under the program's names, as a configuration file would carry it (what the program would call
# its sizes is the next ``model_config`` PR's to say: these names are the fixture's)
KIMI_PROGRAM = {
    "transformer_config": {
        "vocab_size": 20480, "d_model": 2304, "n_layers": 5, "n_dense_layers": 1, "d_ff": 9216, "moe_d_ff": 1024,
        "n_experts": 256, "n_experts_held": 8, "top_k": 8, "n_shared_experts": 1, "n_heads": 32,
        "kv_lora_rank": 512, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
        "linear_head_dim": 128, "linear_n_heads": 32, "conv_kernel": 4, "rope_theta": 10000.0, "dtype": "bfloat16",
    },
    "published_as": {
        "vocab_size": "vocab_size", "d_model": "hidden_size", "n_layers": "num_hidden_layers",
        "n_dense_layers": "first_k_dense_replace", "d_ff": "intermediate_size", "moe_d_ff": "moe_intermediate_size",
        "n_experts": "share.num_experts.published", "n_experts_held": "num_experts", "top_k": "num_experts_per_token",
        "n_shared_experts": "num_shared_experts", "n_heads": "num_attention_heads", "kv_lora_rank": "kv_lora_rank",
        "qk_nope_head_dim": "qk_nope_head_dim", "qk_rope_head_dim": "qk_rope_head_dim", "v_head_dim": "v_head_dim",
        "linear_head_dim": "linear_attn_config.head_dim", "linear_n_heads": "linear_attn_config.num_heads",
        "conv_kernel": "linear_attn_config.short_conv_kernel_size", "rope_theta": "rope_theta",
    },
    "unmapped": {
        "head_dim": "72 = hidden_size / heads: no layer of the model uses it (KDA's heads are 128 wide, MLA's 192 and 128)",
        "num_key_value_heads": "equal to num_attention_heads: MLA shares no key/value head",
        "num_expert_group": "1: one group, the grouped top-k is the plain one",
        "topk_group": "1: as num_expert_group",
    },
}


def _program(drop=(), unmapped=None, **tc):
    program = {**KIMI_PROGRAM, "transformer_config": {**KIMI_PROGRAM["transformer_config"], **tc}}
    program["published_as"] = {k: v for k, v in KIMI_PROGRAM["published_as"].items() if k not in drop}
    program["unmapped"] = {**KIMI_PROGRAM["unmapped"], **(unmapped or {})}
    return program


@pytest.mark.parametrize("program, refused", [
    (_program(), None),
    # a size that makes the architecture what it is, left out of the map and of ``unmapped``
    (_program(drop=["kv_lora_rank"]), "published width 'kv_lora_rank' is the target of no published_as entry"),
    (_program(drop=["linear_head_dim"]), "published width 'linear_attn_config.head_dim' is the target of no"),
    (_program(drop=["n_experts", "n_experts_held"]), "published width 'num_experts' is the target of no"),
    (_program(drop=["n_experts_held"]), None),  # the published count through the share covers the key
    # an entry that does not hold
    (_program(moe_d_ff=512), "transformer_config.moe_d_ff is 512 and moe_intermediate_size is 1024"),
    (_program(n_experts=8), "transformer_config.n_experts is 8 and share.num_experts.published is 256"),
    (_program(vocab_size=163840), "transformer_config.vocab_size is 163840 and vocab_size is 20480"),
    (_program(unmapped={"topk_group": ""}), "program.unmapped['topk_group'] gives no one-line reason"),
    ({**_program(), "published_as": {**KIMI_PROGRAM["published_as"], "window": "sliding_window"}}, "'window' to 'sliding_window', which the configuration file lacks"),
    ({**_program(), "published_as": {**KIMI_PROGRAM["published_as"], "n_kv_heads": "num_key_value_heads"}}, "names 'n_kv_heads', which transformer_config lacks"),
])
def test_what_the_map_to_the_programs_names_holds(program, refused):
    cfg = {**KIMI, **KIMI_CUT, "program": program}
    found = map_violations(cfg, KIMI)
    if refused is None:
        assert found == []
    else:
        assert len(found) == 1 and refused in found[0], found


def test_a_file_without_the_map_is_held_to_the_tuple_of_before():
    tc = dict(d_model=2048, d_ff=1024, n_heads=16, head_dim=128, vocab_size=50304, n_layers=1, rope_theta=10000.0, n_experts=64, top_k=8)
    olmoe = {**{k: v for k, v in OLMOE.items() if k != "rope_parameters"}, "num_hidden_layers": 1, "rope_theta": 10000}
    assert map_violations({**olmoe, "program": {"transformer_config": tc}}, OLMOE) == []
    assert map_violations({**olmoe, "program": {"transformer_config": {**tc, "d_ff": 2048}}}, OLMOE) != []
    assert map_violations({**olmoe, "program": {"transformer_config": {**tc, "n_experts": 8}}}, OLMOE) == ["n_experts is not num_experts"]
    assert map_violations({**olmoe, "program": {"transformer_config": {**tc, "top_k": 2}}}, OLMOE) == ["top_k is not num_experts_per_tok"]


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


def test_every_configurations_count_has_the_interface_and_adds_up(bench):
    """``opcount.for_config``: the file ``program.opcount`` names, or ``opcount.py``. A named file exists and has
    the interface (else the resolver raises, naming it); its scopes are top-level scopes, the feed-forward ones
    among them; and where it gives a total of its own, that is the sum over its scopes."""
    import opcount
    import xplane_meta

    for c in bench["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        counts = opcount.for_config(cfg)
        tc = cfg["program"]["transformer_config"]
        assert isinstance(counts.n_params(tc), int) and counts.n_params(tc) > 0, c["name"]
        for w in bench["workloads"]:
            if w["config"] != c["name"]:
                continue
            with open(os.path.join(BENCH, "traffic", w["traffic"] + ".json")) as f:
                traffic = json.load(f)
            flops = counts.flops_per_token_by_scope(tc, traffic["seq"])
            moved = counts.bytes_per_step_by_scope(tc, traffic["batch"], traffic["seq"])
            assert set(flops) | set(moved) <= set(xplane_meta.SCOPES), w["name"]
            assert all(v > 0 for v in list(flops.values()) + list(moved.values())), w["name"]
            ffn = counts.ffn_scopes(tc)
            assert len(ffn) >= 1 and set(ffn) <= set(flops) & set(moved), w["name"]
            if hasattr(counts, "flops_per_token"):
                assert sum(flops.values()) == pytest.approx(counts.flops_per_token(tc, traffic["seq"]), rel=1e-12)


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
    # the configuration's count holds as many parameters as the program placed: a count that took the experts held
    # for the published ones, or the other way, is stale before any roofline is read (within 0.1 % and not equal:
    # opcount.py leaves out OLMoE's two QK-norm weights a layer, 4 096 of 625.6 M at the cell's size)
    import opcount

    run_dir = os.path.join(ROOT, "benchmark_runs", workload)
    with open(os.path.join(run_dir, "cell.json")) as f:
        config = json.load(f)["config"]  # as rehearsed: the tiny sizes merged in
    with open(os.path.join(run_dir, "result.0.json")) as f:
        placed = json.load(f)["n_params"]
    counted = opcount.for_config(config).n_params(config["program"]["transformer_config"])
    assert abs(counted - placed) <= 1e-3 * placed, (counted, placed)


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
