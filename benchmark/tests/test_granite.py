"""What the ``granite-*`` configurations bring: the plain reference
(``reference/granite_hybrid_decoder.py``) against the program at the rehearsal's
sizes as ``worker.py`` compares them, the count (``opcounts/granite_hybrid.py``)
against numbers worked by hand at the cell's size, and the eight readers over
``ssd_scopes.py`` on op paths and rows written by hand, and where there is
nothing to read. ``tests/test_ssd_hybrid.py`` (tier-1) holds the program to the
reference in every gradient leaf, each of ten slips failing, and more."""

import os

import jax
import jax.numpy as jnp
import pytest

import measure
import opcount
import ssd_scopes
import subscopes
from common import load_json, load_module
from reference import granite_hybrid_decoder as ref
from torchft_tpu.models.transformer import TransformerConfig, init_params, loss_fn

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
NAME = "granite-4_0-h-micro-1g"
CONFIG = load_json(os.path.join(BENCH, "configs", NAME + ".json"))
TC = CONFIG["program"]["transformer_config"]
PEAKS = load_json(os.path.join(BENCH, "peaks.json"))["TPU v5 lite"]
CELL = NAME + ".fused-b1"
READERS = (
    "attn_ssd_device_s", "ssd_core_device_s", "ssd_core_roofline", "ssd_proj_device_s", "ssd_conv_gate_device_s",
    "attn_nope64_device_s", "nope64_core_device_s", "nope64_core_roofline",
)
COUNT = opcount.for_config(CONFIG)


def reader(name):
    return load_module(os.path.join(BENCH, "layer_metrics", name + ".py"), "metric_" + name)


def a_run(config=CONFIG, peaks=PEAKS, seq=8192):
    traffic = {"batch": 1, "seq": seq, "steps_per_unit": 5}
    return measure.Run({}, config, traffic, peaks, [{"group": 0, "trace": {"xplane": "no such file"}}])


# -- the reference ---------------------------------------------------------------------------------------


def test_per_sequence_loss_is_what_the_worker_compares_at_the_rehearsals_sizes():
    tc = {**TC, **load_json(os.path.join(HERE, "rehearsal.json"))["transformer_config"], **CONFIG["rehearsal"]["transformer_config"]}
    cfg = TransformerConfig(**{**tc, "dtype": jnp.dtype(tc["dtype"])})
    params = init_params(jax.random.PRNGKey(3), cfg)
    assert "out" not in params and len(ref.layer_params(params, tc)) == 10
    tokens = jax.random.randint(jax.random.PRNGKey(4), (2, 40), 0, cfg.vocab_size)
    with jax.default_matmul_precision("highest"):
        per = ref.per_sequence_loss(params, tokens, tc)
        mine = [float(loss_fn(params, tokens[i : i + 1], cfg)) for i in range(2)]
    assert [float(x) for x in per] == pytest.approx(mine, abs=2e-6)


# -- the count, by hand --------------------------------------------------------------------------------------


def test_hand_worked_parameters():
    # a state-space layer: in 2048 x (4096 + 4352 + 64), the taps and bias 5 x 4352, dt_bias | a_log | d_skip 3 x 64,
    # the gated norm 4096, out 4096 x 2048; two norms; SwiGLU 3 x 2048 x 8192
    ssd = 2048 * 8512 + 5 * 4352 + 192 + 4096 + 4096 * 2048
    assert ssd == 17_432_576 + 21_760 + 192 + 4_096 + 8_388_608 and COUNT.ssd_params(TC) == (25_821_184, ssd)
    swiglu = 3 * 2048 * 8192
    assert COUNT.layer_params(TC) == {"ssd": ssd + 4096 + swiglu, "full": 10_485_760 + 4096 + swiglu}
    assert COUNT.layer_params(TC) == {"ssd": 76_182_976, "full": 60_821_504}  # 76.18 M and 60.82 M
    # one period (nine and one) 746.47 M, the tied table ONCE 12 544 x 2048, the final norm
    assert 9 * 76_182_976 + 60_821_504 == 746_468_288
    assert COUNT.n_params(TC) == 746_468_288 + 12_544 * 2048 + 2048 == 772_160_448  # 772.2 M
    assert COUNT.n_params({**TC, "tie_embeddings": False}) == 772_160_448 + 12_544 * 2048


def test_hand_worked_operations_at_s8192(monkeypatch):
    by = COUNT.flops_per_token_by_scope(TC, 8192)
    cores = COUNT.core_flops_per_token(TC, 8192)
    # a chunk of 128: C·Bᵀ once for all heads 2 x 128 x 128, a head's apply 2 x 128 x 64, write and read 2 x 2 x 128 x 64
    assert cores["ssd_core"] == 9 * 3 * (32_768 + 64 * (16_384 + 32_768)) == 85_819_392  # 9.54 M a layer
    assert cores["global_core"] == 3 * 32 * 2 * 4096.5 * 128 == 100_675_584.0
    assert by["attn"] == 6 * (9 * 25_821_184 + 10_485_760) + 85_819_392 + 100_675_584
    assert by["ffn"] == 6 * 10 * 50_331_648 and by["head_loss"] == 6 * 2048 * 12_544
    assert sum(by.values()) == pytest.approx(4.818e9, rel=1e-4)  # 4 818 M a token, 39.5 T a step
    assert by["ffn"] / sum(by.values()) == pytest.approx(0.627, abs=1e-3)
    assert (6 * 9 * 25_821_184 + 85_819_392) / sum(by.values()) == pytest.approx(0.307, abs=1e-3)  # the nine mixers
    assert COUNT.ffn_scopes(TC) == ("ffn",)
    # the count is at the program's chunk: at 256 (ISSUE 69's hand count) the core is 12.78 M a layer and the sum 4 847 M
    from torchft_tpu.models import transformer

    assert COUNT.SSD_CHUNK == transformer._SSD_CHUNK == 128 and COUNT.SSD_BLOCK == transformer._KDA_BLOCK
    monkeypatch.setattr(COUNT, "SSD_CHUNK", 256)
    assert COUNT.core_flops_per_token(TC, 8192)["ssd_core"] == 9 * 3 * (65_536 + 64 * (32_768 + 32_768)) == 115_015_680
    assert sum(COUNT.flops_per_token_by_scope(TC, 8192).values()) == pytest.approx(4.847e9, rel=1e-4)


def test_which_bound_is_the_nearer_for_the_two_cores_at_the_cells_shape():
    flops, moved = COUNT.core_flops_per_token(TC, 8192), COUNT.core_bytes_per_step(TC, 1, 8192)
    # the state-space core, a token: Δ·x 8192 B, B and C 512 B, the log-decay 256 B = 8960 B of operands read twice and
    # their gradients written, the output and its gradient 2 x 8192 B; a block of 1024: one float32 state 64 x 128 x 64
    # written, read and its gradient handed on
    assert moved["ssd_core"] == 9 * (8192 * (3 * 8960 + 16_384) + 3 * 4 * 524_288 * 8)
    by_ops, by_bytes = flops["ssd_core"] * 8192 / PEAKS["bf16_flops_per_s"], moved["ssd_core"] / PEAKS["hbm_bytes_per_s"]
    assert by_ops == pytest.approx(3.569e-3, rel=1e-3) and by_bytes == pytest.approx(4.448e-3, rel=1e-3)
    assert by_bytes > by_ops  # the bytes, by a quarter: at chunk 256 it would be the operations (4.783 ms)
    # the softmax core: 32 heads at 4096.5 keys are operations by far
    assert moved["global_core"] == 8192 * 2 * 64 * (6 * 32 + 6 * 8)
    assert flops["global_core"] * 8192 / PEAKS["bf16_flops_per_s"] == pytest.approx(4.186e-3, rel=1e-3)
    assert moved["global_core"] / PEAKS["hbm_bytes_per_s"] == pytest.approx(0.307e-3, rel=1e-2)


# -- the readers --------------------------------------------------------------------------------------------


def test_the_innermost_name_on_a_path_through_attn():
    mixers, parts = ssd_scopes.MIXERS, ssd_scopes.PARTS
    core = "jit(tft_fused)/transpose(jvp())/while/body/closed_call/attn/ssd/while/body/closed_call/checkpoint/rematted_computation/ssd_core/bhnij,bnjhp->bnihp/dot_general"
    assert subscopes.innermost(core, "attn", mixers) == "ssd" and subscopes.innermost(core, "attn", parts) == "ssd_core"
    proj = "jit(tft_fused)/jvp()/attn/ssd/while/body/closed_call/checkpoint/ssd_in/dot_general"
    assert subscopes.innermost(proj, "attn", mixers) == "ssd" and subscopes.innermost(proj, "attn", parts) == "ssd_in"
    norm = "jit(tft_fused)/jvp()/attn/ssd/while/body/closed_call/checkpoint/gated_norm/rsqrt"
    assert subscopes.innermost(norm, "attn", parts) == "gated_norm"
    kernel = "jit(tft_fused)/jvp()/attn/attn/checkpoint/global/global_core/flash_fwd/pallas_call"
    assert subscopes.innermost(kernel, "attn", mixers) == "global" and subscopes.innermost(kernel, "attn", parts) == "global_core"
    first_norm = "jit(tft_fused)/jvp()/while/body/closed_call/attn/mul"
    assert subscopes.innermost(first_norm, "attn", mixers) == "attn" and subscopes.innermost(first_norm, "ffn", parts) is None


ROWS = {
    ("attn", ssd_scopes.MIXERS): {"ssd": 0.200, "global": 0.060, "attn": 0.004},
    ("attn", ssd_scopes.PARTS): {
        "ssd_in": 0.050, "ssd_out": 0.025, "conv": 0.010, "gates": 0.004, "ssd_core": 0.090, "gated_norm": 0.012,
        "global_core": 0.045, "attn": 0.028,
    },
}


def test_the_readers_on_rows_written_by_hand(monkeypatch):
    monkeypatch.setattr(subscopes, "seconds", lambda run, parent, names, rows_of=None: ROWS[(parent, tuple(names))])
    got = {name: reader(name).compute(a_run()) for name in READERS}
    assert (got["attn_ssd_device_s"], got["attn_nope64_device_s"]) == (0.200, 0.060)  # + 0.004 of neither = attn_device_s
    assert (got["ssd_core_device_s"], got["nope64_core_device_s"]) == (0.090, 0.045)
    assert got["ssd_proj_device_s"] == pytest.approx(0.075) and got["ssd_conv_gate_device_s"] == pytest.approx(0.026)
    assert got["ssd_core_roofline"] == pytest.approx(100 * 4.448e-3 / 0.090, rel=1e-3)
    assert got["nope64_core_roofline"] == pytest.approx(100 * 4.186e-3 / 0.045, rel=1e-3)
    fast = {**ROWS, ("attn", ssd_scopes.PARTS): {**ROWS[("attn", ssd_scopes.PARTS)], "ssd_core": 0.004}}
    monkeypatch.setattr(subscopes, "seconds", lambda run, parent, names, rows_of=None: fast[(parent, tuple(names))])
    assert reader("ssd_core_roofline").compute(a_run()) == pytest.approx(111.2, rel=1e-3)  # over 100 and said so
    assert reader("ssd_core_roofline").compute(a_run(peaks=None)) is None


def test_nothing_to_read_is_none_and_never_an_error(monkeypatch):
    # a trace that cannot be opened: every reader gives None
    assert all(reader(name).compute(a_run()) is None for name in READERS)
    # a program without the scopes (the parent commit), or another architecture's ``global`` layers without ``ssd``
    for rows in ({"attn": 0.5, "gdn": 0.1}, {"attn": 0.1, "global": 0.2, "global_core": 0.1}):
        monkeypatch.setattr(subscopes, "seconds", lambda run, parent, names, rows_of=None: rows)
        assert all(reader(name).compute(a_run()) is None for name in READERS)
    # a configuration whose count has no cores (opcount.py itself), or other cores (qwen3_next.py)
    monkeypatch.setattr(subscopes, "seconds", lambda run, parent, names, rows_of=None: {"ssd": 0.2, "ssd_core": 0.1, "global_core": 0.1})
    for other in ("olmo1b-1g", "qwen3-next-80b-a3b-1g"):
        config = load_json(os.path.join(BENCH, "configs", other + ".json"))
        assert reader("ssd_core_roofline").compute(a_run(config=config)) is None


def test_the_new_entries_are_found_by_name_and_list_the_new_cell():
    """By name, not by position: a later PR appends after these."""
    bench = load_json(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json"))
    listed = {m["name"]: m for m in bench["per_layer"]}
    for name in READERS:
        assert listed[name]["workloads"] == [CELL] and listed[name]["layer"] == "device compute"
        assert (listed[name]["unit"], listed[name]["better"]) == (("%", "higher") if name.endswith("_roofline") else ("s", "lower"))
        module = reader(name)
        assert (module.NAME, module.UNIT, module.LAYER, module.MOVES, module.SOURCE) == (
            name, listed[name]["unit"], "device compute", "step_p50_s", "device_trace",
        )
    names = [m["name"] for m in bench["per_layer"]]
    assert all(names.index(name) > names.index("moe_held16_route_device_s") for name in READERS)  # after what PR 65 appended
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["chips"]) == (NAME, 1) and cell["traffic"] in ("fused-b1-s8192", "fused-b1-s4096")
    traffic = load_json(os.path.join(BENCH, "traffic", cell["traffic"] + ".json"))
    assert (traffic["loop"], traffic["batch"], traffic["events"]) == ("fused", 1, [])
    entry = next(c for c in bench["configs"] if c["name"] == NAME)
    assert entry["reduced"] == ["num_hidden_layers", "layer_types", "vocab_size"] and sorted(CONFIG["reduced"]) == sorted(entry["reduced"])
    assert CONFIG["tie_word_embeddings"] is True and TC["tie_embeddings"] is True  # tied as published, NOT reduced
    assert CONFIG["share"] == {"chips_per_layer": 8, "vocab_size": {"published": 100352, "held": 12544}}
    assert CELL not in next(m for m in bench["end_to_end"] if m["name"] == "tokens_per_s")["workloads"]
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
