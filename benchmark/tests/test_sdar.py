"""What the ``sdar-*`` configurations bring: the plain reference
(``reference/sdar_moe_decoder.py``) against the program at a tiny size, the count
(``opcounts/sdar_moe.py``) against numbers worked by hand at the cell's size,
and the seven readers over ``sdar_scopes.py`` on op paths and rows written by
hand, and where there is nothing to read. ``tests/test_block_diffusion.py``
(tier-1) holds the program to the reference at every gradient leaf, every slip
of the mathematics, the 2·S rows to the definition block by block, and more."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import measure
import moe_scopes
import opcount
import sdar_scopes
import subscopes
from common import load_json, load_module
from reference import sdar_moe_decoder as ref
from torchft_tpu.models.transformer import TransformerConfig, init_params, loss_fn

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
CONFIG = load_json(os.path.join(BENCH, "configs", "sdar-30b-a3b-1g.json"))
TC = CONFIG["program"]["transformer_config"]
PEAKS = load_json(os.path.join(BENCH, "peaks.json"))["TPU v5 lite"]
CELL = "sdar-30b-a3b-1g.fused-s8192"
READERS = (
    "attn_blockdiff_device_s", "blockdiff_core_device_s", "blockdiff_core_roofline", "diffusion_noise_device_s",
    "moe_held16_experts_device_s", "moe_held16_experts_roofline", "moe_held16_route_device_s",
)
S, TOKENS, L = 8192, 2 * 8192, TC["n_layers"]

# -- the reference -------------------------------------------------------------------

SIZES = dict(
    vocab_size=97, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2, head_dim=16, d_ff=16, moe_d_ff=16, norm_eps=1e-6,
    qk_norm=True, qk_norm_per_head=True, rope_pairing="half", rope_theta=1000000,
    n_experts=16, n_experts_held=4, expert_share_index=1, top_k=4, router_gate="softmax", router_renormalize=True,
    diffusion_block=4, diffusion_t_min=0.001, diffusion_mask_id=-1, diffusion_seed=0,
)


@pytest.fixture(scope="module")
def setup():
    cfg = TransformerConfig(dtype=jnp.float32, remat=True, **SIZES)
    params = init_params(jax.random.PRNGKey(3), cfg)
    layers = params["layers"]  # norm weights off 1, or one that is not applied would pass
    layers["ln1"], layers["ln2"], layers["router"] = layers["ln1"] * 1.3, layers["ln2"] * 0.8, layers["router"] * 3.0
    layers["q_norm"], layers["k_norm"] = layers["q_norm"] * 1.2, layers["k_norm"] * 0.9
    params["final_norm"] = params["final_norm"] * 0.7
    tokens = jnp.asarray(np.random.default_rng(0).integers(0, 97, (2, 40)), jnp.int32)
    return cfg, params, tokens


def test_loss_and_gradients_agree(setup):
    cfg, params, tokens = setup
    with jax.default_matmul_precision("highest"):
        got, g_got = jax.jit(jax.value_and_grad(lambda p: loss_fn(p, tokens, cfg)))(params)
    want, g_want = jax.jit(jax.value_and_grad(lambda p: ref.loss(p, tokens, SIZES)))(params)
    assert float(got) == pytest.approx(float(want), rel=2e-6)
    errs = jax.tree_util.tree_map(lambda a, b: float(jnp.max(jnp.abs(a - b)) / (jnp.max(jnp.abs(b)) + 1e-30)), g_got, g_want)
    assert max(jax.tree_util.tree_leaves(errs)) < 5e-5, errs


def test_per_sequence_loss_is_what_the_worker_compares(setup):
    cfg, params, tokens = setup
    with jax.default_matmul_precision("highest"):
        per = ref.per_sequence_loss(params, tokens, SIZES)
        mine = [float(loss_fn(params, tokens[i : i + 1], cfg)) for i in range(2)]
    np.testing.assert_allclose(per, mine, rtol=2e-6)


def test_the_reference_is_independent_of_the_program():
    with open(os.path.join(BENCH, "reference", "sdar_moe_decoder.py")) as f:
        text = f.read()
    assert "import torchft_tpu" not in text and "from torchft_tpu" not in text


# -- the count, worked by hand at the cell's size --------------------------------------------------------


def test_hand_worked_parameters():
    counts = opcount.for_config(CONFIG)
    assert counts.__name__ == "bench_opcount_sdar_moe"
    d = 2048
    attn = 2 * d * 32 * 128 + 2 * d * 4 * 128       # q and o over 32 heads, k and v over 4
    assert attn == 18_874_368 and counts.attn_params(TC) == attn
    expert = 3 * d * 768
    layer = attn + d * 128 + 16 * expert + 2 * d + 2 * 128   # router over all 128, 16 held, two norms, the q / k norms
    assert expert == 4_718_592 and layer == 94_638_336
    tables = 2 * 18992 * d
    assert tables == 77_791_232
    assert counts.n_params(TC) == L * layer + tables + d
    # the depths compile_check.py was asked about: 6, 5 and 4 layers
    by_depth = {n: n * layer + tables + d for n in (6, 5, 4)}
    assert by_depth == {6: 645_623_296, 5: 550_984_960, 4: 456_346_624}
    assert [round(16 * by_depth[n] / 1e9, 2) for n in (6, 5, 4)] == [10.33, 8.82, 7.30]  # f32 parameters, two moments, f32 gradients
    # one layer WHOLE (128 experts) is 623.1 M = 9.97 GB: no chip holds two
    whole = attn + d * 128 + 128 * expert + 2 * d + 2 * 128
    assert whole / 1e6 == pytest.approx(623.1, abs=0.05) and 16 * whole / 1e9 == pytest.approx(9.97, abs=5e-3)
    # the whole model by the same arithmetic: the 30 B of its name, 3 B of them active a token
    model = 48 * whole + 2 * 151936 * d + d
    active = 48 * (attn + d * 128 + 8 * expert + 2 * d + 256) + 2 * 151936 * d + d
    assert model / 1e9 == pytest.approx(30.5, abs=5e-2) and active / 1e9 == pytest.approx(3.35, abs=5e-2)


def test_hand_worked_operations_at_s8192():
    counts = opcount.for_config(CONFIG)
    flops = counts.flops_per_token_by_scope(TC, S)
    # pairs the staircase lets through: S² + 4·S of the (2·S)², S + 4 a trained token a head
    assert counts.keys_per_token(TC, S) == 8196.0 and (S * S + 4 * S) / (512 * 512) == pytest.approx(256.125)
    core = L * 3 * 32 * 2 * 8196 * 256                  # forward and backward, QK and PV 128 wide each
    assert counts.core_flops_per_token(TC, S) == {"blockdiff_core": core} and core / L == 402_849_792
    # a trained token is TWO rows through every layer: projections, router, and 8 x 16 / 128 = 1 pass of an expert each
    assert flops["attn"] == 2 * 6 * L * 18_874_368 + core
    assert flops["moe"] == 2 * 6 * L * (2048 * 128 + 1.0 * 4_718_592)
    assert flops["head_loss"] == 6 * 2048 * 18992       # ONE row: the noised half's
    assert set(flops) == {"attn", "moe", "head_loss"} and counts.ffn_scopes(TC) == ("moe",)
    total = sum(flops.values())
    assert total == counts.flops_per_token(TC, S)
    if L == 6:
        assert total / 1e9 == pytest.approx(4.3680, abs=1e-4) and total * TOKENS / 1e12 == pytest.approx(71.57, abs=1e-2)
        assert total * TOKENS / PEAKS["bf16_flops_per_s"] == pytest.approx(0.3633, abs=1e-4)  # the step at the bf16 peak
        shares = {k: round(100 * v / total) for k, v in {**flops, "core": core}.items()}
        assert shares == {"attn": 86, "moe": 8, "head_loss": 5, "core": 55}
    # the mechanism at work: the same 2·S rows under a full square would be 4 x S² pairs: 4 x what the staircase lets through
    assert (2 * S) ** 2 / (S * S + 4 * S) == pytest.approx(3.998, abs=1e-3)
    # the held experts alone, as ``moe_held16_experts_roofline`` takes them
    experts = counts.experts_flops_per_token(TC) * TOKENS
    assert experts == 2 * 6 * L * 4_718_592 * TOKENS
    # rows the held experts get a layer: 32 768 rows x 8 x 16 / 128, 2 048 an expert at balance
    assert 2 * TOKENS * 8 * 16 // 128 == 32_768 and 32_768 // 16 == 2_048


def test_which_bound_is_the_nearer_at_the_cells_shape():
    counts = opcount.for_config(CONFIG)
    flops, moved = counts.flops_per_token_by_scope(TC, S), counts.bytes_per_step_by_scope(TC, 2, S)
    for scope in ("attn", "moe", "head_loss"):  # operations everywhere
        assert flops[scope] * TOKENS / PEAKS["bf16_flops_per_s"] > 3 * moved[scope] / PEAKS["hbm_bytes_per_s"]
    assert moved["optimizer"] == 26 * counts.n_params(TC)
    core_flops, core_bytes = counts.core_flops_per_token(TC, S), counts.core_bytes_per_step(TC, 2, S)
    assert core_bytes == {"blockdiff_core": L * 2 * TOKENS * 2 * 128 * 6 * (32 + 4)}
    # the core a layer: 6.60 TFLOP = 33.5 ms of operations against 2.2 ms of bytes
    assert core_flops["blockdiff_core"] * TOKENS / L / 1e12 == pytest.approx(6.600, abs=1e-3)
    assert core_flops["blockdiff_core"] * TOKENS / L / PEAKS["bf16_flops_per_s"] == pytest.approx(33.50e-3, rel=1e-3)
    assert core_bytes["blockdiff_core"] / L / PEAKS["hbm_bytes_per_s"] == pytest.approx(2.212e-3, rel=1e-3)
    # the held experts a layer: 4.71 ms of operations against 1.37 ms of bytes (their weights three times, the scope's edge)
    assert counts.experts_flops_per_token(TC) * TOKENS / L / PEAKS["bf16_flops_per_s"] == pytest.approx(4.709e-3, rel=1e-3)
    assert counts.experts_bytes_per_step(TC, 2, S) / L / PEAKS["hbm_bytes_per_s"] == pytest.approx(1.372e-3, rel=1e-3)


# -- the readers -------------------------------------------------------------------------


def reader(name):
    return load_module(os.path.join(BENCH, "layer_metrics", name + ".py"), "m_" + name)


def a_run(config=CONFIG, peaks=PEAKS):
    traffic = {"batch": 2, "seq": S, "steps_per_unit": 5}
    return measure.Run({}, config, traffic, peaks, [{"group": 0, "trace": {"xplane": "no such file"}}])


def test_the_innermost_name_on_a_path_of_the_new_parts():
    draw = "jit(tft_fused)/jvp(embed)/noise/vmap(jit(_uniform))/shift_right_logical"
    assert subscopes.innermost(draw, "embed", ("noise",)) == "noise"
    gather = "jit(tft_fused)/jvp(embed)/jit(_take)/gather"
    assert subscopes.innermost(gather, "embed", ("noise",)) == "embed"
    fwd = "jit(tft_fused)/jvp()/while/body/closed_call/attn/checkpoint/blockdiff/blockdiff_core/flash_fwd/pallas_call"
    assert subscopes.innermost(fwd, "attn", ("blockdiff",)) == "blockdiff"
    assert subscopes.innermost(fwd, "attn", ("blockdiff_core",)) == "blockdiff_core"
    bwd = "jit(tft_fused)/transpose(jvp())/while/body/closed_call/attn/attn/checkpoint/blockdiff/blockdiff_core/flash_bwd/pallas_call"
    assert subscopes.innermost(bwd, "attn", ("blockdiff_core",)) == "blockdiff_core"
    proj = "jit(tft_fused)/jvp()/while/body/closed_call/attn/checkpoint/blockdiff/dot_general"
    assert subscopes.innermost(proj, "attn", ("blockdiff_core",)) == "attn"
    assert subscopes.innermost(proj, "moe", moe_scopes.SUBSCOPES) is None


def test_the_readers_on_rows_written_by_hand(monkeypatch):
    rows = {
        ("embed", ("noise",)): {"noise": 0.0004, "embed": 0.003},
        ("attn", ("blockdiff",)): {"blockdiff": 0.560, "attn": 0.010},
        ("attn", ("blockdiff_core",)): {"blockdiff_core": 0.380, "attn": 0.190},
        ("moe", moe_scopes.SUBSCOPES): {"router": 0.012, "dispatch": 0.020, "experts": 0.060, "combine": 0.024, "moe": 0.004},
    }
    monkeypatch.setattr(subscopes, "seconds", lambda run, parent, names, rows_of=None: rows[(parent, tuple(names))])
    run = a_run()
    got = {name: reader(name).compute(run) for name in READERS}
    assert (got["diffusion_noise_device_s"], got["attn_blockdiff_device_s"], got["blockdiff_core_device_s"]) == (0.0004, 0.560, 0.380)
    assert got["moe_held16_experts_device_s"] == 0.060
    assert got["moe_held16_route_device_s"] == pytest.approx(0.012 + 0.020 + 0.024 + 0.004)  # + the experts = ffn_device_s
    # the least time of each (the test above) over its seconds; a share, never clamped
    assert got["blockdiff_core_roofline"] == pytest.approx(100 * L * 33.50e-3 / 0.380, rel=1e-3)
    assert got["moe_held16_experts_roofline"] == pytest.approx(100 * L * 4.709e-3 / 0.060, rel=1e-3)
    assert all(reader(name).compute(a_run(peaks=None)) is None for name in READERS if name.endswith("_roofline"))
    # every reader has a row of the one table, and nothing else is in it
    assert set(sdar_scopes.SPLITS) | set(sdar_scopes.ROOFLINES) == set(READERS)


def test_nothing_to_read_is_none_and_never_an_error(monkeypatch):
    # a trace that cannot be opened: every reader gives None
    assert all(reader(name).compute(a_run()) is None for name in READERS)
    # a program without the scopes (the parent of the PR that brought them): embed without noise, attn without the kind
    rows = {"embed": {"embed": 0.003}, "attn": {"attn": 0.5}, "moe": {"moe": 0.1}}
    monkeypatch.setattr(subscopes, "seconds", lambda run, parent, names, rows_of=None: rows[parent])
    got = {name: reader(name).compute(a_run()) for name in READERS}
    assert got.pop("moe_held16_route_device_s") == 0.1  # what of moe is not the experts': all of it
    assert all(v is None for v in got.values())
    # a configuration whose count has no such core (opcount.py itself, laguna.py's two)
    monkeypatch.setattr(subscopes, "seconds", lambda run, parent, names, rows_of=None: {"blockdiff_core": 0.1, "experts": 0.1})
    for other in ("olmoe-1g", "laguna-xs2-1g"):
        config = load_json(os.path.join(BENCH, "configs", other + ".json"))
        assert reader("blockdiff_core_roofline").compute(a_run(config=config)) is None
        assert reader("moe_held16_experts_roofline").compute(a_run(config=config)) is None


def test_the_new_entries_list_the_new_cell():
    bench = load_json(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json"))
    listed = {m["name"]: m for m in bench["per_layer"]}
    for name in READERS:
        assert listed[name]["workloads"] == [CELL] and listed[name]["layer"] == "device compute"
        assert listed[name]["moves"] == "step_p50_s" and listed[name]["source"] == "device_trace"
    names = [m["name"] for m in bench["per_layer"]]
    assert names[-len(READERS):] == list(READERS)  # appended together, at the end
    assert bench["workloads"][-1] == next(w for w in bench["workloads"] if w["name"] == CELL)
    cell = bench["workloads"][-1]
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("sdar-30b-a3b-1g", "fused-b2-s8192", 1)
    entry = bench["configs"][-1]
    assert entry["name"] == "sdar-30b-a3b-1g" and sorted(entry["reduced"]) == sorted(CONFIG["reduced"]) and entry["source"] == CONFIG["source"]
    # the configuration says the objective's settings it had to assume, and the program's fields carry them
    assert {"objective", "diffusion_block", "schedule", "mask_id", "noise"} <= set(CONFIG["assumed"])
    assert (TC["diffusion_block"], TC["diffusion_t_min"], TC["diffusion_mask_id"], TC["diffusion_seed"]) == (4, 0.001, -1, 0)
