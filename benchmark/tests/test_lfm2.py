"""What the ``lfm2-*`` configurations bring: the plain reference
(``reference/lfm2_moe_decoder.py``) against the program at a tiny size and against
equations worked by hand, the count (``opcounts/lfm2_moe.py``) against numbers
worked by hand at the cell's size, and the three readers that call
``subscopes.seconds`` themselves on op paths and rows written by hand, and where
there is nothing to read. ``tests/test_gated_conv.py`` (tier-1) holds the program
to the reference at each kind of layer, the four shares to the uncut layer, and more."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import measure
import opcount
import subscopes
from common import load_json, load_module
from reference import lfm2_moe_decoder as ref
from torchft_tpu.models.transformer import TransformerConfig, init_params, loss_fn

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
CONFIG = load_json(os.path.join(BENCH, "configs", "lfm2-8b-a1b-1g.json"))
TC = CONFIG["program"]["transformer_config"]
PEAKS = load_json(os.path.join(BENCH, "peaks.json"))["TPU v5 lite"]
CELL = "lfm2-8b-a1b-1g.fused-s8192"
READERS = ("attn_gated_conv_device_s", "gated_conv_core_device_s", "gated_conv_core_roofline")

# -- the reference -------------------------------------------------------------------

SIZES = dict(
    vocab_size=97, d_model=32, n_layers=6, n_dense_layers=2, conv_layers=[1, 2, 4, 5, 6], conv_kernel=3,
    n_heads=4, n_kv_heads=2, head_dim=8, qk_norm=True, qk_norm_per_head=True, d_ff=64, norm_eps=1e-5,
    moe_d_ff=16, n_experts=16, n_experts_held=4, expert_share_index=1, top_k=4,
    router_gate="sigmoid", router_renormalize=True, router_norm_eps=1e-6, routed_scaling_factor=1.0,
    rope_pairing="half", rope_theta=1000000.0,
)
# float32 on both sides, sums in another order: measured 2e-6 of a gradient leaf's largest entry
RTOL = 5e-5


@pytest.fixture(scope="module")
def setup():
    cfg = TransformerConfig(dtype=jnp.float32, remat=True, **SIZES)
    params = init_params(jax.random.PRNGKey(3), cfg)
    for group in (params["lead"], params["periods"]):  # norm weights off 1 and a bias off 0, or one not applied would pass
        for layers in group.values():
            layers["ln1"], layers["ln2"] = layers["ln1"] * 1.3, layers["ln2"] * 0.8
            if "router" in layers:
                layers["router"] = layers["router"] * 3.0
                layers["router_bias"] = 0.05 * jax.random.normal(jax.random.PRNGKey(7), layers["router_bias"].shape)
            if "q_norm" in layers:
                layers["q_norm"], layers["k_norm"] = layers["q_norm"] * 1.2, layers["k_norm"] * 0.9
    params["final_norm"] = params["final_norm"] * 0.7
    tokens = jnp.asarray(np.random.default_rng(0).integers(0, 97, (2, 40)), jnp.int32)
    return cfg, params, tokens


def test_loss_and_gradients_agree(setup):
    cfg, params, tokens = setup
    with jax.default_matmul_precision("highest"):
        got, g_got = jax.value_and_grad(loss_fn)(params, tokens, cfg)
    want, g_want = jax.value_and_grad(ref.loss)(params, tokens, SIZES)
    assert float(got) == pytest.approx(float(want), rel=2e-6)
    errs = jax.tree_util.tree_map(
        lambda a, b: float(jnp.max(jnp.abs(a - b)) / (jnp.max(jnp.abs(b)) + 1e-30)), g_got, g_want
    )
    assert max(jax.tree_util.tree_leaves(errs)) < RTOL, errs


def test_per_sequence_loss_is_what_the_worker_compares(setup):
    cfg, params, tokens = setup
    with jax.default_matmul_precision("highest"):
        per_seq = ref.per_sequence_loss(params, tokens, SIZES)
        for i in range(2):
            assert float(loss_fn(params, tokens[i : i + 1], cfg)) == pytest.approx(float(per_seq[i]), rel=2e-6)


def test_the_reference_finds_each_layers_parameters_in_the_programs_tree(setup):
    _, params, _ = setup
    layers = ref.layer_params(params, SIZES)
    assert [("conv_in" in w, "wq" in w, "router" in w) for w in layers] == (
        [(True, False, False)] * 2 + [(False, True, True)] + [(True, False, True)] * 3
    )
    np.testing.assert_array_equal(layers[1]["conv_w"], params["lead"]["conv.dense"]["conv_w"][1])
    np.testing.assert_array_equal(layers[4]["conv_in"], params["periods"]["conv.experts"]["conv_in"][1, 0])


def test_the_gated_convolution_is_the_equations_written_out():
    """Two channels, four positions, three taps, by hand: [B, C, X] = h W_in in this order, u = B * X,
    c_t = w0 u_{t-2} + w1 u_{t-1} + w2 u_t with zeros ahead of position 0, y = (C * c) W_out."""
    h = np.array([[1.0, 2.0], [0.5, -1.0], [2.0, 0.0], [-1.0, 1.0]])
    w_in = np.array([[1.0, 0.5, -1.0, 2.0, 0.25, 1.0], [0.0, 1.0, 0.5, -0.5, 2.0, -1.0]])
    taps = np.array([[0.5, -1.0], [2.0, 0.25], [-1.0, 3.0]])
    w_out = np.array([[1.0, -2.0], [0.5, 1.0]])
    cols = h @ w_in
    b, c, x = cols[:, 0:2], cols[:, 2:4], cols[:, 4:6]
    u = b * x
    conv = np.zeros((4, 2))
    for t in range(4):
        for i in range(3):
            if t - 2 + i >= 0:
                conv[t] += taps[i] * u[t - 2 + i]
    want = (c * conv) @ w_out
    w = {name: jnp.asarray(a, jnp.float32) for name, a in (("conv_in", w_in), ("conv_w", taps), ("conv_out", w_out))}
    got = ref._gated_conv(w, jnp.asarray(h, jnp.float32)[None], {"conv_kernel": 3})
    np.testing.assert_allclose(np.asarray(got)[0], want, rtol=1e-5, atol=1e-6)
    assert np.all(conv[0] == taps[2] * u[0])  # position 0 sees itself alone


# -- the count, by hand at the cell's size ---------------------------------------------


def test_hand_worked_parameters():
    counts = opcount.for_config(CONFIG)
    assert counts.__name__ == "bench_opcount_lfm2_moe"
    d = 2048
    conv = d * 3 * d + d * d                          # W_in to B | C | X, W_out
    taps = 3 * d
    assert conv == 16_777_216 and counts.conv_params(TC) == (conv, conv + taps)
    attn = 2 * d * 32 * 64 + 2 * d * 8 * 64          # q and o over 32 heads of 64, k and v over 8
    assert attn == 10_485_760 and counts.attn_params(TC) == (attn, attn + 2 * 64)  # one 64-wide weight for q, one for k
    dense = 3 * d * 7168
    expert = 3 * d * 1792
    sparse = d * 32 + 32 + 8 * expert                # router, selection bias, the 8 held
    assert (dense, expert, sparse) == (44_040_192, 11_010_048, 88_145_952)
    layer01 = conv + taps + dense + 2 * d            # layers 0-1 as the source counts them
    layer2 = attn + 128 + sparse + 2 * d
    layer345 = conv + taps + sparse + 2 * d
    assert (layer01, layer2, layer345) == (60_827_648, 98_635_936, 104_933_408)
    total = 2 * layer01 + layer2 + 3 * layer345 + 2 * 16384 * d + d
    assert counts.n_params(TC) == total == 602_202_368
    assert 16 * total / 1e9 == pytest.approx(9.635, abs=5e-3)   # f32 parameters, two moments, f32 gradients
    assert 12 * total / 1e9 == pytest.approx(7.226, abs=5e-3)   # resident: 45 % of the chip's 16 GB
    # a fifth sparse layer (a second attention layer, off the published 3 : 1) would be 700.8 M, 11.2 GB
    assert (total + layer2) / 1e6 == pytest.approx(700.8, abs=0.05)
    # the whole model by the same arithmetic, the output table tied as published: the published 8.3 B
    whole = 18 * (conv + taps) + 6 * (attn + 128) + 2 * dense + 22 * (d * 32 + 32 + 32 * expert) + 48 * d + d + 65536 * d
    assert whole / 1e9 == pytest.approx(8.34, abs=1e-2)
    # and the parameters a token passes through, four experts of 32: the published ~1.5 B active
    active = whole - 22 * 28 * expert
    assert active / 1e9 == pytest.approx(1.56, abs=1e-2)


def test_hand_worked_operations_at_s8192():
    counts = opcount.for_config(CONFIG)
    flops = counts.flops_per_token_by_scope(TC, 8192)
    core = 3 * 32 * 2 * 4096.5 * 128                  # forward and backward, 32 heads, QK and PV 64 wide each
    mixers = 6 * 5 * 16_777_216
    assert flops["attn"] == mixers + 6 * 10_485_760 + core
    assert flops["ffn"] == 6 * 2 * 44_040_192
    # a token passes the router and 4 x 8 / 32 = 1 of its 4 routed experts here, in each of 4 sparse layers
    assert flops["moe"] == 6 * 4 * (2048 * 32 + 1.0 * 11_010_048)
    assert flops["head_loss"] == 6 * 2048 * 16384
    assert set(flops) == {"attn", "ffn", "moe", "head_loss"} and counts.ffn_scopes(TC) == ("ffn", "moe")
    total = sum(flops.values())
    assert total == counts.flops_per_token(TC, 8192)
    assert total / 1e9 == pytest.approx(1.6625, abs=1e-3) and total * 2 * 8192 / 1e12 == pytest.approx(27.24, abs=1e-2)
    parts = {"conv": mixers, "dense": flops["ffn"], "experts": flops["moe"], "head": flops["head_loss"], "gqa": 6 * 10_485_760 + core}
    assert {k: round(100 * v / total) for k, v in parts.items()} == {"conv": 30, "dense": 32, "experts": 16, "head": 12, "gqa": 10}
    # the elementwise core stays out of the scopes' operations, as every elementwise op does: 7 a channel forward
    assert counts.core_flops_per_token(TC, 8192) == {"gated_conv_core": 3 * 5 * 7 * 2048}


def test_which_bound_is_the_nearer_at_the_cells_shape():
    counts = opcount.for_config(CONFIG)
    flops, moved = counts.flops_per_token_by_scope(TC, 8192), counts.bytes_per_step_by_scope(TC, 2, 8192)
    tokens = 2 * 8192
    for scope in ("attn", "ffn", "head_loss"):  # the scopes with matmuls: operations, by 10 x and more
        assert flops[scope] * tokens / PEAKS["bf16_flops_per_s"] > 10 * moved[scope] / PEAKS["hbm_bytes_per_s"]
    # the sparse layers hold 8 experts a token passes one of: operations by 5.2 x
    assert flops["moe"] * tokens / PEAKS["bf16_flops_per_s"] == pytest.approx(5.2 * moved["moe"] / PEAKS["hbm_bytes_per_s"], rel=2e-2)
    assert moved["optimizer"] == 26 * 602_202_368
    # the convolution's core: 4 arrays forward and 7 backward of [16 384, 2 048] bfloat16 a layer, five layers — the
    # bytes, by four orders: 4.5 ms against 0.018 ms of operations
    core_flops, core_bytes = counts.core_flops_per_token(TC, 8192), counts.core_bytes_per_step(TC, 2, 8192)
    assert core_bytes == {"gated_conv_core": 5 * 11 * tokens * 2048 * 2}
    by_bytes = core_bytes["gated_conv_core"] / PEAKS["hbm_bytes_per_s"]
    by_ops = core_flops["gated_conv_core"] * tokens / PEAKS["bf16_flops_per_s"]
    assert by_bytes == pytest.approx(4.507e-3, rel=1e-3) and by_ops == pytest.approx(1.79e-5, rel=1e-2)
    assert by_bytes > 200 * by_ops


def test_a_model_of_conv_layers_alone_is_counted_too():
    tc = dict(d_model=32, n_layers=2, conv_layers=[1, 2], conv_kernel=4, n_heads=2, head_dim=16, d_ff=64, vocab_size=100)
    counts = opcount.for_config(CONFIG)
    assert counts.n_params(tc) == 2 * (4 * 32 * 32 + 4 * 32 + 3 * 32 * 64 + 64) + 32 + 2 * 100 * 32
    assert counts.ffn_scopes(tc) == ("ffn",) and counts.core_flops_per_token(tc, 64) == {"gated_conv_core": 3 * 2 * 9 * 32}


# -- the readers -------------------------------------------------------------------------


def reader(name):
    return load_module(os.path.join(BENCH, "layer_metrics", name + ".py"), "m_" + name)


def a_run(config=CONFIG, peaks=PEAKS):
    traffic = {"batch": 2, "seq": 8192, "steps_per_unit": 5}
    return measure.Run({}, config, traffic, peaks, [{"group": 0, "trace": {"xplane": "no such file"}}])


def test_the_innermost_name_on_a_path_through_attn():
    kinds, cores = ("gated_conv", "global"), ("gated_conv_core",)
    taps = "jit(tft_fused)/transpose(jvp())/while/body/closed_call/attn/attn/checkpoint/rematted_computation/gated_conv/gated_conv_core/mul"
    assert subscopes.innermost(taps, "attn", kinds) == "gated_conv" and subscopes.innermost(taps, "attn", cores) == "gated_conv_core"
    proj = "jit(tft_fused)/jvp()/while/body/closed_call/attn/attn/checkpoint/gated_conv/dot_general"
    assert subscopes.innermost(proj, "attn", kinds) == "gated_conv"
    assert subscopes.innermost(proj, "attn", cores) == "attn"  # of attn, and not of the core
    scan = "jit(tft_fused)/jvp()/attn/attn/checkpoint/global/global_core/while/body/checkpoint/dot_general"
    assert subscopes.innermost(scan, "attn", kinds) == "global" and subscopes.innermost(scan, "attn", cores) == "attn"
    norm = "jit(tft_fused)/jvp()/while/body/closed_call/attn/mul"
    assert subscopes.innermost(norm, "attn", kinds) == "attn"  # outside every checkpoint: neither kind's
    held = "jit(tft_fused)/jvp()/while/body/closed_call/moe/checkpoint/cond/branch_1_fun/experts/jit(gmm)/pallas_call"
    assert subscopes.innermost(held, "attn", kinds) is None


def test_the_readers_on_rows_written_by_hand(monkeypatch):
    rows = {
        ("gated_conv", "global"): {"gated_conv": 0.090, "global": 0.060, "attn": 0.002},
        ("gated_conv_core",): {"gated_conv_core": 0.020, "attn": 0.132},
    }
    monkeypatch.setattr(subscopes, "seconds", lambda run, parent, names, rows_of=None: rows[tuple(names)] if parent == "attn" else None)
    run = a_run()
    got = {name: reader(name).compute(run) for name in READERS}
    assert got["attn_gated_conv_device_s"] == 0.090  # + 0.060 of the attention layer + 0.002 of neither = attn_device_s
    assert got["gated_conv_core_device_s"] == 0.020
    # the least time of the core (the test above: its bytes) over its seconds; a share, never clamped
    assert got["gated_conv_core_roofline"] == pytest.approx(100 * 4.507e-3 / 0.020, rel=1e-3)
    fast = {**rows, ("gated_conv_core",): {"gated_conv_core": 0.002, "attn": 0.150}}
    monkeypatch.setattr(subscopes, "seconds", lambda run, parent, names, rows_of=None: fast[tuple(names)])
    assert reader("gated_conv_core_roofline").compute(a_run()) == pytest.approx(225.3, rel=1e-3)  # over 100 and said so
    assert reader("gated_conv_core_roofline").compute(a_run(peaks=None)) is None


def test_nothing_to_read_is_none_and_never_an_error(monkeypatch):
    # a trace that cannot be opened: every reader gives None
    assert all(reader(name).compute(a_run()) is None for name in READERS)
    # a program without the scopes (the parent commit, another architecture): attn without the names
    monkeypatch.setattr(subscopes, "seconds", lambda run, parent, names, rows_of=None: {"attn": 0.5, "global": 0.1})
    assert all(reader(name).compute(a_run()) is None for name in READERS)
    # a configuration whose count has no cores (opcount.py itself), or other cores (laguna.py)
    monkeypatch.setattr(subscopes, "seconds", lambda run, parent, names, rows_of=None: {"gated_conv_core": 0.1})
    for other in ("olmoe-1g", "laguna-xs2-1g"):
        config = load_json(os.path.join(BENCH, "configs", other + ".json"))
        assert reader("gated_conv_core_roofline").compute(a_run(config=config)) is None


def test_the_new_entries_are_found_by_name_and_list_the_new_cell():
    """By name, not by position: a later PR appends after these."""
    bench = load_json(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json"))
    listed = {m["name"]: m for m in bench["per_layer"]}
    for name in READERS:
        assert listed[name]["workloads"] == [CELL] and listed[name]["layer"] == "device compute"
    names = [m["name"] for m in bench["per_layer"]]
    assert all(names.index(name) > names.index("heal_fetch_streams") for name in READERS)  # after what PR 49 appended
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("lfm2-8b-a1b-1g", "fused-b2-s8192", 1)
    entry = next(c for c in bench["configs"] if c["name"] == "lfm2-8b-a1b-1g")
    assert entry["reduced"] == ["num_hidden_layers", "layer_types", "num_experts", "vocab_size"]
    assert CONFIG["share"]["chips_per_layer"] == 4 and sorted(CONFIG["reduced"]) == sorted(entry["reduced"])
    assert CELL not in next(m for m in bench["end_to_end"] if m["name"] == "tokens_per_s")["workloads"]
