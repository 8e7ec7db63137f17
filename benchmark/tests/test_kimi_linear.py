"""What the ``kimi-linear-*`` configurations bring: the plain reference
(``reference/kimi_linear_decoder.py``) against the program at a tiny size, the
count (``opcounts/kimi_linear.py``) against numbers worked by hand at the cell's
size, and the eight readers over ``hybrid_scopes.py`` on op paths and rows written
by hand, and where there is nothing to read. ``tests/test_hybrid.py`` (tier-1)
holds the program to the reference at each kind of layer and more."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import hybrid_scopes
import measure
import opcount
import subscopes
from common import load_json, load_module
from reference import kimi_linear_decoder as ref
from torchft_tpu.models.transformer import TransformerConfig, init_params, loss_fn

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
CONFIG = load_json(os.path.join(BENCH, "configs", "kimi-linear-1g.json"))
TC = CONFIG["program"]["transformer_config"]
PEAKS = load_json(os.path.join(BENCH, "peaks.json"))["TPU v5 lite"]
CELL = "kimi-linear-1g.fused-s8192"
READERS = (
    "attn_kda_device_s", "attn_mla_device_s", "kda_core_device_s", "kda_core_roofline",
    "mla_core_device_s", "mla_core_roofline", "moe_shared_device_s", "moe_routed_device_s",
)

# -- the reference -------------------------------------------------------------------

SIZES = dict(
    vocab_size=97, d_model=32, n_layers=5, n_heads=2, head_dim=16, d_ff=64, norm_eps=1e-5,
    kda_layers=[1, 2, 3, 5], mla_layers=[4], n_dense_layers=1,
    moe_d_ff=16, n_experts=16, n_experts_held=4, expert_share_index=1, top_k=4, n_shared_experts=1,
    router_gate="sigmoid", router_renormalize=True, routed_scaling_factor=2.446,
    kv_lora_rank=8, qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8,
    linear_head_dim=8, linear_n_heads=4, conv_kernel=4,
)
# float32 on both sides, sums in another order (chunked against the recurrence, sorted rows against
# every held expert over every token): measured 1e-4 of a gradient leaf's largest entry, as tests/test_hybrid.py
RTOL = 5e-4


@pytest.fixture(scope="module")
def setup():
    cfg = TransformerConfig(dtype=jnp.float32, remat=True, **SIZES)
    params = init_params(jax.random.PRNGKey(3), cfg)
    # norm weights off 1 and a selection bias off 0, or one that is not applied would pass
    for group in (params["lead"], params["periods"]):
        for layers in group.values():
            layers["ln1"], layers["ln2"] = layers["ln1"] * 1.3, layers["ln2"] * 0.8
            if "router_bias" in layers:
                layers["router_bias"] = 0.05 * jnp.cos(jnp.arange(16.0)) + jnp.zeros_like(layers["router_bias"])
                layers["router"] = layers["router"] * 3.0
    params["final_norm"] = params["final_norm"] * 0.7
    tokens = jnp.asarray(np.random.default_rng(0).integers(0, 97, (2, 48)), jnp.int32)
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
    per_seq = ref.per_sequence_loss(params, tokens, SIZES)
    for i in range(2):
        assert float(loss_fn(params, tokens[i : i + 1], cfg)) == pytest.approx(float(per_seq[i]), rel=2e-6)


def test_the_reference_finds_each_layers_parameters_in_the_programs_tree(setup):
    _, params, _ = setup
    layers = ref.layer_params(params, SIZES)
    assert ["wo" in w and ("a_log" in w, "w_kva" in w, "router" in w) for w in layers] == [
        (True, False, False), (True, False, True), (True, False, True), (False, True, True), (True, False, True),
    ]
    # layers 2, 3 and 5 are the period's three KDA slots with experts, in order
    np.testing.assert_array_equal(layers[4]["wq"], params["periods"]["kda.experts"]["wq"][0, 2])
    np.testing.assert_array_equal(layers[0]["wq"], params["periods"]["kda.dense"]["wq"][0, 0])


def test_the_recurrence_is_the_rule_written_out():
    """One head, three positions, by hand: S <- diag(e^g) S; S <- S + b k (v - S^T k)^T; o = S^T q."""
    w = {
        "wq": jnp.eye(2), "wk": jnp.eye(2), "wv": jnp.eye(2), "wo": jnp.eye(2), "o_norm": jnp.ones(2),
        "conv_q": jnp.array([[0.0, 0.0], [1.0, 1.0]]), "conv_k": jnp.array([[0.0, 0.0], [1.0, 1.0]]),
        "conv_v": jnp.array([[0.0, 0.0], [1.0, 1.0]]),  # the current position alone
        "w_fa": jnp.zeros((2, 2)), "w_fb": jnp.zeros((2, 2)), "dt_bias": jnp.array([0.3, 1.2]), "a_log": jnp.array([0.5]),
        "w_beta": jnp.array([[0.7], [-0.4]]), "w_ga": jnp.zeros((2, 2)), "w_gb": jnp.zeros((2, 2)),
    }
    h = jnp.array([[[0.5, -1.0], [1.5, 0.25], [-0.75, 2.0]]])
    got = ref._kda(w, h, {"linear_n_heads": 1, "linear_head_dim": 2}, 1e-5)[0]
    silu = lambda x: x / (1 + np.exp(-x))
    S, outs = np.zeros((2, 2)), []
    decay = np.exp(-np.exp(0.5) * np.log1p(np.exp(np.array([0.3, 1.2]))))
    for x in np.asarray(h[0], np.float64):
        a = silu(x)
        unit = a / np.sqrt(np.sum(a * a) + 1e-6)
        q, k, v = unit * 2**-0.5, unit, a
        beta = 1 / (1 + np.exp(-(0.7 * x[0] - 0.4 * x[1])))
        S = decay[:, None] * S
        S = S + beta * np.outer(k, v - S.T @ k)
        o = S.T @ q
        outs.append(o / np.sqrt(np.mean(o * o) + 1e-5) * 0.5)  # the output gate is sigmoid(0)
    np.testing.assert_allclose(got, np.array(outs), rtol=2e-5, atol=1e-6)


# -- the count, by hand at the cell's size ---------------------------------------------


def test_hand_worked_parameters():
    counts = opcount.for_config(CONFIG)
    assert counts.__name__ == "bench_opcount_kimi_linear"
    d = 2304
    kda_matmul = 4 * d * 4096 + 2 * (d * 128 + 128 * 4096) + d * 32     # q k v o, two low-rank gates, beta
    assert kda_matmul == 39_460_864
    kda = kda_matmul + 3 * 4 * 4096 + 32 + 4096 + 128                   # conv filters, a_log, dt_bias, output norm
    mla_matmul = d * 32 * 192 + d * 576 + 512 * 32 * 256 + 4096 * d     # W_q, W_kva, W_kvb, W_o
    assert (kda, mla_matmul, mla_matmul + 512) == (39_514_272, 29_114_368, 29_114_880)
    assert counts.kda_params(TC) == (kda_matmul, kda) and counts.mla_params(TC) == (mla_matmul, mla_matmul + 512)
    expert = 3 * d * 1024
    layer_experts = d * 256 + 256 + expert + 8 * expert                 # router, its bias, the shared expert, 8 held
    assert expert == 7_077_888 and layer_experts == 64_291_072
    total = 4 * kda + (mla_matmul + 512) + 3 * d * 9216 + 4 * layer_experts + 5 * 2 * d + d + 2 * 20480 * d
    assert counts.n_params(TC) == total == 602_434_432
    # ISSUE 35 quotes PR 34's 602 416 384: 18 048 fewer, the small leaves (0.003 %)
    assert abs(total - 602_416_384) < 1e-4 * total


def test_hand_worked_operations_at_s8192():
    counts = opcount.for_config(CONFIG)
    flops = counts.flops_per_token_by_scope(TC, 8192)
    kda_core = 3 * 32 * 3 * 2 * 128 * 128        # three D x D matrix-vector products a head, forward and backward
    mla_core = 3 * 32 * 2 * 4096 * (192 + 128)   # half the square, keys 192 and values 128 wide
    assert counts.core_flops_per_token(TC, 8192) == {"kda_core": 4 * kda_core, "mla_core": mla_core}
    assert flops["attn"] == 6 * (4 * 39_460_864 + 29_114_368) + 4 * kda_core + mla_core
    assert flops["ffn"] == 6 * 3 * 2304 * 9216
    # a token passes the router, the shared expert and 8 x 8 / 256 = 0.25 of its 8 routed experts here
    assert flops["moe"] == 6 * 4 * (2304 * 256 + 7_077_888 + 0.25 * 7_077_888)
    assert flops["head_loss"] == 6 * 2304 * 20480
    assert set(flops) == {"attn", "ffn", "moe", "head_loss"} and counts.ffn_scopes(TC) == ("ffn", "moe")
    assert sum(flops.values()) / 1e9 == pytest.approx(2.303, abs=1e-3)
    assert sum(flops.values()) * 2 * 8192 / 1e12 == pytest.approx(37.73, abs=1e-2)


def test_which_bound_is_the_nearer_at_the_cells_shape():
    counts = opcount.for_config(CONFIG)
    flops, moved = counts.flops_per_token_by_scope(TC, 8192), counts.bytes_per_step_by_scope(TC, 2, 8192)
    tokens = 2 * 8192
    for scope in ("attn", "ffn", "moe", "head_loss"):  # the matmul scopes: operations, by 5 x and more
        assert flops[scope] * tokens / PEAKS["bf16_flops_per_s"] > 5 * moved[scope] / PEAKS["hbm_bytes_per_s"]
    assert moved["optimizer"] == 26 * 602_434_432
    core_flops, core_bytes = counts.core_flops_per_token(TC, 8192), counts.core_bytes_per_step(TC, 2, 8192)
    # the MLA core: operations (20.9 ms against 2.5); the KDA cores: BYTES (11.2 ms against 3.1)
    assert core_flops["mla_core"] * tokens / PEAKS["bf16_flops_per_s"] == pytest.approx(20.9e-3, rel=1e-2)
    assert core_bytes["mla_core"] == tokens * 32 * 2 * ((384 + 256) + (384 + 384) + (384 + 128))
    assert core_bytes["kda_core"] == 4 * tokens * (4096 * (12 + 12 + 10) + 32 * 12)
    assert core_bytes["kda_core"] / PEAKS["hbm_bytes_per_s"] == pytest.approx(11.2e-3, rel=1e-2)
    assert core_flops["kda_core"] * tokens / PEAKS["bf16_flops_per_s"] == pytest.approx(3.14e-3, rel=1e-2)


# -- the readers -------------------------------------------------------------------------


def reader(name):
    return load_module(os.path.join(BENCH, "layer_metrics", name + ".py"), "m_" + name)


def a_run(config=CONFIG, peaks=PEAKS):
    traffic = {"batch": 2, "seq": 8192, "steps_per_unit": 5}
    return measure.Run({}, config, traffic, peaks, [{"group": 0, "trace": {"xplane": "no such file"}}])


def test_the_innermost_name_on_a_path_through_attn_and_moe():
    kda_core = "jit(tft_fused)/transpose(jvp())/while/body/closed_call/checkpoint/rematted_computation/attn/kda/while/body/checkpoint/kda_core/while/body/bhck,bhkd->bhcd/dot_general"
    assert subscopes.innermost(kda_core, "attn", hybrid_scopes.MIXERS) == "kda"
    assert subscopes.innermost(kda_core, "attn", hybrid_scopes.CORES) == "kda_core"
    gates = "jit(tft_fused)/jvp()/checkpoint/attn/kda/while/body/checkpoint/gates/dot_general"
    assert subscopes.innermost(gates, "attn", hybrid_scopes.MIXERS) == "kda"
    assert subscopes.innermost(gates, "attn", hybrid_scopes.CORES) == "attn"  # of attn, and of neither core
    flash = "jit(tft_fused)/jvp()/while/body/closed_call/checkpoint/attn/mla/mla_core/shard_map/flash_fwd/pallas_call"
    assert subscopes.innermost(flash, "attn", hybrid_scopes.MIXERS) == "mla"
    assert subscopes.innermost(flash, "attn", hybrid_scopes.CORES) == "mla_core"
    norm = "jit(tft_fused)/jvp()/while/body/closed_call/checkpoint/attn/mul"
    assert subscopes.innermost(norm, "attn", hybrid_scopes.MIXERS) == "attn"  # the layer's first norm: neither mixer's
    shared = "jit(tft_fused)/jvp()/while/body/closed_call/checkpoint/moe/shared/dot_general"
    held = "jit(tft_fused)/jvp()/while/body/closed_call/checkpoint/moe/cond/branch_1_fun/experts/jit(gmm)/pallas_call"
    assert subscopes.innermost(shared, "moe", hybrid_scopes.MOE_PARTS) == "shared"
    assert subscopes.innermost(held, "moe", hybrid_scopes.MOE_PARTS) == "experts"
    assert subscopes.innermost(shared, "attn", hybrid_scopes.MIXERS) is None


def test_the_readers_on_rows_written_by_hand(monkeypatch):
    rows = {
        ("attn", hybrid_scopes.MIXERS): {"kda": 0.60, "mla": 0.10, "attn": 0.02},
        ("attn", hybrid_scopes.CORES): {"kda_core": 0.40, "mla_core": 0.05, "attn": 0.27},
        ("moe", hybrid_scopes.MOE_PARTS): {"router": 0.004, "dispatch": 0.010, "experts": 0.006, "combine": 0.012, "shared": 0.030, "moe": 0.003},
    }
    monkeypatch.setattr(subscopes, "seconds", lambda run, parent, names, rows_of=None: rows[(parent, tuple(names))])
    run = a_run()
    got = {name: reader(name).compute(run) for name in READERS}
    assert (got["attn_kda_device_s"], got["attn_mla_device_s"]) == (0.60, 0.10)  # + 0.02 of neither = attn_device_s
    assert (got["kda_core_device_s"], got["mla_core_device_s"], got["moe_shared_device_s"]) == (0.40, 0.05, 0.030)
    assert got["moe_routed_device_s"] == pytest.approx(0.004 + 0.010 + 0.006 + 0.012 + 0.003)
    # the least time of each core (the test above) over its seconds; a share, never clamped
    assert got["kda_core_roofline"] == pytest.approx(100 * 11.175e-3 / 0.40, rel=1e-3)
    assert got["mla_core_roofline"] == pytest.approx(100 * 20.93e-3 / 0.05, rel=1e-3)
    assert all(reader(name).compute(a_run(peaks=None)) is None for name in READERS if name.endswith("_roofline"))


def test_nothing_to_read_is_none_and_never_an_error(monkeypatch):
    # a trace that cannot be opened: every reader gives None
    assert all(reader(name).compute(a_run()) is None for name in READERS)
    # a program without the scopes (the parent commit, another architecture): moe without `shared`, attn without mixers
    rows = {"attn": {"attn": 0.5}, "moe": {"router": 0.01, "dispatch": 0.02, "experts": 0.1, "combine": 0.02, "moe": 0.001}}
    monkeypatch.setattr(subscopes, "seconds", lambda run, parent, names, rows_of=None: rows[parent])
    assert all(reader(name).compute(a_run()) is None for name in READERS)
    # a configuration whose count has no cores (opcount.py itself)
    olmoe = load_json(os.path.join(BENCH, "configs", "olmoe-1g.json"))
    monkeypatch.setattr(subscopes, "seconds", lambda run, parent, names, rows_of=None: {"kda_core": 0.1, "mla_core": 0.1})
    assert reader("kda_core_roofline").compute(a_run(config=olmoe)) is None


def test_the_new_entries_list_the_new_cell_and_nothing_else_changed():
    bench = load_json(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json"))
    listed = {m["name"]: m for m in bench["per_layer"]}
    for name in READERS:
        assert listed[name]["workloads"] == [CELL] and listed[name]["layer"] == "device compute"
    assert [w["name"] for w in bench["workloads"]][-1] == CELL and [c["name"] for c in bench["configs"]][-1] == "kimi-linear-1g"
