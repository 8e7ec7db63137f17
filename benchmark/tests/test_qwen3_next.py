"""What the ``qwen3-next-*`` configurations bring: the plain reference
(``reference/qwen3_next_decoder.py``) against the program at a tiny size and
against the delta rule worked by hand, the count (``opcounts/qwen3_next.py``)
against numbers worked by hand at the cell's size, and the eight readers over
``gdn_scopes.py`` on op paths and rows written by hand, and where there is
nothing to read. ``tests/test_gdn.py`` (tier-1) holds the program to the
reference at each kind of layer, the four shares to the uncut layer, and more."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import gdn_scopes
import measure
import opcount
import subscopes
from common import load_json, load_module
from reference import qwen3_next_decoder as ref
from torchft_tpu.models.transformer import TransformerConfig, init_params, loss_fn

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
CONFIG = load_json(os.path.join(BENCH, "configs", "qwen3-next-80b-a3b-1g.json"))
TC = CONFIG["program"]["transformer_config"]
PEAKS = load_json(os.path.join(BENCH, "peaks.json"))["TPU v5 lite"]
CELL = "qwen3-next-80b-a3b-1g.fused-s8192"
READERS = (
    "attn_gdn_device_s", "gdn_core_device_s", "gdn_core_roofline", "attn_gated_softmax_device_s",
    "gated_softmax_core_device_s", "gated_softmax_core_roofline", "moe_shared_gated_device_s", "moe_routed_top10_device_s",
)

# -- the reference -------------------------------------------------------------------

SIZES = dict(
    vocab_size=97, d_model=32, n_layers=4, gdn_layers=[1, 2, 3], linear_n_heads=4, linear_n_key_heads=2, linear_head_dim=8,
    conv_kernel=4, n_heads=4, n_kv_heads=2, head_dim=16, rotary_dim=4, rope_pairing="half", rope_theta=1e7, qk_norm=True,
    qk_norm_per_head=True, norm_zero_centered=True, attn_output_gate=True, norm_eps=1e-6, moe_d_ff=16, n_experts=16,
    n_experts_held=4, expert_share_index=1, top_k=4, n_shared_experts=1, shared_expert_gate=True, router_gate="softmax",
    router_renormalize=True,
)
# float32 on both sides, sums in another order, decays of tens of nats a position behind an L2 norm: float32's own
# distance is 1.3e-4 of a gradient leaf's largest entry here (tests/test_gdn.py holds the reading against float64)
RTOL = 3e-4


@pytest.fixture(scope="module")
def setup():
    cfg = TransformerConfig(dtype=jnp.float32, remat=True, **SIZES)
    params = init_params(jax.random.PRNGKey(3), cfg)
    for layers in params["periods"].values():  # norm weights off their start, or one not applied would pass
        layers["ln1"], layers["ln2"] = layers["ln1"] + 0.3, layers["ln2"] - 0.2
        layers["router"] = layers["router"] * 3.0
        if "q_norm" in layers:
            layers["q_norm"], layers["k_norm"] = layers["q_norm"] + 0.2, layers["k_norm"] - 0.1
        if "o_norm" in layers:
            layers["o_norm"] = layers["o_norm"] * 1.3
    params["final_norm"] = params["final_norm"] - 0.3
    tokens = jnp.asarray(np.random.default_rng(0).integers(0, 97, (2, 72)), jnp.int32)
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
    assert [("w_ba" in w, "q_norm" in w, "shared_scale" in w) for w in layers] == [(True, False, True)] * 3 + [(False, True, True)]
    np.testing.assert_array_equal(layers[2]["w_z"], params["periods"]["gdn.experts"]["w_z"][0, 2])
    np.testing.assert_array_equal(layers[3]["wq"], params["periods"]["full.experts"]["wq"][0, 0])


def test_the_delta_rule_is_the_equations_written_out():
    """One batch, two value heads over ONE key head, two lanes, three positions, by hand: S <- e^g S;
    S <- S + beta k (v - S^T k)^T; o = S^T q — value head j reads key head j // 2 = 0, each under its own decay."""
    q = np.array([[1.0, 0.0], [0.5, 0.5], [0.0, 2.0]])
    k = np.array([[1.0, 0.0], [0.6, 0.8], [0.0, 1.0]])
    v = np.array([[[1.0, 2.0], [-1.0, 0.5]], [[0.0, 1.0], [2.0, 2.0]], [[3.0, -1.0], [0.5, 0.0]]])  # [position, head, lane]
    g = np.array([[-0.1, -2.0], [-0.5, 0.0], [-1.0, -0.3]])
    beta = np.array([[0.5, 1.0], [1.0, 0.25], [0.75, 0.5]])
    want = np.zeros((3, 2, 2))
    for head in range(2):
        S = np.zeros((2, 2))
        for t in range(3):
            S = np.exp(g[t, head]) * S
            S = S + beta[t, head] * np.outer(k[t], v[t, head] - S.T @ k[t])
            want[t, head] = S.T @ q[t]
    f = lambda a: jnp.asarray(a, jnp.float32)
    reads = ref._key_head_of(2, 1)
    assert list(np.asarray(reads)) == [0, 0] and list(np.asarray(ref._key_head_of(32, 16)))[:5] == [0, 0, 1, 1, 2]
    got = ref._delta_rule(f(q)[None, :, None][:, :, reads], f(k)[None, :, None][:, :, reads], f(v)[None], f(g)[None], f(beta)[None])
    np.testing.assert_allclose(np.asarray(got)[0], want, rtol=1e-5, atol=1e-6)
    # a position's own write is read at once, and a head with beta 1 and a unit key stores its value exactly
    np.testing.assert_allclose(want[0, 1], v[0, 1] * 1.0, rtol=1e-6)


# -- the count, by hand at the cell's size ---------------------------------------------


def test_hand_worked_parameters():
    counts = opcount.for_config(CONFIG)
    assert counts.__name__ == "bench_opcount_qwen3_next"
    d = 2048
    qkvz = d * (2 * 16 * 128 + 2 * 32 * 128)          # q, k over 16 key heads; v, z over 32 value heads
    ba, gdn_out = d * 64, 32 * 128 * d
    taps = 4 * (2 * 16 * 128 + 32 * 128)              # the convolution over q | k | v
    assert (qkvz, ba, gdn_out, taps) == (25_165_824, 131_072, 8_388_608, 32_768)
    gdn = qkvz + ba + taps + 64 + 128 + gdn_out        # a_log and dt_bias a value head, the output norm's weight
    assert gdn == 33_718_464 and counts.gdn_params(TC) == (qkvz + ba + gdn_out, gdn)
    wq, wkv, wo = d * 16 * 512, 2 * d * 2 * 256, 16 * 256 * d   # a head's [q | gate]; two key/value heads
    attn = wq + wkv + wo + 512
    assert (wq, wkv, wo, attn) == (16_777_216, 2_097_152, 8_388_608, 27_263_488) and counts.attn_params(TC) == (attn - 512, attn)
    expert = 3 * d * 512
    layer_experts = d * 512 + 32 * expert + expert + d    # router, the 32 held, the shared expert, its gate
    assert (expert, layer_experts) == (3_145_728, 104_859_648)
    gdn_layer, attn_layer = gdn + layer_experts + 2 * d, attn + layer_experts + 2 * d
    assert (gdn_layer, attn_layer) == (138_582_208, 132_127_232)
    total = 3 * gdn_layer + attn_layer + 2 * 18992 * d + d
    assert counts.n_params(TC) == total == 625_667_136
    assert 16 * total / 1e9 == pytest.approx(10.011, abs=5e-3)   # f32 parameters, two moments, f32 gradients
    assert 12 * total / 1e9 == pytest.approx(7.508, abs=5e-3)    # resident: 47 % of the chip's 16 GB
    # the stated fallback, one of 32 chips and 16 held: 424.3 M
    assert counts.n_params({**TC, "n_experts_held": 16}) == total - 4 * 16 * expert == 424_340_544
    # the whole model by the same arithmetic: 36 Gated DeltaNet and 12 attention layers, 512 experts each, both tables
    whole_experts = d * 512 + 512 * expert + expert + d
    whole = 36 * gdn + 12 * attn + 48 * (whole_experts + 2 * d) + 2 * 151936 * d + d
    assert whole / 1e9 == pytest.approx(79.67, abs=1e-2)          # the published 80B
    active = whole - 48 * 502 * expert                             # ten of 512 experts a token
    assert active / 1e9 == pytest.approx(3.875, abs=1e-2) and (active - 2 * 151936 * d) / 1e9 == pytest.approx(3.25, abs=1e-2)  # A3B


def test_hand_worked_operations_at_s8192():
    counts = opcount.for_config(CONFIG)
    flops = counts.flops_per_token_by_scope(TC, 8192)
    cores = counts.core_flops_per_token(TC, 8192)
    assert cores == {"gdn_core": 3 * 3 * 32 * 3 * 2 * 128 * 128, "global_core": 3 * 16 * 2 * 4096.5 * 2 * 256}
    projections = 6 * (3 * 33_685_504 + 27_262_976)
    assert flops["attn"] == projections + cores["gdn_core"] + cores["global_core"]
    # a token passes the router, the shared expert and its gate, and 10 x 32 / 512 = 0.625 of a routed expert, in 4 layers
    assert flops["moe"] == 6 * 4 * (2048 * 512 + 3_145_728 + 2048 + 0.625 * 3_145_728)
    assert flops["head_loss"] == 6 * 2048 * 18992
    assert set(flops) == {"attn", "moe", "head_loss"} and counts.ffn_scopes(TC) == ("moe",)
    total = sum(flops.values())
    assert total == counts.flops_per_token(TC, 8192)
    assert total / 1e9 == pytest.approx(1.3809, abs=1e-3) and total * 2 * 8192 / 1e12 == pytest.approx(22.62, abs=1e-2)
    parts = {
        "gdn projections": 6 * 3 * 33_685_504, "gdn cores": cores["gdn_core"], "softmax projections": 6 * 27_262_976,
        "softmax core": cores["global_core"], "experts": flops["moe"], "head": flops["head_loss"],
    }
    assert {k: round(100 * v / total) for k, v in parts.items()} == {
        "gdn projections": 44, "gdn cores": 2, "softmax projections": 12, "softmax core": 15, "experts": 11, "head": 17,
    }  # the two new mixers: 72 %


def test_which_bound_is_the_nearer_at_the_cells_shape():
    counts = opcount.for_config(CONFIG)
    flops, moved = counts.flops_per_token_by_scope(TC, 8192), counts.bytes_per_step_by_scope(TC, 2, 8192)
    tokens = 2 * 8192
    for scope in ("attn", "head_loss"):  # the scopes of large matmuls: operations, by 10 x and more
        assert flops[scope] * tokens / PEAKS["bf16_flops_per_s"] > 10 * moved[scope] / PEAKS["hbm_bytes_per_s"]
    # the expert layers hold 32 experts a token passes 0.625 of: operations still, by 2.6 x
    assert flops["moe"] * tokens / PEAKS["bf16_flops_per_s"] == pytest.approx(2.6 * moved["moe"] / PEAKS["hbm_bytes_per_s"], rel=2e-2)
    assert moved["optimizer"] == 26 * 625_667_136
    core_flops, core_bytes = counts.core_flops_per_token(TC, 8192), counts.core_bytes_per_step(TC, 2, 8192)
    # the delta rule at its edge, a token a layer: q, k over 16 x 128 and v over 32 x 128 in bfloat16, g and beta float32 a
    # value head — read forward, read again backward, their gradients written — and o written, its gradient read
    operands = (2 * 16 + 32) * 128 * 2 + 2 * 32 * 4
    assert operands == 16_640 and core_bytes["gdn_core"] == 3 * tokens * (3 * operands + 2 * 32 * 128 * 2)
    by_bytes, by_ops = core_bytes["gdn_core"] / PEAKS["hbm_bytes_per_s"], core_flops["gdn_core"] * tokens / PEAKS["bf16_flops_per_s"]
    assert by_bytes == pytest.approx(3.979e-3, rel=1e-3) and by_ops == pytest.approx(2.355e-3, rel=1e-3)  # the bytes bound it
    # the softmax core: 16 query and 2 key/value heads of 256 lanes, six passes of each: the operations, by 30 x
    assert core_bytes["global_core"] == tokens * 2 * 256 * (6 * 16 + 6 * 2)
    by_bytes, by_ops = core_bytes["global_core"] / PEAKS["hbm_bytes_per_s"], core_flops["global_core"] * tokens / PEAKS["bf16_flops_per_s"]
    assert by_bytes == pytest.approx(1.106e-3, rel=1e-3) and by_ops == pytest.approx(16.75e-3, rel=1e-3)


# -- the readers -------------------------------------------------------------------------


def reader(name):
    return load_module(os.path.join(BENCH, "layer_metrics", name + ".py"), "m_" + name)


def a_run(config=CONFIG, peaks=PEAKS):
    traffic = {"batch": 2, "seq": 8192, "steps_per_unit": 5}
    return measure.Run({}, config, traffic, peaks, [{"group": 0, "trace": {"xplane": "no such file"}}])


def test_the_innermost_name_on_a_path_through_attn_and_moe():
    mixers, cores, parts = gdn_scopes.MIXERS, gdn_scopes.CORES, gdn_scopes.MOE_PARTS
    rule = "jit(tft_fused)/transpose(jvp())/while/body/closed_call/attn/gdn/while/body/closed_call/checkpoint/rematted_computation/gdn_core/...ck,...kd->...cd/dot_general"
    assert subscopes.innermost(rule, "attn", mixers) == "gdn" and subscopes.innermost(rule, "attn", cores) == "gdn_core"
    gates = "jit(tft_fused)/jvp()/while/body/closed_call/attn/gdn/while/body/closed_call/gates/exp"
    assert subscopes.innermost(gates, "attn", mixers) == "gdn" and subscopes.innermost(gates, "attn", cores) == "attn"
    kernel = "jit(tft_fused)/jvp()/while/body/closed_call/attn/attn/checkpoint/global/global_core/flash_fwd/pallas_call"
    assert subscopes.innermost(kernel, "attn", mixers) == "global" and subscopes.innermost(kernel, "attn", cores) == "global_core"
    gate = "jit(tft_fused)/jvp()/while/body/closed_call/attn/attn/checkpoint/global/out_gate/mul"
    assert subscopes.innermost(gate, "attn", mixers) == "global" and subscopes.innermost(gate, "attn", cores) == "attn"
    norm = "jit(tft_fused)/jvp()/while/body/closed_call/attn/mul"
    assert subscopes.innermost(norm, "attn", mixers) == "attn"  # outside every mixer: neither kind's
    shared = "jit(tft_fused)/jvp()/while/body/closed_call/moe/moe/checkpoint/shared/logistic"
    assert subscopes.innermost(shared, "moe", parts) == "shared" and subscopes.innermost(shared, "attn", mixers) is None
    held = "jit(tft_fused)/jvp()/while/body/closed_call/moe/moe/checkpoint/cond/branch_1_fun/experts/jit(gmm)/pallas_call"
    assert subscopes.innermost(held, "moe", parts) == "experts"


ROWS = {
    ("attn", gdn_scopes.MIXERS): {"gdn": 0.300, "global": 0.050, "attn": 0.004},
    ("attn", gdn_scopes.CORES): {"gdn_core": 0.160, "global_core": 0.025, "attn": 0.169},
    ("moe", gdn_scopes.MOE_PARTS): {"router": 0.008, "dispatch": 0.010, "experts": 0.030, "combine": 0.006, "shared": 0.010, "moe": 0.002},
}


def test_the_readers_on_rows_written_by_hand(monkeypatch):
    monkeypatch.setattr(subscopes, "seconds", lambda run, parent, names, rows_of=None: ROWS[(parent, tuple(names))])
    run = a_run()
    got = {name: reader(name).compute(run) for name in READERS}
    assert (got["attn_gdn_device_s"], got["attn_gated_softmax_device_s"]) == (0.300, 0.050)  # + 0.004 of neither = attn_device_s
    assert (got["gdn_core_device_s"], got["gated_softmax_core_device_s"]) == (0.160, 0.025)
    assert got["moe_shared_gated_device_s"] == 0.010
    assert got["moe_routed_top10_device_s"] == pytest.approx(0.056)  # all of moe but the shared expert
    # the least time of each core (the test above: the rule's bytes, the softmax's operations) over its seconds
    assert got["gdn_core_roofline"] == pytest.approx(100 * 3.979e-3 / 0.160, rel=1e-3)
    assert got["gated_softmax_core_roofline"] == pytest.approx(100 * 16.75e-3 / 0.025, rel=1e-3)
    fast = {**ROWS, ("attn", gdn_scopes.CORES): {"gdn_core": 0.002, "global_core": 0.010, "attn": 0.342}}
    monkeypatch.setattr(subscopes, "seconds", lambda run, parent, names, rows_of=None: fast[(parent, tuple(names))])
    assert reader("gdn_core_roofline").compute(a_run()) == pytest.approx(198.9, rel=1e-3)  # over 100 and said so
    assert reader("gated_softmax_core_roofline").compute(a_run(peaks=None)) is None


def test_nothing_to_read_is_none_and_never_an_error(monkeypatch):
    # a trace that cannot be opened: every reader gives None
    assert all(reader(name).compute(a_run()) is None for name in READERS)
    # a program without the scopes (the parent commit, another architecture): attn and moe without the names
    monkeypatch.setattr(subscopes, "seconds", lambda run, parent, names, rows_of=None: {parent: 0.5, "kda": 0.1, "router": 0.1})
    assert all(reader(name).compute(a_run()) is None for name in READERS)
    # a configuration whose count has no cores (opcount.py itself), or other cores (lfm2_moe.py)
    monkeypatch.setattr(subscopes, "seconds", lambda run, parent, names, rows_of=None: {"gdn_core": 0.1, "global_core": 0.1})
    for other in ("olmoe-1g", "lfm2-8b-a1b-1g"):
        config = load_json(os.path.join(BENCH, "configs", other + ".json"))
        assert reader("gdn_core_roofline").compute(a_run(config=config)) is None
        assert reader("gated_softmax_core_roofline").compute(a_run(config=config)) is None


def test_the_new_entries_are_found_by_name_and_list_the_new_cell():
    """By name, not by position: a later PR appends after these."""
    bench = load_json(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json"))
    listed = {m["name"]: m for m in bench["per_layer"]}
    for name in READERS:
        assert listed[name]["workloads"] == [CELL] and listed[name]["layer"] == "device compute"
        assert (listed[name]["unit"], listed[name]["better"]) == (("%", "higher") if name.endswith("_roofline") else ("s", "lower"))
    names = [m["name"] for m in bench["per_layer"]]
    assert all(names.index(name) > names.index("gated_conv_core_roofline") for name in READERS)  # after what PR 51 appended
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("qwen3-next-80b-a3b-1g", "fused-b2-s8192", 1)
    entry = next(c for c in bench["configs"] if c["name"] == "qwen3-next-80b-a3b-1g")
    assert entry["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert CONFIG["share"]["chips_per_layer"] == 16 and sorted(CONFIG["reduced"]) == sorted(entry["reduced"])
    assert CELL not in next(m for m in bench["end_to_end"] if m["name"] == "tokens_per_s")["workloads"]
    assert len(bench["workloads"]) == 8 and sum(w["chips"] == 4 for w in bench["workloads"]) == 1
