"""What the ``laguna-*`` configurations bring: the plain reference
(``reference/laguna_decoder.py``) against the program at a tiny size, the count
(``opcounts/laguna.py``) against numbers worked by hand at the cell's size, and
the seven readers over ``window_scopes.py`` and ``hybrid_scopes.py`` on op paths
and rows written by hand, and where there is nothing to read.
``tests/test_window_gqa.py`` (tier-1) holds the program to the reference at each
kind of layer, the three cores to one another at the band's edges, and more."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import hybrid_scopes
import measure
import opcount
import subscopes
import window_scopes
from common import load_json, load_module
from reference import laguna_decoder as ref
from torchft_tpu.models.transformer import TransformerConfig, init_params, loss_fn

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
CONFIG = load_json(os.path.join(BENCH, "configs", "laguna-xs2-1g.json"))
TC = CONFIG["program"]["transformer_config"]
PEAKS = load_json(os.path.join(BENCH, "peaks.json"))["TPU v5 lite"]
CELL = "laguna-xs2-1g.fused-s8192"
READERS = (
    "attn_window_device_s", "attn_global_device_s", "window_core_device_s", "window_core_roofline",
    "global_core_device_s", "global_core_roofline", "moe_held_device_s",
)

# -- the reference -------------------------------------------------------------------

SIZES = dict(
    vocab_size=97, d_model=32, n_layers=5, n_heads=6, n_kv_heads=2, head_dim=16, d_ff=64, norm_eps=1e-6,
    n_heads_per_layer=[6, 8, 8, 8, 6, 8, 8], window=8, window_layers=[2, 3, 4], n_dense_layers=1,
    moe_d_ff=16, n_experts=16, n_experts_held=4, expert_share_index=1, top_k=4, n_shared_experts=1,
    router_gate="sigmoid", router_renormalize=True, routed_scaling_factor=2.5, router_selection_bias=False,
    rope_pairing="half", rotary_dim=8, rope_theta=500000.0, window_rope_theta=10000.0,
    yarn_factor=64.0, yarn_original_max=64, yarn_beta_fast=8.0, yarn_beta_slow=1.0,
    yarn_attention_factor=1.4158883083359672,
)
# float32 on both sides, sums in another order: measured 1.6e-6 of a gradient leaf's largest entry
RTOL = 5e-5


@pytest.fixture(scope="module")
def setup():
    cfg = TransformerConfig(dtype=jnp.float32, remat=True, **SIZES)
    params = init_params(jax.random.PRNGKey(3), cfg)
    for layers in params["periods"].values():  # norm weights off 1, or one that is not applied would pass
        layers["ln1"], layers["ln2"] = layers["ln1"] * 1.3, layers["ln2"] * 0.8
        if "router" in layers:
            layers["router"] = layers["router"] * 3.0
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
    assert [(w["wq"].shape[-1] // 16, "router" in w) for w in layers] == [(6, False), (8, True), (8, True), (8, True), (6, True)]
    np.testing.assert_array_equal(layers[3]["wq"], params["periods"]["window.experts"]["wq"][0, 2])
    np.testing.assert_array_equal(layers[4]["wq"], params["periods"]["full.experts"]["wq"][0, 0])


def test_attention_is_the_equations_written_out():
    """Two query heads over one key/value head of four lanes, three positions,
    a band of two, by hand: rotation of lanes (0, 1) with an angle of the
    position (r = 2 of 4 lanes; the others pass), scores over the band, softmax."""
    sizes = dict(n_heads=2, n_kv_heads=1, head_dim=4, window=2, rope_theta=100.0, window_rope_theta=100.0, rotary_dim=2)
    h = jnp.array([[[0.5, -1.0, 0.3, 0.2], [1.5, 0.25, -0.4, 0.1], [-0.75, 2.0, 0.6, -0.3]]])
    eye, two = jnp.eye(4), jnp.concatenate([jnp.eye(4), 0.5 * jnp.eye(4)], axis=1)
    w = {"wq": two, "wk": eye, "wv": eye, "wo": jnp.concatenate([jnp.eye(4), jnp.eye(4)], axis=0)}
    got = np.asarray(ref._attention(w, h, sizes, 1, "full"))[0]  # global: every key up to the position
    x = np.asarray(h[0], np.float64)

    def turned(row, pos):  # lane 0 with lane 1 at frequency 1 (r = 2: one pair), lanes 2, 3 pass
        a, b = row[0], row[1]
        return np.array([a * np.cos(pos) - b * np.sin(pos), b * np.cos(pos) + a * np.sin(pos), row[2], row[3]])

    keys = np.stack([turned(x[p], p) for p in range(3)])
    for window, mixer in ((None, "full"), (2, "window")):
        if mixer == "window":  # r = 4: lane 0 with lane 2 at 1, lane 1 with lane 3 at 100^(-1/2)
            def turned(row, pos):  # noqa: F811
                out = np.empty(4)
                for lane, f in ((0, 1.0), (1, 0.1)):
                    a, b = row[lane], row[lane + 2]
                    out[lane], out[lane + 2] = a * np.cos(pos * f) - b * np.sin(pos * f), b * np.cos(pos * f) + a * np.sin(pos * f)
                return out
            keys = np.stack([turned(x[p], p) for p in range(3)])
            got = np.asarray(ref._attention(w, h, sizes, 1, "window"))[0]
        want = np.zeros((3, 4))
        for p in range(3):
            for scale in (1.0, 0.5):  # the two query heads, both reading the one key/value head
                q = turned(scale * x[p], p)
                seen = [j for j in range(3) if j <= p and (window is None or j > p - window)]
                s = np.array([q @ keys[j] / 2.0 for j in seen])
                prob = np.exp(s - s.max()) / np.exp(s - s.max()).sum()
                want[p] += sum(pj * x[j] for pj, j in zip(prob, seen))
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=1e-6)


# -- the count, by hand at the cell's size ---------------------------------------------


def test_hand_worked_parameters():
    counts = opcount.for_config(CONFIG)
    assert counts.__name__ == "bench_opcount_laguna"
    d = 2048
    glob = 2 * d * 48 * 128 + 2 * d * 8 * 128      # q and o over 48 heads, k and v over 8
    wind = 2 * d * 64 * 128 + 2 * d * 8 * 128      # q and o over 64 heads
    assert (glob, wind) == (29_360_128, 37_748_736)
    assert counts.attn_params(TC, 48) == glob and counts.attn_params(TC, 64) == wind
    assert counts.layer_heads(TC) == [("global", 48), ("window", 64), ("window", 64), ("window", 64), ("global", 48)]
    expert = 3 * d * 512
    sparse = d * 256 + expert + 32 * expert          # router (no selection bias), the shared expert, 32 held
    assert expert == 3_145_728 and sparse == 104_333_312
    layer0 = glob + 3 * d * 8192 + 2 * d
    window_layer, layer4 = wind + sparse + 2 * d, glob + sparse + 2 * d
    assert (layer0, window_layer, layer4) == (79_695_872, 142_086_144, 133_697_536)
    total = layer0 + 3 * window_layer + layer4 + 2 * 12544 * d + d
    assert counts.n_params(TC) == total == 691_034_112
    assert 16 * total / 1e9 == pytest.approx(11.06, abs=5e-3)  # f32 parameters, two moments, f32 gradients
    # the whole model by the same arithmetic: the published 33.4 B with three matrices an expert (`assumed.gating`)
    whole = 10 * glob + 30 * wind + 39 * (257 * expert + d * 256) + 3 * d * 8192 + 2 * 100352 * d
    assert whole / 1e9 == pytest.approx(33.44, abs=5e-3)
    two = 10 * glob + 30 * wind + 39 * (257 * 2 * d * 512 + d * 256) + 2 * d * 8192 + 2 * 100352 * d
    assert two / 1e9 == pytest.approx(22.9, abs=5e-2)


def test_hand_worked_operations_at_s8192():
    counts = opcount.for_config(CONFIG)
    flops = counts.flops_per_token_by_scope(TC, 8192)
    band = (512 * 513 / 2 + (8192 - 512) * 512) / 8192   # the first 512 positions see fewer than 512 keys
    assert band == 496.03125 and counts.keys_per_query(TC, 8192) == {"global": 4096.5, "window": band}
    window_core = 3 * 3 * 64 * 2 * band * 256            # three layers, forward and backward, QK and PV 128 wide each
    global_core = 3 * 2 * 48 * 2 * 4096.5 * 256
    assert counts.core_flops_per_token(TC, 8192) == {"window_core": window_core, "global_core": global_core}
    assert flops["attn"] == 6 * (2 * 29_360_128 + 3 * 37_748_736) + window_core + global_core
    assert flops["ffn"] == 6 * 3 * 2048 * 8192
    # a token passes the router, the shared expert and 8 x 32 / 256 = 1 of its 8 routed experts here
    assert flops["moe"] == 6 * 4 * (2048 * 256 + 3_145_728 + 1.0 * 3_145_728)
    assert flops["head_loss"] == 6 * 2048 * 12544
    assert set(flops) == {"attn", "ffn", "moe", "head_loss"} and counts.ffn_scopes(TC) == ("ffn", "moe")
    total = sum(flops.values())
    assert total / 1e9 == pytest.approx(2.402, abs=1e-3) and total * 2 * 8192 / 1e12 == pytest.approx(39.35, abs=1e-2)
    # the mechanism at work: without the band the three window cores would be 8.26 x what they are,
    # 44 % more operations in the step
    full = 3 * 3 * 64 * 2 * 4096.5 * 256
    assert full / window_core == pytest.approx(8.26, abs=1e-2) and (full - window_core) / total == pytest.approx(0.44, abs=5e-3)
    shares = {k: round(100 * v / total) for k, v in {**flops, "window": window_core, "global": global_core}.items()}
    assert shares == {"attn": 74, "ffn": 13, "moe": 7, "head_loss": 6, "window": 6, "global": 25}


def test_which_bound_is_the_nearer_at_the_cells_shape():
    counts = opcount.for_config(CONFIG)
    flops, moved = counts.flops_per_token_by_scope(TC, 8192), counts.bytes_per_step_by_scope(TC, 2, 8192)
    tokens = 2 * 8192
    for scope in ("attn", "ffn", "head_loss"):  # operations, by 5 x and more
        assert flops[scope] * tokens / PEAKS["bf16_flops_per_s"] > 5 * moved[scope] / PEAKS["hbm_bytes_per_s"]
    # the sparse layers hold 32 experts a token passes one of: operations by 2.9 x only
    assert flops["moe"] * tokens / PEAKS["bf16_flops_per_s"] == pytest.approx(2.9 * moved["moe"] / PEAKS["hbm_bytes_per_s"], rel=2e-2)
    assert moved["optimizer"] == 26 * 691_034_112
    core_flops, core_bytes = counts.core_flops_per_token(TC, 8192), counts.core_bytes_per_step(TC, 2, 8192)
    assert core_bytes == {"window_core": 3 * tokens * 2 * 128 * 6 * (64 + 8), "global_core": 2 * tokens * 2 * 128 * 6 * (48 + 8)}
    # both cores: operations (12.2 ms against 6.6 of bytes; 50.2 against 3.4)
    assert core_flops["window_core"] * tokens / PEAKS["bf16_flops_per_s"] == pytest.approx(12.17e-3, rel=1e-3)
    assert core_bytes["window_core"] / PEAKS["hbm_bytes_per_s"] == pytest.approx(6.64e-3, rel=1e-3)
    assert core_flops["global_core"] * tokens / PEAKS["bf16_flops_per_s"] == pytest.approx(50.24e-3, rel=1e-3)
    assert core_bytes["global_core"] / PEAKS["hbm_bytes_per_s"] == pytest.approx(3.44e-3, rel=1e-3)


def test_a_model_without_a_band_or_experts_is_counted_too():
    tc = dict(d_model=32, n_layers=2, n_heads=4, n_kv_heads=2, head_dim=8, d_ff=64, vocab_size=100)
    counts = opcount.for_config(CONFIG)
    assert counts.n_params(tc) == 2 * (2 * 32 * 32 + 2 * 32 * 16 + 3 * 32 * 64 + 64) + 32 + 2 * 100 * 32
    assert counts.ffn_scopes(tc) == ("ffn",) and counts.core_flops_per_token(tc, 64)["window_core"] == 0


# -- the readers -------------------------------------------------------------------------


def reader(name):
    return load_module(os.path.join(BENCH, "layer_metrics", name + ".py"), "m_" + name)


def a_run(config=CONFIG, peaks=PEAKS):
    traffic = {"batch": 2, "seq": 8192, "steps_per_unit": 5}
    return measure.Run({}, config, traffic, peaks, [{"group": 0, "trace": {"xplane": "no such file"}}])


def test_the_innermost_name_on_a_path_through_attn():
    banded = "jit(tft_fused)/transpose(jvp())/while/body/closed_call/attn/attn/checkpoint/rematted_computation/window/window_core/shard_map/flash_fwd/pallas_call"
    assert subscopes.innermost(banded, "attn", window_scopes.KINDS) == "window"
    assert subscopes.innermost(banded, "attn", window_scopes.CORES) == "window_core"
    proj = "jit(tft_fused)/jvp()/while/body/closed_call/attn/attn/checkpoint/global/dot_general"
    assert subscopes.innermost(proj, "attn", window_scopes.KINDS) == "global"
    assert subscopes.innermost(proj, "attn", window_scopes.CORES) == "attn"  # of attn, and of neither core
    norm = "jit(tft_fused)/jvp()/while/body/closed_call/attn/mul"
    assert subscopes.innermost(norm, "attn", window_scopes.KINDS) == "attn"  # the layer's first norm: neither kind's
    held = "jit(tft_fused)/jvp()/while/body/closed_call/moe/checkpoint/cond/branch_1_fun/experts/jit(gmm)/pallas_call"
    assert subscopes.innermost(held, "attn", window_scopes.KINDS) is None


def test_the_readers_on_rows_written_by_hand(monkeypatch):
    rows = {
        ("attn", window_scopes.KINDS): {"window": 0.150, "global": 0.120, "attn": 0.004},
        ("attn", window_scopes.CORES): {"window_core": 0.040, "global_core": 0.080, "attn": 0.154},
        ("moe", hybrid_scopes.MOE_PARTS): {"router": 0.004, "dispatch": 0.010, "experts": 0.016, "combine": 0.012, "shared": 0.008, "moe": 0.003},
    }
    monkeypatch.setattr(subscopes, "seconds", lambda run, parent, names, rows_of=None: rows[(parent, tuple(names))])
    run = a_run()
    got = {name: reader(name).compute(run) for name in READERS}
    assert (got["attn_window_device_s"], got["attn_global_device_s"]) == (0.150, 0.120)  # + 0.004 of neither = attn_device_s
    assert (got["window_core_device_s"], got["global_core_device_s"]) == (0.040, 0.080)
    assert got["moe_held_device_s"] == pytest.approx(0.004 + 0.010 + 0.016 + 0.012 + 0.003)
    # the least time of each core (the test above) over its seconds; a share, never clamped
    assert got["window_core_roofline"] == pytest.approx(100 * 12.166e-3 / 0.040, rel=1e-3)
    assert got["global_core_roofline"] == pytest.approx(100 * 50.238e-3 / 0.080, rel=1e-3)
    assert all(reader(name).compute(a_run(peaks=None)) is None for name in READERS if name.endswith("_roofline"))


def test_nothing_to_read_is_none_and_never_an_error(monkeypatch):
    # a trace that cannot be opened: every reader gives None
    assert all(reader(name).compute(a_run()) is None for name in READERS)
    # a program without the scopes (the parent commit, another architecture): attn without kinds, moe without `shared`
    rows = {"attn": {"attn": 0.5}, "moe": {"router": 0.01, "dispatch": 0.02, "experts": 0.1, "combine": 0.02, "moe": 0.001}}
    monkeypatch.setattr(subscopes, "seconds", lambda run, parent, names, rows_of=None: rows[parent])
    assert all(reader(name).compute(a_run()) is None for name in READERS)
    # a configuration whose count has no cores (opcount.py itself), or other cores (kimi_linear.py)
    monkeypatch.setattr(subscopes, "seconds", lambda run, parent, names, rows_of=None: {"window_core": 0.1, "global_core": 0.1})
    for other in ("olmoe-1g", "kimi-linear-1g"):
        config = load_json(os.path.join(BENCH, "configs", other + ".json"))
        assert reader("window_core_roofline").compute(a_run(config=config)) is None


def test_the_new_entries_are_appended_and_list_the_new_cell():
    bench = load_json(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json"))
    listed = {m["name"]: m for m in bench["per_layer"]}
    for name in READERS:
        assert listed[name]["workloads"] == [CELL] and listed[name]["layer"] == "device compute"
    assert [m["name"] for m in bench["per_layer"]][-7:] == list(READERS)
    assert CELL in [w["name"] for w in bench["workloads"]] and "laguna-xs2-1g" in [c["name"] for c in bench["configs"]]
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("laguna-xs2-1g", "fused-b2-s8192", 1)
