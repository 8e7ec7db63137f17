"""What the ``joyai-flash-*`` configurations bring: the plain reference
(``reference/joyai_flash_decoder.py``) against the program at a tiny size, the
count (``opcounts/joyai_flash.py``) against numbers worked by hand at the cell's
size, and the four readers (two over ``hybrid_scopes.py``, two over ``mla_mtp_scopes.py``) on op paths and rows
written by hand, and where there is nothing to read.
``tests/test_mla_rope_mtp.py`` (tier-1) holds the program to the reference at
each step of the architecture, the share, the module's gradients and more."""

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
from reference import joyai_flash_decoder as ref
from torchft_tpu.models.transformer import TransformerConfig, init_params, loss_fn

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
CONFIG = load_json(os.path.join(BENCH, "configs", "joyai-flash-1g.json"))
TC = CONFIG["program"]["transformer_config"]
PEAKS = load_json(os.path.join(BENCH, "peaks.json"))["TPU v5 lite"]
CELL = "joyai-flash-1g.fused-s8192"
READERS = ("mla_rope_core_device_s", "mla_rope_core_roofline", "mla_latent_device_s", "mtp_device_s")

# -- the reference -------------------------------------------------------------------

SIZES = dict(
    vocab_size=97, d_model=32, n_layers=3, mla_layers=[1, 2, 3], n_heads=2, d_ff=64, norm_eps=1e-6,
    q_lora_rank=12, kv_lora_rank=8, qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8, mla_rope_theta=32000000.0,
    n_dense_layers=1, moe_d_ff=16, n_experts=16, n_experts_held=4, expert_share_index=1, top_k=4, n_shared_experts=1,
    router_gate="sigmoid", router_renormalize=True, routed_scaling_factor=2.5, n_mtp_modules=1, mtp_loss_weight=0.1,
)
# float32 on both sides, sums in another order: measured 1.3e-6 of a gradient leaf's largest entry
RTOL = 5e-5


@pytest.fixture(scope="module")
def setup():
    cfg = TransformerConfig(dtype=jnp.float32, remat=True, **SIZES)
    params = init_params(jax.random.PRNGKey(3), cfg)
    for layers in (params["lead"]["mla.dense"], params["periods"]["mla.experts"], params["mtp"]["layer"]):
        # norm weights off 1, or one that is not applied would pass
        layers["ln1"], layers["q_a_norm"], layers["kv_norm"] = layers["ln1"] * 1.3, layers["q_a_norm"] * 0.8, layers["kv_norm"] * 1.2
        if "router" in layers:
            layers["router"] = layers["router"] * 3.0
            layers["router_bias"] = layers["router_bias"] + 0.05 * jnp.sin(jnp.arange(16.0))
    params["final_norm"], params["mtp"]["hnorm"] = params["final_norm"] * 0.7, params["mtp"]["hnorm"] * 1.1
    tokens = jnp.asarray(np.random.default_rng(0).integers(0, 97, (2, 40)), jnp.int32)
    return cfg, params, tokens


def test_loss_and_gradients_agree(setup):
    cfg, params, tokens = setup
    with jax.default_matmul_precision("highest"):
        got, g_got = jax.jit(jax.value_and_grad(loss_fn), static_argnums=2)(params, tokens, cfg)
    want, g_want = jax.jit(jax.value_and_grad(lambda p, t: ref.loss(p, t, SIZES)))(params, tokens)
    assert float(got) == pytest.approx(float(want), rel=2e-6)
    errs = jax.tree_util.tree_map(
        lambda a, b: float(jnp.max(jnp.abs(a - b)) / (jnp.max(jnp.abs(b)) + 1e-30)), g_got, g_want
    )
    assert max(jax.tree_util.tree_leaves(errs)) < RTOL, errs


def test_per_sequence_loss_is_what_the_worker_compares(setup):
    cfg, params, tokens = setup
    one = jax.jit(lambda p, t: loss_fn(p, t, cfg))
    with jax.default_matmul_precision("highest"):
        per_seq = jax.jit(lambda p, t: ref.per_sequence_loss(p, t, SIZES))(params, tokens)
        for i in range(2):
            assert float(one(params, tokens[i : i + 1])) == pytest.approx(float(per_seq[i]), rel=2e-6)
        main, mtp = ref.losses(params, tokens[:1], SIZES)
    assert float(per_seq[0]) == pytest.approx(float(main) + 0.1 * float(mtp), rel=1e-6)  # both terms, the second at its weight


def test_the_reference_finds_each_layers_parameters_in_the_programs_tree(setup):
    _, params, _ = setup
    layers = ref.layer_params(params, SIZES)
    assert ["router" in w for w in layers] == [False, True, True]
    np.testing.assert_array_equal(layers[0]["wq_b"], params["lead"]["mla.dense"]["wq_b"][0])
    np.testing.assert_array_equal(layers[2]["wq_b"], params["periods"]["mla.experts"]["wq_b"][1, 0])


def test_attention_is_the_equations_written_out():
    """One head, three positions, by hand: keys of 2 per-head lanes beside 2
    rotated lanes shared by the heads, values 2 wide; the query through a
    low-rank pair and its norm; lanes (0, 1) of the rotated part turn by the
    position at frequency 1."""
    sizes = dict(n_heads=1, kv_lora_rank=2, q_lora_rank=2, qk_nope_head_dim=2, qk_rope_head_dim=2, v_head_dim=2,
                 mla_rope_theta=100.0, norm_eps=0.0)
    h = jnp.array([[[0.5, -1.0, 0.3, 0.2], [1.5, 0.25, -0.4, 0.1], [-0.75, 2.0, 0.6, -0.3]]])
    w = {
        "wq_a": jnp.eye(4)[:, :2], "q_a_norm": jnp.array([2.0, 0.5]),
        "wq_b": jnp.array([[1.0, 0.0, 0.5, 0.0], [0.0, 1.0, 0.0, -1.0]]),           # c_q -> (nope 2 | rope 2)
        "w_kva": jnp.eye(4), "kv_norm": jnp.ones(2),                                   # latent = lanes 0, 1; k_r = lanes 2, 3
        "w_kvb": jnp.array([[1.0, 0.0, 0.0, 2.0], [0.0, 1.0, 1.0, 0.0]]),           # c -> (k_n 2 | v 2)
        "wo": jnp.eye(2, 4),
    }
    got = np.asarray(ref._attention(w, h, sizes))[0]
    x = np.asarray(h[0], np.float64)
    unit = lambda v: v / np.sqrt(np.mean(v * v))
    turn = lambda v, p: np.array([v[0] * np.cos(p) - v[1] * np.sin(p), v[0] * np.sin(p) + v[1] * np.cos(p)])
    q, k, v = [], [], []
    for p in range(3):
        c_q = unit(x[p, :2]) * np.array([2.0, 0.5])
        full = c_q @ np.asarray(w["wq_b"], np.float64)
        q.append(np.concatenate([full[:2], turn(full[2:], p)]))
        c = unit(x[p, :2])
        up = c @ np.asarray(w["w_kvb"], np.float64)
        k.append(np.concatenate([up[:2], turn(x[p, 2:], p)]))
        v.append(up[2:])
    want = np.zeros((3, 4))
    for p in range(3):
        s = np.array([q[p] @ k[j] / 2.0 for j in range(p + 1)])  # sqrt(nope + rope) = 2
        prob = np.exp(s - s.max()) / np.exp(s - s.max()).sum()
        want[p, :2] = sum(pj * v[j] for j, pj in enumerate(prob))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=1e-6)


# -- the count, by hand at the cell's size ---------------------------------------------


def test_hand_worked_parameters():
    counts = opcount.for_config(CONFIG)
    assert counts.__name__ == "bench_opcount_joyai_flash"
    d = 2048
    attn = d * 1536 + 1536 * 32 * 192 + d * 576 + 512 * 32 * 256 + 32 * 128 * d   # W_qa, W_qb, W_kva, W_kvb, W_o
    assert attn == 26_345_472 and counts.attn_params(TC) == (attn, attn + 1536 + 512)
    expert = 3 * d * 768
    sparse = d * 256 + 256 + expert + 8 * expert        # router and its selection bias, the shared expert, 8 held
    assert expert == 4_718_592 and sparse == 42_991_872
    layer0 = attn + 2048 + 3 * d * 7168 + 2 * d
    sparse_layer = attn + 2048 + sparse + 2 * d
    assert (layer0, sparse_layer) == (70_391_808, 69_343_488)
    module = 3 * d + 2 * d * d + sparse_layer
    assert module == 77_738_240 == counts.module_params(TC)
    total = layer0 + 4 * sparse_layer + module + 2 * 16160 * d + d
    assert counts.n_params(TC) == total == 491_697_408
    assert 16 * total / 1e9 == pytest.approx(7.87, abs=5e-3)  # f32 parameters, two moments, f32 gradients
    # the whole model by the same arithmetic: the published 48B, 2.7 B of it active in the layers
    whole = 40 * (attn + 2048 + 2 * d) + 3 * d * 7168 + 39 * (257 * expert + d * 256 + 256) + 2 * 129280 * d + d
    assert whole / 1e9 == pytest.approx(48.93, abs=5e-2)
    active = 40 * attn + 3 * d * 7168 + 39 * (9 * expert + d * 256)
    assert active / 1e9 == pytest.approx(2.77, abs=5e-2)
    # as one of 16 chips (what ISSUE 46 asked for first: 17.73 GB of fused step, which no chip holds) and the module not held
    assert counts.n_params({**TC, "n_experts_held": 16}) == total + 5 * 8 * expert == 680_441_088
    assert counts.n_params({**TC, "n_mtp_modules": 0}) == total - module


def test_hand_worked_operations_at_s8192():
    counts = opcount.for_config(CONFIG)
    flops = counts.flops_per_token_by_scope(TC, 8192)
    core = 6 * 3 * 32 * 2 * 4096.5 * (192 + 128)       # six calls (five layers and the module's), forward and backward
    assert counts.core_flops_per_token(TC, 8192) == {"mla_core": core}
    assert flops["attn"] == 6 * 6 * 26_345_472 + core
    assert flops["ffn"] == 6 * 3 * 2048 * 7168
    # a token passes the router, the shared expert and 8 x 8 / 256 = 0.25 of its 8 routed experts here, in five sparse layers
    assert flops["moe"] == 6 * 5 * (2048 * 256 + 4_718_592 + 0.25 * 4_718_592)
    assert flops["head_loss"] == 2 * 6 * 2048 * 16160  # the head twice over the rows held
    assert flops["embed"] == 6 * 2 * 2048 * 2048        # eh_proj
    assert set(flops) == {"attn", "ffn", "moe", "head_loss", "embed"} and counts.ffn_scopes(TC) == ("ffn", "moe")
    total = sum(flops.values())
    assert total / 1e9 == pytest.approx(3.363, abs=1e-3) and total * 2 * 8192 / 1e12 == pytest.approx(55.10, abs=1e-2)
    shares = {k: round(100 * v / total) for k, v in {**flops, "core": core}.items()}
    assert shares == {"attn": 73, "ffn": 8, "moe": 6, "head_loss": 12, "embed": 1, "core": 45}
    # the module as a whole: its layer's attention, shared and held experts and router, eh_proj, its head pass
    module = 6 * 26_345_472 + core / 6 + 6 * (2048 * 256 + 1.25 * 4_718_592) + flops["embed"] + flops["head_loss"] / 2
    assert module / total == pytest.approx(0.21, abs=5e-3)
    # with a weight of 0 the program does not run the module, and the count leaves it out
    off = counts.flops_per_token_by_scope({**TC, "mtp_loss_weight": 0.0}, 8192)
    assert sum(off.values()) == pytest.approx(total - module, rel=1e-12) and "embed" not in off


def test_which_bound_is_the_nearer_at_the_cells_shape():
    counts = opcount.for_config(CONFIG)
    flops, moved = counts.flops_per_token_by_scope(TC, 8192), counts.bytes_per_step_by_scope(TC, 2, 8192)
    tokens = 2 * 8192
    for scope in ("attn", "ffn", "head_loss"):  # operations, by 5 x and more
        assert flops[scope] * tokens / PEAKS["bf16_flops_per_s"] > 5 * moved[scope] / PEAKS["hbm_bytes_per_s"]
    # the sparse layers hold 8 experts a token passes a quarter of one of: operations by 4.4 x only
    assert flops["moe"] * tokens / PEAKS["bf16_flops_per_s"] == pytest.approx(4.4 * moved["moe"] / PEAKS["hbm_bytes_per_s"], rel=2e-2)
    assert moved["optimizer"] == 26 * 491_697_408
    core_flops, core_bytes = counts.core_flops_per_token(TC, 8192), counts.core_bytes_per_step(TC, 2, 8192)
    assert core_bytes == {"mla_core": 6 * tokens * 32 * 2 * (6 * 192 + 6 * 128)}
    # the core: operations (125.6 ms a step against 14.7 of bytes)
    assert core_flops["mla_core"] * tokens / PEAKS["bf16_flops_per_s"] == pytest.approx(125.6e-3, rel=1e-3)
    assert core_bytes["mla_core"] / PEAKS["hbm_bytes_per_s"] == pytest.approx(14.75e-3, rel=1e-3)


def test_a_model_without_experts_a_low_rank_query_or_a_module_is_counted_too():
    tc = dict(d_model=32, n_layers=2, mla_layers=[1, 2], n_heads=2, kv_lora_rank=8, qk_nope_head_dim=8, qk_rope_head_dim=4,
              v_head_dim=8, d_ff=64, vocab_size=100)
    counts = opcount.for_config(CONFIG)
    attn = 32 * 2 * 12 + 32 * 12 + 8 * 2 * 16 + 2 * 8 * 32
    assert counts.n_params(tc) == 2 * (attn + 8 + 3 * 32 * 64 + 64) + 32 + 2 * 100 * 32
    assert counts.ffn_scopes(tc) == ("ffn",) and set(counts.flops_per_token_by_scope(tc, 64)) == {"attn", "ffn", "head_loss"}


# -- the readers -------------------------------------------------------------------------


def reader(name):
    return load_module(os.path.join(BENCH, "layer_metrics", name + ".py"), "m_" + name)


def a_run(config=CONFIG, peaks=PEAKS):
    traffic = {"batch": 2, "seq": 8192, "steps_per_unit": 5}
    return measure.Run({}, config, traffic, peaks, [{"group": 0, "trace": {"xplane": "no such file"}}])


def test_the_innermost_name_on_a_path():
    core = "jit(tft_fused)/transpose(jvp())/while/body/closed_call/attn/checkpoint/rematted_computation/mla/mla_core/shard_map/flash_bwd/pallas_call"
    assert subscopes.innermost(core, "attn", ("mla", "mla_core")) == "mla_core"
    assert subscopes.innermost(core, "attn", ("mtp",)) == "attn"  # a layer of the main stack: not the module's
    up = "jit(tft_fused)/jvp()/while/body/closed_call/attn/checkpoint/mla/q_lora/dot_general"
    assert subscopes.innermost(up, "attn", ("mla", "mla_core")) == "mla"
    norm = "jit(tft_fused)/jvp()/while/body/closed_call/attn/mul"
    assert subscopes.innermost(norm, "attn", ("mla", "mla_core")) == "attn"  # the layer's first norm: of attn alone
    module_core = "jit(tft_fused)/transpose(jvp(attn))/mtp/checkpoint/rematted_computation/mla/mla_core/shard_map/flash_fwd/pallas_call"
    assert subscopes.innermost(module_core, "attn", ("mtp",)) == "mtp"
    assert subscopes.innermost(module_core, "attn", ("mla_core",)) == "mla_core"  # the sixth call counts to the core too
    head = "jit(tft_fused)/jvp(head_loss)/mtp/while/body/dot_general"
    assert subscopes.innermost(head, "head_loss", ("mtp",)) == "mtp" and subscopes.innermost(head, "attn", ("mtp",)) is None
    proj = "jit(tft_fused)/transpose(jvp(embed))/mtp/dot_general"
    assert subscopes.innermost(proj, "embed", ("mtp",)) == "mtp"
    lookup = "jit(tft_fused)/jvp(embed)/jit(_take)/gather"
    assert subscopes.innermost(lookup, "embed", ("mtp",)) == "embed"  # the main lookup


def test_the_readers_on_rows_written_by_hand(monkeypatch):
    rows = {
        ("attn", hybrid_scopes.CORES): {"mla_core": 0.360, "attn": 0.140},
        ("attn", ("mla", "mla_core")): {"mla": 0.130, "mla_core": 0.360, "attn": 0.010},
        ("embed", ("mtp",)): {"mtp": 0.003, "embed": 0.002},
        ("attn", ("mtp",)): {"mtp": 0.084, "attn": 0.416},
        ("ffn", ("mtp",)): {"ffn": 0.030},
        ("moe", ("mtp",)): {"mtp": 0.012, "moe": 0.050},
        ("head_loss", ("mtp",)): {"mtp": 0.025, "head_loss": 0.025},
    }
    monkeypatch.setattr(subscopes, "seconds", lambda run, parent, names, rows_of=None: rows[(parent, tuple(names))])
    run = a_run()
    got = {name: reader(name).compute(run) for name in READERS}
    assert got["mla_rope_core_device_s"] == 0.360
    assert got["mla_latent_device_s"] == 0.130  # + 0.360 of the core + 0.010 of neither = attn_device_s
    assert got["mtp_device_s"] == pytest.approx(0.003 + 0.084 + 0.012 + 0.025)
    # the least time of the core (the test above) over its seconds; a share, never clamped
    assert got["mla_rope_core_roofline"] == pytest.approx(100 * 125.6e-3 / 0.360, rel=1e-3)
    assert reader("mla_rope_core_roofline").compute(a_run(peaks=None)) is None


def test_nothing_to_read_is_none_and_never_an_error(monkeypatch):
    # a trace that cannot be opened: every reader gives None
    assert all(reader(name).compute(a_run()) is None for name in READERS)
    # a program without the scopes (another architecture): attn without mla, no op of a module anywhere
    monkeypatch.setattr(subscopes, "seconds", lambda run, parent, names, rows_of=None: {parent: 0.5})
    assert all(reader(name).compute(a_run()) is None for name in READERS)
    # a configuration whose count has no cores (opcount.py itself)
    monkeypatch.setattr(subscopes, "seconds", lambda run, parent, names, rows_of=None: {"mla_core": 0.1})
    config = load_json(os.path.join(BENCH, "configs", "olmoe-1g.json"))
    assert reader("mla_rope_core_roofline").compute(a_run(config=config)) is None


def test_the_new_entries_list_the_new_cell():
    bench = load_json(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json"))
    listed = {m["name"]: m for m in bench["per_layer"]}
    for name in READERS:  # found by name, wherever later entries are appended
        assert listed[name]["workloads"] == [CELL] and listed[name]["layer"] == "device compute"
        assert listed[name]["moves"] == "step_p50_s" and listed[name]["source"] == "device_trace"
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("joyai-flash-1g", "fused-b2-s8192", 1)
    entry = next(c for c in bench["configs"] if c["name"] == "joyai-flash-1g")
    assert sorted(entry["reduced"]) == ["n_routed_experts", "num_hidden_layers", "vocab_size"] == sorted(CONFIG["reduced"])
    assert CELL not in next(m for m in bench["end_to_end"] if m["name"] == "tokens_per_s")["workloads"]
    assert set(CONFIG["assumed"]) >= {"mtp_loss_weight", "mtp_concatenation", "mtp_hidden", "optimizer", "init", "remat", "compute_dtype"}
    assert CONFIG["share"]["chips_per_layer"] * CONFIG["n_routed_experts"] == 256
