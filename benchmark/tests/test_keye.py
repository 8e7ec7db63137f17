"""What the ``keye-*`` configurations bring: the plain reference
(``reference/keye_sparse_decoder.py``) against the program at a tiny size, the
count (``opcounts/keye_sparse.py``) against numbers worked by hand at the cell's
size, the parameters placed, and the seven readers over ``keye_scopes.py`` on op
paths and rows written by hand, and where there is nothing to read.
``tests/test_sparse_attention.py`` (tier-1) holds the program to the reference
by sets and by values given the sets at every gradient leaf, every slip of the
mathematics, the gradient split, the share, and ``tests/test_sparse_kernels.py``
the kernels."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import keye_scopes
import measure
import opcount
import subscopes
from common import load_json, load_module
from reference import keye_sparse_decoder as ref
from torchft_tpu.models.transformer import TransformerConfig, init_params, loss_fn, selections

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
NAME = "keye-vl-2_0-30b-a3b-1g"
CONFIG = load_json(os.path.join(BENCH, "configs", NAME + ".json"))
TC = CONFIG["program"]["transformer_config"]
PEAKS = load_json(os.path.join(BENCH, "peaks.json"))["TPU v5 lite"]
CELL = NAME + ".fused-s16384"
READERS = (
    "attn_sparse_device_s", "indexer_device_s", "select_device_s", "sparse_core_device_s", "sparse_core_roofline",
    "indexer_loss_device_s", "indexer_loss_roofline",
)
S, TOKENS, L = 16384, 16384, TC["n_layers"]

# -- the reference -------------------------------------------------------------------

SIZES = dict(
    vocab_size=97, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2, head_dim=16, d_ff=16, moe_d_ff=16, norm_eps=1e-6,
    qk_norm=True, qk_norm_per_head=True, rope_pairing="half", rope_theta=10000000,
    n_experts=16, n_experts_held=4, expert_share_index=1, top_k=4, router_gate="softmax", router_renormalize=True,
    sparse_topk=8, indexer_heads=4, indexer_head_dim=8,
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


def test_the_sets_agree_and_given_them_loss_and_gradients(setup):
    cfg, params, tokens = setup
    with jax.default_matmul_precision("highest"):
        got, g_got = jax.jit(jax.value_and_grad(lambda p: loss_fn(p, tokens, cfg)))(params)
        sets = jax.jit(lambda p: selections(p, tokens, cfg))(params)  # [L, B, S, S]

        def theirs(p, given):
            each = [ref.sequence_loss(p, tokens[i], SIZES, None, None if given is None else given[:, i]) for i in range(2)]
            return jnp.mean(jnp.stack(each))

        own = jnp.stack([ref.hidden(params, tokens[i], SIZES)[2] for i in range(2)], axis=1)
        scores = jnp.stack([ref.hidden(params, tokens[i], SIZES)[3] for i in range(2)], axis=1)
        want, g_want = jax.jit(jax.value_and_grad(lambda p: theirs(p, sets)))(params)
    differ, outside = ref.sets_apart(scores, own, sets, 1e-5)
    assert int(outside) == 0 and int(differ) <= 2
    assert float(got) == pytest.approx(float(want), rel=2e-6)
    errs = jax.tree_util.tree_map(lambda a, b: float(jnp.max(jnp.abs(a - b)) / (jnp.max(jnp.abs(b)) + 1e-30)), g_got, g_want)
    assert max(jax.tree_util.tree_leaves(errs)) < 5e-5, errs


def test_per_sequence_loss_is_what_the_worker_compares(setup):
    cfg, params, tokens = setup
    with jax.default_matmul_precision("highest"):
        per = ref.per_sequence_loss(params, tokens, SIZES)
        mine = [float(loss_fn(params, tokens[i : i + 1], cfg)) for i in range(2)]
    np.testing.assert_allclose(per, mine, rtol=5e-6)
    assert float(ref.loss(params, tokens, SIZES)) == pytest.approx(float(jnp.mean(per)), rel=1e-6)


def test_the_reference_is_independent_of_the_program():
    with open(os.path.join(BENCH, "reference", "keye_sparse_decoder.py")) as f:
        text = f.read()
    assert "import torchft_tpu" not in text and "from torchft_tpu" not in text
    assert set(ref.SLIPS) == {
        "no_selection", "topk_by_head", "indexer_sees_gradient", "target_not_detached", "kl_reversed", "indexer_unrotated",
        "not_renormalised",
    }


# -- the count, worked by hand at the cell's size --------------------------------------------------------


def test_hand_worked_parameters_and_where_they_are_placed():
    counts = opcount.for_config(CONFIG)
    assert counts.__name__ == "bench_opcount_keye_sparse"
    d = 2048
    attn = 2 * d * 32 * 128 + 2 * d * 4 * 128       # q and o over 32 heads, k and v over 4
    indexer = d * (16 * 64 + 64 + 16)               # 16 query heads of 64, ONE key head, a weight a head
    assert (attn, indexer) == (18_874_368, 2_260_992) == (counts.attn_params(TC), counts.indexer_params(TC))
    expert = 3 * d * 768
    layer = attn + indexer + d * 128 + 16 * expert + 2 * d + 2 * 128   # router over all 128, 16 held, the norms
    assert expert == 4_718_592 and layer == 96_899_328
    tables = 2 * 18992 * d
    assert counts.n_params(TC) == L * layer + tables + d
    by_depth = {n: n * layer + tables + d for n in (6, 5, 4)}
    assert by_depth == {6: 659_189_248, 5: 562_289_920, 4: 465_390_592}
    assert [round(16 * by_depth[n] / 1e9, 2) for n in (6, 5, 4)] == [10.55, 9.0, 7.45]  # f32 parameters, two moments, f32 gradients
    whole = attn + indexer + d * 128 + 128 * expert + 2 * d + 2 * 128  # one layer WHOLE: no chip holds two
    assert whole / 1e6 == pytest.approx(625.4, abs=0.05) and 16 * whole / 1e9 == pytest.approx(10.0, abs=1e-2)
    # the leaves the program places are the count's, and the selector's three are among them
    cfg = TransformerConfig(**{**TC, "dtype": jnp.bfloat16})
    shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    assert sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(shapes)) == counts.n_params(TC)
    lay = shapes["layers"]
    assert (lay["idx_wq"].shape, lay["idx_wk"].shape, lay["idx_ww"].shape) == ((1, L, d, 1024), (1, L, d, 64), (1, L, d, 16))
    assert lay["w_gate"].shape == (1, L, 16, d, 768) and lay["router"].shape == (1, L, d, 128) and "out" in shapes


def test_hand_worked_operations_at_s16384():
    counts = opcount.for_config(CONFIG)
    flops = counts.flops_per_token_by_scope(TC, S)
    # selected pairs a query: rows 0..2047 take all their causal keys, the other 14 336 take 2048
    keys = (2048 * 2049 / 2 + 14336 * 2048) / 16384
    assert counts.keys_per_token(TC, S) == keys == 1920.0625 and counts.causal_keys_per_token(S) == 8192.5
    assert keys / 8192.5 == pytest.approx(0.2344, abs=1e-4)  # 4.3 times fewer live pairs than dense
    core = L * 3 * 32 * 2 * keys * 256                    # forward and backward, QK and PV 128 wide each, SELECTED pairs
    scores = 16 * (2 * 64 + 2) * 8192.5                   # every causal pair: 64 lanes a head, and the weighted sum
    term = L * (32 * keys * 256 + 2 * scores)             # the target forward only; the scores' backward is the term's
    parts = counts.core_flops_per_token(TC, S)
    assert parts == {"sparse_core": core, "indexer_loss": term} and counts.indexer_scores_flops_per_token(TC, S) == scores
    assert core / L / 1e6 == pytest.approx(94.375, abs=1e-3) and term / L / 1e6 == pytest.approx(49.810, abs=1e-3)
    assert flops["attn"] == 6 * L * (18_874_368 + 2_260_992) + core + term + L * scores
    assert flops["moe"] == 6 * L * (2048 * 128 + 1.0 * 4_718_592)   # 8 x 16 / 128 = 1 pass of an expert a token
    assert flops["head_loss"] == 6 * 2048 * 18992
    assert set(flops) == {"attn", "moe", "head_loss"} and counts.ffn_scopes(TC) == ("moe",)
    total = sum(flops.values())
    assert total == counts.flops_per_token(TC, S)
    if L == 6:
        assert total / 1e9 == pytest.approx(2.1409, abs=1e-4) and total * TOKENS / 1e12 == pytest.approx(35.08, abs=1e-2)
        assert total * TOKENS / PEAKS["bf16_flops_per_s"] == pytest.approx(0.1781, abs=1e-4)  # the step at the bf16 peak
    # ISSUE 71's forward count a token a layer: selector, selected core and term 68.8 M of 116.4 M = 59 %
    fwd = dict(core=core / L / 3, scores=16 * 128 * 8192.5, proj_i=2 * (2048 * 1024 + 2048 * 64), weights=2 * 16 * 8192.5 + 2 * 2048 * 16,
               target=32 * keys * 256, proj=2 * 18_874_368, experts=2 * 4_718_592, router=2 * 2048 * 128)
    new = sum(fwd[k] for k in ("core", "scores", "proj_i", "weights", "target"))
    assert new / 1e6 == pytest.approx(68.8, abs=0.1) and sum(fwd.values()) / 1e6 == pytest.approx(116.4, abs=0.1)
    assert round(100 * new / sum(fwd.values())) == 59
    # as EXECUTED by a dense walk of 512 x 512 tiles: 528 causal tiles of 1024, 8 448 keys a query
    assert 32 * 33 // 2 == 528 and 528 * 512 / 32 == 8448 and 32 * 8448 * 512 / 1e6 == pytest.approx(138.4, abs=0.1)
    assert 1024 * TOKENS * 8 * 16 // 128 // TOKENS == 1024  # rows an expert gets at balance: 16 384 x 8 / 128


def test_which_bound_is_the_nearer_at_the_cells_shape():
    counts = opcount.for_config(CONFIG)
    flops, moved = counts.flops_per_token_by_scope(TC, S), counts.bytes_per_step_by_scope(TC, 1, S)
    for scope in ("attn", "moe", "head_loss"):  # operations everywhere
        assert flops[scope] * TOKENS / PEAKS["bf16_flops_per_s"] > 2 * moved[scope] / PEAKS["hbm_bytes_per_s"]
    assert moved["optimizer"] == 26 * counts.n_params(TC)
    core_flops, core_bytes = counts.core_flops_per_token(TC, S), counts.core_bytes_per_step(TC, 1, S)
    assert core_bytes["sparse_core"] == L * TOKENS * 2 * 128 * 6 * (32 + 4)
    assert core_flops["sparse_core"] * TOKENS / PEAKS["bf16_flops_per_s"] == pytest.approx(L * 7.849e-3, rel=1e-3)
    assert core_bytes["sparse_core"] / PEAKS["hbm_bytes_per_s"] == pytest.approx(L * 1.106e-3, rel=1e-3)
    assert core_flops["indexer_loss"] * TOKENS / PEAKS["bf16_flops_per_s"] == pytest.approx(L * 4.143e-3, rel=1e-3)
    assert core_bytes["indexer_loss"] / PEAKS["hbm_bytes_per_s"] < 1e-3 * L


# -- the readers -------------------------------------------------------------------------


def reader(name):
    return load_module(os.path.join(BENCH, "layer_metrics", name + ".py"), "m_" + name)


def a_run(config=CONFIG, peaks=PEAKS):
    traffic = {"batch": 1, "seq": S, "steps_per_unit": 5}
    return measure.Run({}, config, traffic, peaks, [{"group": 0, "trace": {"xplane": "no such file"}}])


PARTS = ("indexer", "select", "sparse_core", "indexer_loss")


def test_the_innermost_name_on_a_path_of_the_new_parts():
    at = "jit(tft_fused)/jvp()/while/body/closed_call/attn/checkpoint/sparse/"
    fwd = at + "sparse_core/flash_fwd/pallas_call"
    assert subscopes.innermost(fwd, "attn", ("sparse",)) == "sparse" and subscopes.innermost(fwd, "attn", PARTS) == "sparse_core"
    scores = at + "while/body/indexer/indexer_scores/pallas_call"
    assert subscopes.innermost(scores, "attn", PARTS) == "indexer"
    again = at + "indexer_loss/while/body/indexer_scores/pallas_call"  # the scores again, inside the term: the term's
    assert subscopes.innermost(again, "attn", PARTS) == "indexer_loss"
    target = "jit(tft_fused)/transpose(jvp())/while/body/closed_call/attn/attn/checkpoint/sparse/indexer_loss/while/body/head_probs/pallas_call"
    assert subscopes.innermost(target, "attn", PARTS) == "indexer_loss"
    count = at + "while/body/select/reduce_sum"
    assert subscopes.innermost(count, "attn", PARTS) == "select"
    proj = at + "dot_general"
    assert subscopes.innermost(proj, "attn", PARTS) == "attn" and subscopes.innermost(proj, "attn", ("sparse",)) == "sparse"
    assert subscopes.innermost(proj, "moe", PARTS) is None


def test_the_readers_on_rows_written_by_hand(monkeypatch):
    rows = {
        ("attn", ("sparse",)): {"sparse": 0.900, "attn": 0.010},
        ("attn", PARTS): {"indexer": 0.060, "select": 0.150, "sparse_core": 0.330, "indexer_loss": 0.210, "attn": 0.160},
    }
    monkeypatch.setattr(subscopes, "seconds", lambda run, parent, names, rows_of=None: rows[(parent, tuple(names))])
    run = a_run()
    got = {name: reader(name).compute(run) for name in READERS}
    assert (got["attn_sparse_device_s"], got["indexer_device_s"], got["select_device_s"]) == (0.900, 0.060, 0.150)
    assert (got["sparse_core_device_s"], got["indexer_loss_device_s"]) == (0.330, 0.210)
    # the least time of each (the test above) over its seconds; a share, never clamped
    assert got["sparse_core_roofline"] == pytest.approx(100 * L * 7.849e-3 / 0.330, rel=1e-3)
    assert got["indexer_loss_roofline"] == pytest.approx(100 * L * 4.143e-3 / 0.210, rel=1e-3)
    assert all(reader(name).compute(a_run(peaks=None)) is None for name in READERS if name.endswith("_roofline"))
    assert set(keye_scopes.SPLITS) | set(keye_scopes.ROOFLINES) == set(READERS)


def test_nothing_to_read_is_none_and_never_an_error(monkeypatch):
    assert all(reader(name).compute(a_run()) is None for name in READERS)  # a trace that cannot be opened
    # a program without the scopes (the parent of the PR that brought them): attn without the kind
    monkeypatch.setattr(subscopes, "seconds", lambda run, parent, names, rows_of=None: {"attn": 0.5})
    assert all(reader(name).compute(a_run()) is None for name in READERS)
    # a configuration whose count has no such part (opcount.py itself, sdar_moe.py's staircase core)
    monkeypatch.setattr(subscopes, "seconds", lambda run, parent, names, rows_of=None: {"sparse_core": 0.1, "indexer_loss": 0.1})
    for other in ("olmoe-1g", "sdar-30b-a3b-1g"):
        config = load_json(os.path.join(BENCH, "configs", other + ".json"))
        assert reader("sparse_core_roofline").compute(a_run(config=config)) is None
        assert reader("indexer_loss_roofline").compute(a_run(config=config)) is None


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
    assert all(names.index(name) > names.index("nope64_core_roofline") for name in READERS)  # after what PR 69 appended
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (NAME, "fused-b1-s16384", 1)
    traffic = load_json(os.path.join(BENCH, "traffic", cell["traffic"] + ".json"))
    assert (traffic["loop"], traffic["batch"], traffic["seq"], traffic["events"]) == ("fused", 1, 16384, [])
    entry = next(c for c in bench["configs"] if c["name"] == NAME)
    assert entry["reduced"] == ["num_hidden_layers", "num_experts", "num_local_experts", "vocab_size"]
    assert sorted(CONFIG["reduced"]) == sorted(entry["reduced"]) and entry["source"] == CONFIG["source"]
    assert CONFIG["sa_config"] == {"indexer_head_dim": 64, "indexer_num_heads": 16, "indexer_num_kv_heads": 1, "kv_chunk_size": 512, "q_chunk_size": 512, "topk": 2048}
    assert CONFIG["rope_scaling"] == {"mrope_section": [16, 24, 24], "rope_type": "default", "type": "default"}
    assert (TC["sparse_topk"], TC["indexer_heads"], TC["indexer_head_dim"]) == (2048, 16, 64)
    assert {"text_only", "indexer", "selection", "indexer_loss", "optimizer", "init", "compute_dtype"} <= set(CONFIG["assumed"])
    assert CELL not in next(m for m in bench["end_to_end"] if m["name"] == "tokens_per_s")["workloads"]
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
