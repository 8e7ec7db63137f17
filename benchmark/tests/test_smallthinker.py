"""What the ``smallthinker-*`` configurations bring: the plain reference
(``reference/smallthinker_decoder.py``) against the program at a tiny size, the
count (``opcounts/smallthinker.py``) against numbers worked by hand at the
cell's size, and the nine readers over ``smallthinker_scopes.py`` on op paths
and rows written by hand, and where there is nothing to read.
``tests/test_early_router_nope.py`` (tier-1) holds the program to the reference
at each kind of layer, every slip of the mathematics, the share and more."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import measure
import moe_scopes
import opcount
import smallthinker_scopes
import subscopes
import window_scopes
from common import load_json, load_module
from reference import smallthinker_decoder as ref
from torchft_tpu.models.transformer import TransformerConfig, init_params, loss_fn

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
CONFIG = load_json(os.path.join(BENCH, "configs", "smallthinker-21b-a3b-1g.json"))
TC = CONFIG["program"]["transformer_config"]
PEAKS = load_json(os.path.join(BENCH, "peaks.json"))["TPU v5 lite"]
CELL = "smallthinker-21b-a3b-1g.fused-s16384"
READERS = (
    "attn_nope_global_device_s", "attn_window4k_device_s", "nope_global_core_device_s", "nope_global_core_roofline",
    "window4k_core_device_s", "window4k_core_roofline", "moe_early_router_device_s", "moe_reglu_experts_device_s",
    "moe_reglu_experts_roofline",
)
S, TOKENS = 16384, 2 * 16384

# -- the reference -------------------------------------------------------------------

SIZES = dict(
    vocab_size=97, d_model=32, n_layers=4, n_heads=6, n_kv_heads=2, head_dim=16, d_ff=16, moe_d_ff=16, norm_eps=1e-6,
    nope_layers=[1], window=8, window_layers=[2, 3, 4], rope_pairing="half", rope_theta=1500000,
    n_experts=16, n_experts_held=4, expert_share_index=1, top_k=4, router_gate="softmax", router_renormalize=True,
    router_input="layer", expert_activation="relu",
)
# float32 on both sides, sums in another order: measured 4e-6 of a gradient leaf's largest entry
RTOL = 5e-5


@pytest.fixture(scope="module")
def setup():
    cfg = TransformerConfig(dtype=jnp.float32, remat=True, **SIZES)
    params = init_params(jax.random.PRNGKey(3), cfg)
    for group in (params["lead"], params["periods"]):  # norm weights off 1, or one that is not applied would pass
        for layers in group.values():
            layers["ln1"], layers["ln2"], layers["router"] = layers["ln1"] * 1.3, layers["ln2"] * 0.8, layers["router"] * 3.0
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
    assert len(layers) == 4 and all(w["router"].shape == (32, 16) and w["w_gate"].shape == (4, 32, 16) for w in layers)
    np.testing.assert_array_equal(layers[0]["wq"], params["lead"]["nope.experts"]["wq"][0])
    np.testing.assert_array_equal(layers[3]["wq"], params["periods"]["window.experts"]["wq"][2, 0])


def test_the_layer_is_the_equations_written_out():
    """One token... three positions, one key/value head under two query heads of
    four lanes, four experts of which two are chosen and the middle two held, by
    hand in numpy: the gate from ``N1(x)``, no rotation in a ``nope`` layer,
    ReLU on the gate's lane, the weights renormalised over the chosen two."""
    sizes = dict(
        n_heads=2, n_kv_heads=1, head_dim=4, rope_theta=100.0, n_experts=4, n_experts_held=2, expert_share_index=0, top_k=2,
        router_renormalize=True, router_input="layer", expert_activation="relu", norm_eps=1e-6, window=2,
    )
    rng = np.random.default_rng(1)
    d, f = 4, 3
    w = {
        "ln1": rng.uniform(0.5, 1.5, d), "ln2": rng.uniform(0.5, 1.5, d), "wq": rng.normal(size=(d, 8)), "wk": rng.normal(size=(d, 4)),
        "wv": rng.normal(size=(d, 4)), "wo": rng.normal(size=(8, d)), "router": 2 * rng.normal(size=(d, 4)),
        "w_gate": rng.normal(size=(2, d, f)), "w_in": rng.normal(size=(2, d, f)), "w_out": rng.normal(size=(2, f, d)),
    }
    x = rng.normal(size=(1, 3, d))
    got = np.asarray(ref.layer({k: jnp.asarray(v, jnp.float32) for k, v in w.items()}, jnp.asarray(x, jnp.float32), sizes, "nope"))[0]

    norm = lambda a, g: a / np.sqrt((a * a).mean(-1, keepdims=True) + 1e-6) * g
    h1 = norm(x[0], w["ln1"])
    z = h1 @ w["router"]
    p = np.exp(z - z.max(-1, keepdims=True)); p /= p.sum(-1, keepdims=True)
    chosen = np.argsort(-p, axis=-1)[:, :2]
    q, k, v = (h1 @ w["wq"]).reshape(3, 2, 4), h1 @ w["wk"], h1 @ w["wv"]
    att = np.zeros((3, 2, 4))
    for i in range(3):
        for a in range(2):
            s = np.array([q[i, a] @ k[j] / 2.0 for j in range(i + 1)])  # every j <= i, nothing rotated
            pr = np.exp(s - s.max()); pr /= pr.sum()
            att[i, a] = sum(pj * v[j] for pj, j in zip(pr, range(i + 1)))
    x1 = x[0] + att.reshape(3, 8) @ w["wo"]
    h2 = norm(x1, w["ln2"])
    want = x1.copy()
    for t in range(3):
        total = p[t, chosen[t]].sum()
        for e in chosen[t]:
            if e < 2:  # held: experts 0 and 1
                y = (np.maximum(h2[t] @ w["w_gate"][e], 0.0) * (h2[t] @ w["w_in"][e])) @ w["w_out"][e]
                want[t] += p[t, e] / total * y
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


# -- the count, by hand at the cell's size ---------------------------------------------


def test_hand_worked_parameters():
    counts = opcount.for_config(CONFIG)
    assert counts.__name__ == "bench_opcount_smallthinker"
    d = 2560
    attn = 2 * d * 28 * 128 + 2 * d * 4 * 128       # q and o over 28 heads, k and v over 4
    assert attn == 20_971_520 and counts.attn_params(TC, 28) == attn
    assert counts.layer_heads(TC) == [("global", 28), ("window", 28), ("window", 28), ("window", 28)]
    expert = 3 * d * 768
    layer = attn + d * 64 + 8 * expert + 2 * d      # router over all 64 (no bias), 8 held, no shared expert, two norms
    assert expert == 5_898_240 and layer == 68_326_400
    total = 4 * layer + 2 * 18992 * d + d
    assert counts.n_params(TC) == total == 370_547_200
    assert 16 * total / 1e9 == pytest.approx(5.93, abs=5e-3)  # f32 parameters, two moments, f32 gradients
    # the whole model by the same arithmetic: the 21 B of its name, 3 B of them active a token
    whole = 52 * (attn + d * 64 + 64 * expert + 2 * d) + 2 * 151936 * d + d
    active = 52 * (attn + d * 64 + 6 * expert + 2 * d) + 2 * 151936 * d + d
    assert whole / 1e9 == pytest.approx(21.5, abs=5e-2) and active / 1e9 == pytest.approx(3.72, abs=5e-2)
    # a quarter share (16 experts, 37 984 rows) would be 656 M = 10.5 GB: no room for 32 768 tokens of activations
    quarter = 4 * (attn + d * 64 + 16 * expert + 2 * d) + 2 * 37984 * d + d
    assert quarter / 1e6 == pytest.approx(656.5, abs=0.1) and 16 * quarter / 1e9 == pytest.approx(10.5, abs=5e-2)


def test_hand_worked_operations_at_s16384():
    counts = opcount.for_config(CONFIG)
    flops = counts.flops_per_token_by_scope(TC, S)
    band = (4096 * 4097 / 2 + (S - 4096) * 4096) / S   # the first 4096 positions see fewer than 4096 keys
    assert band * S == 58_722_304 and band == 3584.125
    assert counts.keys_per_query(TC, S) == {"global": 8192.5, "window": band}
    window_core = 3 * 3 * 28 * 2 * band * 256            # three layers, forward and backward, QK and PV 128 wide each
    global_core = 1 * 3 * 28 * 2 * 8192.5 * 256
    assert counts.core_flops_per_token(TC, S) == {"window_core": window_core, "global_core": global_core}
    assert (window_core, global_core) == (462_438_144.0, 352_343_040.0)
    assert flops["attn"] == 6 * 4 * 20_971_520 + window_core + global_core
    # a token passes the router (64 wide) and 6 x 8 / 64 = 0.75 of an expert here, in each of the four layers
    assert flops["moe"] == 6 * 4 * (2560 * 64 + 0.75 * 5_898_240) == 110_100_480.0
    assert flops["head_loss"] == 6 * 2560 * 18992
    assert set(flops) == {"attn", "moe", "head_loss"} and counts.ffn_scopes(TC) == ("moe",)
    total = sum(flops.values())
    assert total / 1e9 == pytest.approx(1.7199, abs=1e-4) and total * TOKENS / 1e12 == pytest.approx(56.36, abs=1e-2)
    assert total * TOKENS / PEAKS["bf16_flops_per_s"] == pytest.approx(0.2861, abs=1e-4)  # the step at the bf16 peak
    # the mechanism at work: without the band the three window cores would be 2.29 x what they are, 35 % more operations
    full = 3 * 3 * 28 * 2 * 8192.5 * 256
    assert full / window_core == pytest.approx(2.286, abs=1e-3) and (full - window_core) / total == pytest.approx(0.346, abs=5e-3)
    shares = {k: round(100 * v / total) for k, v in {**flops, "window": window_core, "global": global_core}.items()}
    assert shares == {"attn": 77, "moe": 6, "head_loss": 17, "window": 27, "global": 20}
    # the experts alone, as ``moe_reglu_experts_roofline`` takes them: the scope less the router's product
    experts = moe_scopes.experts_flops_per_step(TC, 2, S, counts)
    assert experts == 6 * 4 * 0.75 * 5_898_240 * TOKENS and experts / PEAKS["bf16_flops_per_s"] == pytest.approx(17.66e-3, rel=1e-3)
    # rows the held experts get a layer: 32 768 x 6 x 8 / 64, 3 072 an expert at balance
    assert TOKENS * 6 * 8 // 64 == 24_576 and 24_576 // 8 == 3_072


def test_which_bound_is_the_nearer_at_the_cells_shape():
    counts = opcount.for_config(CONFIG)
    flops, moved = counts.flops_per_token_by_scope(TC, S), counts.bytes_per_step_by_scope(TC, 2, S)
    for scope in ("attn", "moe", "head_loss"):  # operations everywhere: 59 x, 3.4 x and 91 x the bytes' time
        assert flops[scope] * TOKENS / PEAKS["bf16_flops_per_s"] > 3 * moved[scope] / PEAKS["hbm_bytes_per_s"]
    assert flops["moe"] * TOKENS / PEAKS["bf16_flops_per_s"] == pytest.approx(3.34 * moved["moe"] / PEAKS["hbm_bytes_per_s"], rel=1e-2)
    assert moved["optimizer"] == 26 * 370_547_200 and moved["optimizer"] / PEAKS["hbm_bytes_per_s"] == pytest.approx(11.76e-3, rel=1e-3)
    core_flops, core_bytes = counts.core_flops_per_token(TC, S), counts.core_bytes_per_step(TC, 2, S)
    assert core_bytes == {"window_core": 3 * TOKENS * 2 * 128 * 6 * (28 + 4), "global_core": TOKENS * 2 * 128 * 6 * (28 + 4)}
    # both cores: operations (76.9 ms against 5.9 of bytes; 58.6 against 2.0): 15.15 and 11.54 TFLOP a step
    assert core_flops["window_core"] * TOKENS / 1e12 == pytest.approx(15.153, abs=1e-3)
    assert core_flops["global_core"] * TOKENS / 1e12 == pytest.approx(11.546, abs=1e-3)
    assert core_flops["window_core"] * TOKENS / PEAKS["bf16_flops_per_s"] == pytest.approx(76.92e-3, rel=1e-3)
    assert core_bytes["window_core"] / PEAKS["hbm_bytes_per_s"] == pytest.approx(5.90e-3, rel=1e-3)
    assert core_flops["global_core"] * TOKENS / PEAKS["bf16_flops_per_s"] == pytest.approx(58.61e-3, rel=1e-3)
    assert core_bytes["global_core"] / PEAKS["hbm_bytes_per_s"] == pytest.approx(1.967e-3, rel=1e-3)
    # the held experts: 17.7 ms of operations against 5.5 ms of bytes (their weights three times, the scope's edge)
    assert moe_scopes.experts_bytes_per_step(TC, 2, S, counts) / PEAKS["hbm_bytes_per_s"] == pytest.approx(5.48e-3, rel=1e-3)


# -- the readers -------------------------------------------------------------------------


def reader(name):
    return load_module(os.path.join(BENCH, "layer_metrics", name + ".py"), "m_" + name)


def a_run(config=CONFIG, peaks=PEAKS):
    traffic = {"batch": 2, "seq": S, "steps_per_unit": 5}
    return measure.Run({}, config, traffic, peaks, [{"group": 0, "trace": {"xplane": "no such file"}}])


def test_the_innermost_name_on_a_path_of_the_new_parts():
    early = "jit(tft_fused)/jvp(moe)/checkpoint/router/top_k"  # the lead layer's gate, ahead of its mixer
    assert subscopes.innermost(early, "moe", moe_scopes.SUBSCOPES) == "router"
    assert subscopes.innermost(early, "attn", window_scopes.KINDS) is None  # the moe scope's, not the mixer's
    again = "jit(tft_fused)/transpose(jvp())/while/body/closed_call/moe/moe/checkpoint/rematted_computation/router/dot_general"
    assert subscopes.innermost(again, "moe", moe_scopes.SUBSCOPES) == "router"
    relu = "jit(tft_fused)/jvp()/while/body/closed_call/moe/checkpoint/cond/branch_1_fun/experts/jit(relu)/max"
    assert subscopes.innermost(relu, "moe", moe_scopes.SUBSCOPES) == "experts"
    nope = "jit(tft_fused)/jvp(attn)/checkpoint/global/global_core/shard_map/flash_fwd/pallas_call"
    assert subscopes.innermost(nope, "attn", window_scopes.KINDS) == "global"
    assert subscopes.innermost(nope, "attn", window_scopes.CORES) == "global_core"
    banded = "jit(tft_fused)/transpose(jvp())/while/body/closed_call/attn/attn/checkpoint/window/window_core/flash_bwd/pallas_call"
    assert subscopes.innermost(banded, "attn", window_scopes.CORES) == "window_core"


def test_the_readers_on_rows_written_by_hand(monkeypatch):
    rows = {
        ("attn", window_scopes.KINDS): {"window": 0.260, "global": 0.150, "attn": 0.006},
        ("attn", window_scopes.CORES): {"window_core": 0.160, "global_core": 0.110, "attn": 0.146},
        ("moe", moe_scopes.SUBSCOPES): {"router": 0.006, "dispatch": 0.012, "experts": 0.030, "combine": 0.014, "moe": 0.004},
    }
    seconds = lambda run, parent, names, rows_of=None: rows[(parent, tuple(names))]  # noqa: E731
    monkeypatch.setattr(subscopes, "seconds", seconds)
    run = a_run()
    got = {name: reader(name).compute(run) for name in READERS}
    assert (got["attn_window4k_device_s"], got["attn_nope_global_device_s"]) == (0.260, 0.150)  # + 0.006 of neither = attn_device_s
    assert (got["window4k_core_device_s"], got["nope_global_core_device_s"]) == (0.160, 0.110)
    assert (got["moe_early_router_device_s"], got["moe_reglu_experts_device_s"]) == (0.006, 0.030)
    # the least time of each (the test above) over its seconds; a share, never clamped
    assert got["window4k_core_roofline"] == pytest.approx(100 * 76.92e-3 / 0.160, rel=1e-3)
    assert got["nope_global_core_roofline"] == pytest.approx(100 * 58.61e-3 / 0.110, rel=1e-3)
    assert got["moe_reglu_experts_roofline"] == pytest.approx(100 * 17.66e-3 / 0.030, rel=1e-3)
    assert all(reader(name).compute(a_run(peaks=None)) is None for name in READERS if name.endswith("_roofline"))
    # every reader has a row of the one table, and nothing else is in it
    assert set(smallthinker_scopes.SPLITS) | set(smallthinker_scopes.ROOFLINES) == set(READERS)


def test_nothing_to_read_is_none_and_never_an_error(monkeypatch):
    # a trace that cannot be opened: every reader gives None
    assert all(reader(name).compute(a_run()) is None for name in READERS)
    # a program without the scopes: attn without kinds, moe without parts
    rows = {"attn": {"attn": 0.5}, "moe": {"moe": 0.1}}
    monkeypatch.setattr(subscopes, "seconds", lambda run, parent, names, rows_of=None: rows[parent])
    assert all(reader(name).compute(a_run()) is None for name in READERS)
    # a configuration whose count has no cores (opcount.py itself), or other cores (kimi_linear.py)
    monkeypatch.setattr(subscopes, "seconds", lambda run, parent, names, rows_of=None: {"window_core": 0.1, "global_core": 0.1})
    for other in ("olmoe-1g", "kimi-linear-1g"):
        config = load_json(os.path.join(BENCH, "configs", other + ".json"))
        assert reader("window4k_core_roofline").compute(a_run(config=config)) is None


def test_the_new_entries_list_the_new_cell():
    bench = load_json(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json"))
    listed = {m["name"]: m for m in bench["per_layer"]}
    for name in READERS:
        assert listed[name]["workloads"] == [CELL] and listed[name]["layer"] == "device compute"
        assert listed[name]["moves"] == "step_p50_s" and listed[name]["source"] == "device_trace"
    names = [m["name"] for m in bench["per_layer"]]
    first = names.index(READERS[0])
    assert names[first : first + len(READERS)] == list(READERS)  # appended together, in this order
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("smallthinker-21b-a3b-1g", "fused-b2-s16384", 1)
    entry = next(c for c in bench["configs"] if c["name"] == "smallthinker-21b-a3b-1g")
    assert sorted(entry["reduced"]) == sorted(CONFIG["reduced"]) and entry["source"] == CONFIG["source"]
