"""A gated short convolution as the sequence mixer, grouped-query attention
whose heads are normed one by one, and a quarter share of sigmoid-routed experts
through the one transformer — against the plain reference
(``benchmark/reference/lfm2_moe_decoder.py``, loaded by path: one copy).
``tests/test_gated_conv_train.py`` holds ``TrainStep``, the Manager, the names in
the lowered program and the other cells' programs: a file of its own, so that a
worker of the tier-1 run gets half of the compiles.

Tolerance of the float32 comparisons: both sides compute in float32 on the CPU
(matmuls at "highest"); what differs is the order of the sums — the program pads
and slices where the reference shifts, sorts rows by expert where the reference
masks. Measured here: the loss to 1e-7 of itself, gradient leaves to 2e-6 of
their largest entry. In bfloat16 at these tiny widths (32 wide, 40 positions)
the loss reads 1e-3 to 8e-3 off the float32 reference: the band is 3e-2, and it
holds the structure only loosely — what holds the structure is the float32
comparison.
"""

import dataclasses
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torchft_tpu.models import transformer as T
from torchft_tpu.models.transformer import TransformerConfig, init_params, layer_pattern, loss_fn
from torchft_tpu.parallel.mesh import MeshConfig, make_mesh

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RTOL = 2e-5
BF16_BAND = 3e-2


def _load_reference():
    path = os.path.join(ROOT, "benchmark", "reference", "lfm2_moe_decoder.py")
    spec = importlib.util.spec_from_file_location("lfm2_moe_decoder_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load_reference()

CONV = dict(vocab_size=64, d_model=32, d_ff=64, norm_eps=1e-5, conv_kernel=3)
ATTENTION = dict(
    n_heads=4, n_kv_heads=2, head_dim=8, qk_norm=True, qk_norm_per_head=True, rope_pairing="half", rope_theta=1000000.0,
)
EXPERTS = dict(
    moe_d_ff=16, n_experts=16, n_experts_held=4, top_k=4, router_gate="sigmoid", router_renormalize=True,
    router_norm_eps=1e-6, routed_scaling_factor=1.0, expert_share_index=1,
)
# the cell's stack at tiny widths: two dense conv layers, the attention layer with experts, three conv layers with
# experts — the program unrolls the first three and scans the last three
STACK = dict(CONV, **ATTENTION, **EXPERTS, n_layers=6, n_dense_layers=2, conv_layers=(1, 2, 4, 5, 6))
SIZES = {
    "conv.dense": dict(CONV, n_heads=2, head_dim=16, n_layers=2, conv_layers=(1, 2)),  # one kind: the plain scan
    "conv.taps4": dict(CONV, n_heads=2, head_dim=16, n_layers=2, conv_layers=(1, 2), conv_kernel=4),  # KDA's four taps
    "heads_normed": dict(CONV, **ATTENTION, n_layers=2),
    "stack": STACK,
}


def off_their_defaults(params):
    """Norm weights off 1 (the two head-wide ones too), a selection bias off 0,
    a router with loads that differ: or a norm without its weight, a bias that
    is not applied or one that reaches the weights would pass."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(params)
    out = []
    for i, (path, a) in enumerate(leaves):
        name = path[-1].key
        if name in ("ln1", "ln2", "q_norm", "k_norm", "final_norm"):
            a = a * (1 + 0.3 * jnp.sin(jnp.arange(a.size, dtype=jnp.float32).reshape(a.shape) + i))
        elif name == "router":
            a = a * 3.0
        elif name == "router_bias":
            a = 0.05 * jax.random.normal(jax.random.PRNGKey(i), a.shape)
        out.append(a)
    return jax.tree_util.tree_unflatten(treedef, out)


def make(size, seq=40, remat=False, **changes):
    sizes = dict(SIZES[size], **changes)
    cfg = TransformerConfig(dtype=jnp.float32, remat=remat, **sizes)
    params = off_their_defaults(init_params(jax.random.PRNGKey(3), cfg))
    tokens = jnp.asarray(np.random.default_rng(0).integers(0, 64, (2, seq)), jnp.int32)
    return cfg, params, tokens, sizes


def grad_errors(g_got, g_want):
    return jax.tree_util.tree_map(
        lambda a, b: float(jnp.max(jnp.abs(a - b)) / (jnp.max(jnp.abs(b)) + 1e-30)), g_got, g_want
    )


def system(cfg, params, tokens):
    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.value_and_grad(loss_fn), static_argnums=2)(params, tokens, cfg)


def conv_leaves(d=32, taps=3, key=0):
    keys = jax.random.split(jax.random.PRNGKey(key), 3)
    return {
        "conv_in": jax.random.normal(keys[0], (d, 3 * d)) * d**-0.5,
        "conv_w": jax.random.normal(keys[1], (taps, d)) * taps**-0.5,
        "conv_out": jax.random.normal(keys[2], (d, d)) * d**-0.5,
    }


# -- the program against the reference ------------------------------------------------


@pytest.mark.parametrize("size", list(SIZES))
def test_loss_and_every_gradient_leaf_agree_with_the_reference(size):
    cfg, params, tokens, sizes = make(size, remat=True)
    got, g_got = system(cfg, params, tokens)
    want, g_want = jax.jit(jax.value_and_grad(lambda p, t: ref.loss(p, t, sizes)))(params, tokens)
    assert float(got) == pytest.approx(float(want), rel=2e-6)
    errs = grad_errors(g_got, g_want)
    assert max(jax.tree_util.tree_leaves(errs)) < RTOL, errs
    for path, g in jax.tree_util.tree_leaves_with_path(g_got):  # the bias chooses and does not weigh
        if path[-1].key == "router_bias":
            assert float(jnp.max(jnp.abs(g))) == 0.0


def test_per_sequence_loss_is_what_the_worker_compares():
    cfg, params, tokens, sizes = make("stack")
    one = jax.jit(lambda p, t: loss_fn(p, t, cfg))
    with jax.default_matmul_precision("highest"):
        per = jax.jit(lambda p, t: ref.per_sequence_loss(p, t, sizes))(params, tokens)
        mine = [float(one(params, tokens[i : i + 1])) for i in range(2)]
    np.testing.assert_allclose(per, mine, rtol=2e-6)


def test_bfloat16_compute_stays_inside_the_stated_band():
    cfg, params, tokens, sizes = make("stack")
    got = float(jax.jit(loss_fn, static_argnums=2)(params, tokens, dataclasses.replace(cfg, dtype=jnp.bfloat16)))
    assert abs(got - float(ref.loss(params, tokens, sizes))) < BF16_BAND


# -- the mixer by itself -------------------------------------------------------------------------


@pytest.mark.parametrize("dtype, tol", [(jnp.float32, 1e-5), (jnp.bfloat16, 5e-2)])
@pytest.mark.parametrize("taps", [3, 4])
def test_the_conv_mixer_and_its_gradients_against_the_reference(taps, dtype, tol):
    """``_mix_conv`` against the reference's three shifted adds, output and the
    gradient of every leaf and of the input: float32 to the order of the sums,
    bfloat16 (operands and the elementwise part rounded to 8 bits) to a few
    percent of the largest entry."""
    lp = conv_leaves(taps=taps)
    h = jax.random.normal(jax.random.PRNGKey(5), (2, 24, 32))
    cast = lambda tree: jax.tree_util.tree_map(lambda a: a.astype(dtype), tree)
    probe = jax.random.normal(jax.random.PRNGKey(6), (2, 24, 32))

    def mine(lp, h):
        y = T._mix_conv(cast(lp), h.astype(dtype)).astype(jnp.float32)
        return jnp.sum(y * probe), y

    def theirs(lp, h):
        y = ref._gated_conv(lp, h, {"conv_kernel": taps})
        return jnp.sum(y * probe), y

    with jax.default_matmul_precision("highest"):
        (g_got, y_got), (g_want, y_want) = (
            jax.jit(lambda lp, h, f=f: (jax.grad(f, argnums=(0, 1), has_aux=True)(lp, h)))(lp, h) for f in (mine, theirs)
        )
    assert float(jnp.max(jnp.abs(y_got - y_want)) / jnp.max(jnp.abs(y_want))) < tol
    assert max(jax.tree_util.tree_leaves(grad_errors(g_got, g_want))) < tol


def test_the_convolution_is_causal_and_reaches_two_positions_back():
    """Changing id t leaves every output before t as it was, through conv
    layers alone; a position's output moves with the two before it and not
    with the third (one layer, 3 taps)."""
    cfg, params, tokens, _ = make("conv.dense")
    hidden = jax.jit(lambda t: T._hidden_states(params, t, cfg)[0])
    t = 17
    changed = tokens.at[:, t].set((tokens[:, t] + 1) % 64)
    moved = np.asarray(jnp.max(jnp.abs(hidden(tokens) - hidden(changed)), axis=(0, 2)))
    assert np.all(moved[:t] == 0.0) and moved[t] > 1e-3
    lp = conv_leaves()
    h = jax.random.normal(jax.random.PRNGKey(1), (1, 12, 32))
    y = T._mix_conv(lp, h)
    moved = np.asarray(jnp.max(jnp.abs(T._mix_conv(lp, h.at[:, 5].add(1.0)) - y), axis=(0, 2)))
    assert np.all(moved[:5] == 0.0) and np.all(moved[5:8] > 1e-4) and np.all(moved[8:] == 0.0)


def test_b_and_c_are_not_interchangeable():
    """``B`` gates ahead of the taps and ``C`` behind them: swapped along the
    features (the order the published split fixes) the output differs."""
    lp = conv_leaves()
    w = lp["conv_in"]
    swapped = dict(lp, conv_in=jnp.concatenate([w[:, 32:64], w[:, :32], w[:, 64:]], axis=1))
    h = jax.random.normal(jax.random.PRNGKey(2), (1, 16, 32))
    assert float(jnp.max(jnp.abs(T._mix_conv(lp, h) - T._mix_conv(swapped, h)))) > 1e-2


# -- the heads normed one by one ------------------------------------------------------------------


def _attention_leaves(sizes, key=0):
    keys = jax.random.split(jax.random.PRNGKey(key), 4)
    d, hd, h, kv = sizes["d_model"], sizes["head_dim"], sizes["n_heads"], sizes["n_kv_heads"]
    wave = lambda n, phase: 1 + 0.3 * jnp.sin(jnp.arange(n, dtype=jnp.float32) + phase)
    return {
        "wq": jax.random.normal(keys[0], (d, h * hd)) * d**-0.5, "wk": jax.random.normal(keys[1], (d, kv * hd)) * d**-0.5,
        "wv": jax.random.normal(keys[2], (d, kv * hd)) * d**-0.5, "wo": jax.random.normal(keys[3], (h * hd, d)) * d**-0.5,
        "q_norm": wave(hd, 0.0), "k_norm": wave(hd, 1.0),
    }


def test_the_per_head_norm_against_the_reference_and_against_the_whole_projections():
    """``_mix_full`` under ``qk_norm_per_head`` is the reference's attention —
    each head's lanes normed by themselves under one shared weight, before the
    rotation — and is NOT the norm over the whole projection (OLMoE's), even
    with that norm's weight the head-wide one repeated over the heads."""
    sizes = dict(CONV, **ATTENTION)
    cfg = TransformerConfig(dtype=jnp.float32, **sizes)
    lp = _attention_leaves(sizes)
    h = jax.random.normal(jax.random.PRNGKey(9), (2, 24, 32))
    with jax.default_matmul_precision("highest"):
        got = T._mix_full(cfg, None, False, lp, h)
        want = ref._attention(lp, h, sizes)
        whole_cfg = dataclasses.replace(cfg, qk_norm_per_head=False)
        whole = T._mix_full(whole_cfg, None, False, dict(lp, q_norm=jnp.tile(lp["q_norm"], 4), k_norm=jnp.tile(lp["k_norm"], 2)), h)
        no_norm = T._mix_full(dataclasses.replace(whole_cfg, qk_norm=False), None, False, lp, h)
    scale = float(jnp.max(jnp.abs(want)))
    assert float(jnp.max(jnp.abs(got - want))) < 1e-5 * scale
    assert float(jnp.max(jnp.abs(got - whole))) > 1e-2 * scale
    assert float(jnp.max(jnp.abs(got - no_norm))) > 1e-2 * scale
    # the leaves: one head wide under the per-head norm, the projection wide without it
    per_head = init_params(jax.random.PRNGKey(0), TransformerConfig(dtype=jnp.float32, n_layers=1, **sizes))["layers"]
    assert per_head["q_norm"].shape == per_head["k_norm"].shape == (1, 1, 8)
    olmoe = init_params(jax.random.PRNGKey(0), dataclasses.replace(cfg, n_layers=1, qk_norm_per_head=False))["layers"]
    assert olmoe["q_norm"].shape == (1, 1, 32) and olmoe["k_norm"].shape == (1, 1, 16)
    with pytest.raises(ValueError, match="qk_norm_per_head says where qk_norm's norm runs"):
        TransformerConfig(qk_norm_per_head=True)


# -- the gate and the share -------------------------------------------------------------------


def _expert_leaves(d=32, e=32, f=16, key=0):
    keys = jax.random.split(jax.random.PRNGKey(key), 5)
    return {
        "router": 3.0 * jax.random.normal(keys[0], (d, e)) * d**-0.5,
        "router_bias": 0.05 * jax.random.normal(keys[1], (e,)),
        "w_gate": jax.random.normal(keys[2], (e, d, f)) * d**-0.5,
        "w_in": jax.random.normal(keys[3], (e, d, f)) * d**-0.5,
        "w_out": jax.random.normal(keys[4], (e, f, d)) * f**-0.5,
    }


QUARTER = dict(STACK, n_experts=32, n_experts_held=8, top_k=4)  # the cell's share: 8 of 32, 4 a token


def test_the_four_shares_of_a_layer_add_up_to_the_uncut_layer():
    """Four chips, eight of thirty-two experts each (``expert_share_index``
    0..3): the four shares' layer outputs add up to the layer of the uncut
    32-expert reference, selection bias and all, and every token-expert row is
    computed on exactly one share."""
    whole = _expert_leaves()
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 48, 32))
    with jax.default_matmul_precision("highest"):
        want = ref._experts(whole, x, dict(QUARTER, n_experts_held=0, expert_share_index=0))
        parts, rows = [], []
        for share in range(4):
            cfg = TransformerConfig(dtype=jnp.float32, **dict(QUARTER, expert_share_index=share))
            lp = {k: (v[8 * share : 8 * share + 8] if k in ("w_gate", "w_in", "w_out") else v) for k, v in whole.items()}
            y, (_, counts, held, _) = jax.jit(lambda lp, x, cfg=cfg: T._ffn_moe(lp, x, cfg))(lp, x)
            np.testing.assert_allclose(y, ref._experts(lp, x, dict(QUARTER, expert_share_index=share)), atol=5e-5)
            parts.append(y)
            rows.append(int(held))
            assert int(jnp.sum(counts)) == 2 * 48 * 4  # the router counts over all 32, on every share
    np.testing.assert_allclose(sum(parts), want, atol=5e-5)
    assert sum(rows) == 2 * 48 * 4 and len(set(rows)) > 1


def test_the_bias_moves_the_choice_and_not_the_weights_and_gets_no_gradient():
    cfg = TransformerConfig(dtype=jnp.float32, **QUARTER)
    lp = _expert_leaves()
    tokens = jax.random.normal(jax.random.PRNGKey(4), (96, 32))
    w, idx, _ = T._route(lp, tokens, cfg)
    pushed = dict(lp, router_bias=lp["router_bias"].at[5].add(10.0))  # expert 5 wins a place at every token
    w2, idx2, _ = T._route(pushed, tokens, cfg)
    assert bool(jnp.all(jnp.any(idx2 == 5, axis=-1))) and not bool(jnp.all(jnp.any(idx == 5, axis=-1)))
    scores = jax.nn.sigmoid(tokens @ lp["router"])
    for weights, chosen in ((w, idx), (w2, idx2)):  # the weights are the chosen experts' own scores, whatever the bias
        own = jnp.take_along_axis(scores, chosen, axis=-1)
        np.testing.assert_allclose(weights, own / (jnp.sum(own, axis=-1, keepdims=True) + 1e-6), rtol=1e-6)
    np.testing.assert_allclose(jnp.sum(w, axis=-1), 1.0, rtol=1e-4)  # norm_topk_prob, routed_scaling_factor 1
    x = tokens.reshape(2, 48, 32)
    held = {k: (v[8:16] if k in ("w_gate", "w_in", "w_out") else v) for k, v in lp.items()}
    grads = jax.grad(lambda lp: jnp.sum(T._ffn_moe(lp, x, cfg)[0] ** 2))(held)
    assert float(jnp.max(jnp.abs(grads["router_bias"]))) == 0.0 and float(jnp.max(jnp.abs(grads["router"]))) > 0


def test_the_gates_epsilon_is_the_configurations():
    """Scores so small that the epsilon beside their sum shows: the weights are
    ``s / (Σ s + router_norm_eps)`` with the configuration's 1e-6, as the
    reference has them, and with the default 1e-20 they sum to one."""
    lp14 = dict(_expert_leaves(), router_bias=jnp.zeros((32,)))
    # one more input lane that is always 1: every logit sits near -14, sigmoid(-14) = 8e-7, beside the 1e-6
    lp14["router"] = jnp.concatenate([0.01 * lp14["router"], jnp.full((1, 32), -14.0)], axis=0)
    tokens14 = jnp.concatenate([jax.random.normal(jax.random.PRNGKey(4), (24, 32)), jnp.ones((24, 1))], axis=-1)
    mine = T._route(lp14, tokens14, TransformerConfig(dtype=jnp.float32, **QUARTER))[0]
    theirs = ref._route(lp14, tokens14, QUARTER)
    np.testing.assert_allclose(jnp.sort(mine, axis=-1), jnp.sort(theirs, axis=-1)[:, -4:], rtol=1e-5)
    assert 0.5 < float(jnp.max(jnp.sum(mine, axis=-1))) < 0.9  # four scores of ~8e-7 beside 1e-6
    default = T._route(lp14, tokens14, TransformerConfig(dtype=jnp.float32, **dict(QUARTER, router_norm_eps=1e-20)))[0]
    np.testing.assert_allclose(jnp.sum(default, axis=-1), 1.0, rtol=1e-5)
    assert TransformerConfig().router_norm_eps == 1e-20  # what every other configuration's gate divides by


# -- the pattern, what it says and what it refuses ---------------------------------------------


def test_layer_kinds_and_pattern_of_the_published_list_and_of_the_cut():
    with open(os.path.join(ROOT, "benchmark", "published", "lfm2-8b-a1b.json")) as f:
        published = json.load(f)["config"]
    types = published["layer_types"]
    assert len(types) == published["num_hidden_layers"] == 24 and types.count("conv") == 18
    of = lambda types: tuple(i for i, t in enumerate(types, 1) if t == "conv")
    whole = TransformerConfig(**dict(STACK, n_layers=24, n_dense_layers=published["num_dense_layers"], conv_layers=of(types)))
    kinds = whole.layer_kinds()
    assert [m for m, _ in kinds] == ["conv" if t == "conv" else "full" for t in types]
    assert [f for _, f in kinds] == ["dense"] * 2 + ["experts"] * 22
    # 3 conv to 1 attention from layer 3 on, but the list ends attn, conv, conv twice: the published stack unrolls 18
    # layers and scans that last period; its first 16 sparse layers are four repeats of (attn, conv, conv, conv)
    assert layer_pattern(whole) == (18, (("full", "experts"),) + (("conv", "experts"),) * 2)
    assert kinds[2:18] == ((("full", "experts"),) + (("conv", "experts"),) * 3) * 4
    with open(os.path.join(ROOT, "benchmark", "configs", "lfm2-8b-a1b-1g.json")) as f:
        cut = json.load(f)
    assert cut["layer_types"] == types[:6] and tuple(cut["program"]["transformer_config"]["conv_layers"]) == of(types[:6])
    cfg = TransformerConfig(**STACK)
    assert cfg.layer_kinds() == (("conv", "dense"),) * 2 + (("full", "experts"),) + (("conv", "experts"),) * 3
    assert layer_pattern(cfg) == (3, (("conv", "experts"),))  # three layers one by one, the last three scanned
    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    assert set(params["lead"]) == {"conv.dense", "full.experts"} and set(params["periods"]) == {"conv.experts"}
    assert params["lead"]["conv.dense"]["conv_in"].shape == (2, 32, 96) and params["periods"]["conv.experts"]["conv_w"].shape == (3, 1, 3, 32)
    assert "wq" not in params["lead"]["conv.dense"] and "conv_in" not in params["lead"]["full.experts"]
    with pytest.raises(ValueError, match="conv_layers .* name layers 1..6, each at most once"):
        TransformerConfig(**dict(STACK, conv_layers=(1, 2, 7)))


def test_the_events_say_the_conv_kind_and_the_core_the_attention_layer_took(monkeypatch):
    from torchft_tpu import telemetry

    monkeypatch.setattr(T, "_PATHS_SAID", set())
    cfg, params, tokens, _ = make("stack", seq=32)
    seen = {kind: len(telemetry.EVENTS.recent(kind)) for kind in ("attention_path", "layer_pattern")}
    for _ in range(2):
        jax.jit(lambda p: loss_fn(p, tokens, cfg, None))(params)
    (path,) = telemetry.EVENTS.recent("attention_path")[seen["attention_path"]:]  # the one attention layer, once
    assert (path["n_heads"], path["n_kv_heads"], path["head_dim"], path["rotary_dim"], path["impl"]) == (4, 2, 8, 8, "plain")
    (pattern,) = telemetry.EVENTS.recent("layer_pattern")[seen["layer_pattern"]:]
    assert (pattern["lead"], pattern["period"], pattern["repeats"]) == ("conv.dense,conv.dense,full.experts", "conv.experts", 3)
    assert (pattern["conv_kernel"], pattern["experts_held"], pattern["experts"]) == (3, 4, 16)


def test_heads_of_64_take_the_kernel_on_a_chip_at_the_cells_length():
    """The cell's attention layer (32 x 64 over 8 x 64, 8 192 positions): on a
    TPU ``auto`` takes the Pallas kernel at the tile measured fastest at 64
    lanes (PERF.md §6, PR 52), grouped heads read in place; on a CPU and
    inside a manual region the chunked scan keeps it."""
    from unittest import mock

    with open(os.path.join(ROOT, "benchmark", "configs", "lfm2-8b-a1b-1g.json")) as f:
        tc = json.load(f)["program"]["transformer_config"]
    assert tc["attention_impl"] == "auto" and tc["head_dim"] == 64
    cfg = TransformerConfig(**{**tc, "dtype": jnp.dtype(tc["dtype"])})
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        impl, why, blocks = T._attention_path(cfg, 8192, 2, None, grouped=True)
        assert (impl, blocks) == ("flash", (512, 512)) and why == "auto on a tpu: the fastest core measured at this (seq, head_dim)"
        assert T._attention_path(cfg, 8192, 2, None, sp_manual=True, grouped=True)[0] == "chunked"
    assert T._attention_path(cfg, 8192, 2, None, grouped=True)[::2] == ("chunked", None)


def test_a_conv_layer_refuses_a_sharded_sequence():
    cfg, params, tokens, _ = make("conv.dense", seq=32)
    sp = make_mesh(MeshConfig(sp=2), devices=jax.devices()[:2])
    with pytest.raises(ValueError, match="sp=2 with a conv layer: .* last 2 positions .* is missing"), jax.set_mesh(sp):
        jax.jit(lambda p, t: loss_fn(p, t, cfg, sp))(params, tokens)
