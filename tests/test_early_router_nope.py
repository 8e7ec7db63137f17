"""A gate computed from the layer's input ahead of the mixer, ReLU-gated experts
held under a share, and softmax layers without positions beside rotated window
layers — through the one transformer, against the plain reference
(``benchmark/reference/smallthinker_decoder.py``, loaded by path: one copy).

Tolerance of the float32 comparisons with the reference: both sides compute in
float32 on the CPU (matmuls at "highest"); what differs is the order of the
sums — a group's heads repeated against a grouped einsum, rows sorted by expert
and a grouped matmul against every held expert over every token. Measured
here: the loss to 3e-7 of it, gradient leaves to 4e-6 of their largest entry.
RTOL is far above that and far below what one slipped term moves (the slips'
test shows each).
"""

import dataclasses
import hashlib
import importlib.util
import json
import os
import re
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from torchft_tpu.models import transformer as T
from torchft_tpu.models.transformer import TransformerConfig, init_params, layer_pattern, loss_and_stats, loss_fn
from torchft_tpu.ops.layers import moe_dropless, moe_dropless_held, swiglu
from torchft_tpu.parallel.mesh import MeshConfig, make_mesh
from torchft_tpu.parallel.train_step import TrainStep

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RTOL = 5e-5


def _load_reference():
    path = os.path.join(ROOT, "benchmark", "reference", "smallthinker_decoder.py")
    spec = importlib.util.spec_from_file_location("smallthinker_decoder_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load_reference()

# the cell's layer at tiny widths: 6 query heads over 2 key/value heads (groups of 3), 16 experts of which 4 are held
# (share 1: experts 4..7) and 4 are chosen a token, the softmax over all 16 renormalised over the chosen
LAYER = dict(
    vocab_size=64, d_model=32, n_heads=6, n_kv_heads=2, head_dim=16, d_ff=16, moe_d_ff=16, norm_eps=1e-6,
    rope_pairing="half", rope_theta=1500000.0, n_experts=16, n_experts_held=4, expert_share_index=1, top_k=4,
    router_gate="softmax", router_renormalize=True, router_input="layer", expert_activation="relu",
)
SIZES = {
    # the cell's stack: one period, global without positions and three rotated window layers (a lead of one, a scan of three)
    "stack": dict(LAYER, n_layers=4, nope_layers=(1,), window=8, window_layers=(2, 3, 4)),
    # two periods: the scan's body unrolls a period of four
    "two_periods": dict(LAYER, n_layers=8, nope_layers=(1, 5), window=8, window_layers=(2, 3, 4, 6, 7, 8)),
    # a stack of ONE kind: layers under the stage's scan and ONE checkpoint a layer, the chain of `TrainStep.grads`
    "nope_only": dict(LAYER, n_layers=2, nope_layers=(1, 2)),
    # a rotated global layer beside one without positions, every expert held
    "full_and_nope": dict(LAYER, n_layers=2, nope_layers=(2,), n_experts_held=0, expert_share_index=0),
    # each new field by itself, the others as every model had them
    "gate_ahead_only": dict(LAYER, n_layers=2, expert_activation="silu"),
    "relu_only": dict(LAYER, n_layers=2, router_input="ffn"),
}


def off_their_defaults(params):
    """Norm weights off 1 and a router with loads that differ: or a norm
    without its weight, or a gate on the other norm's output, could pass."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(params)
    out = []
    for i, (path, a) in enumerate(leaves):
        name = path[-1].key
        if name in ("ln1", "ln2", "final_norm"):
            a = a * (1 + 0.3 * jnp.sin(jnp.arange(a.size, dtype=jnp.float32).reshape(a.shape) + i))
        elif name == "router":
            a = a * 3.0
        out.append(a)
    return jax.tree_util.tree_unflatten(treedef, out)


def make(size, seq=40, **over):
    sizes = dict(SIZES[size], **over)
    cfg = TransformerConfig(dtype=jnp.float32, remat=True, **sizes)
    params = off_their_defaults(init_params(jax.random.PRNGKey(3), cfg))
    tokens = jnp.asarray(np.random.default_rng(0).integers(0, 64, (2, seq)), jnp.int32)
    return cfg, params, tokens, sizes


def grad_errors(g_got, g_want):
    return jax.tree_util.tree_map(
        lambda a, b: float(jnp.max(jnp.abs(a - b)) / (jnp.max(jnp.abs(b)) + 1e-30)), g_got, g_want
    )


# jitted once for the file (``tests/test_gdn.py`` says why): a test that patches what TRACING reads builds its own
_loss_and_grads = jax.jit(jax.value_and_grad(loss_fn), static_argnums=2)


def system(cfg, params, tokens):
    with jax.default_matmul_precision("highest"):
        return _loss_and_grads(params, tokens, cfg)


def reference(params, tokens, sizes):
    return jax.jit(jax.value_and_grad(lambda p, t: ref.loss(p, t, sizes)))(params, tokens)


# -- the program against the reference ------------------------------------------------


@pytest.mark.parametrize("size", list(SIZES))
def test_loss_and_every_gradient_leaf_agree_with_the_reference(size):
    cfg, params, tokens, sizes = make(size)
    got, g_got = system(cfg, params, tokens)
    want, g_want = reference(params, tokens, sizes)
    assert float(got) == pytest.approx(float(want), rel=2e-6)
    errs = grad_errors(g_got, g_want)
    assert max(jax.tree_util.tree_leaves(errs)) < RTOL, errs
    assert all(float(jnp.max(jnp.abs(g))) > 0 for g in jax.tree_util.tree_leaves(g_got))  # the routers' among them


@pytest.mark.parametrize("impl", ["plain", "chunked", "flash"])
def test_the_stack_through_each_core(impl):
    """s128: the chunk divides it and the kernel's tile (clamped to 128) is the
    sequence; groups of three and the band of 8 through each of the cores."""
    cfg, params, tokens, sizes = make("stack", seq=128)
    got, g_got = system(dataclasses.replace(cfg, attention_impl=impl), params, tokens)
    want, g_want = reference(params, tokens, sizes)
    assert float(got) == pytest.approx(float(want), rel=2e-6)
    assert max(jax.tree_util.tree_leaves(grad_errors(g_got, g_want))) < RTOL


def test_without_remat_and_under_the_other_policy_the_numbers_are_the_same():
    cfg, params, tokens, sizes = make("stack")
    want, g_want = reference(params, tokens, sizes)
    for changes in (dict(remat=False), dict(remat_policy="dots")):
        got, g_got = system(dataclasses.replace(cfg, **changes), params, tokens)
        assert float(got) == pytest.approx(float(want), rel=2e-6)
        assert max(jax.tree_util.tree_leaves(grad_errors(g_got, g_want))) < RTOL


def test_per_sequence_loss_is_what_the_worker_compares():
    cfg, params, tokens, sizes = make("stack")
    with jax.default_matmul_precision("highest"):
        per = ref.per_sequence_loss(params, tokens, sizes)
        mine = [float(loss_fn(params, tokens[i : i + 1], cfg)) for i in range(2)]
    np.testing.assert_allclose(per, mine, rtol=2e-6)


def test_the_stack_is_a_leading_layer_without_positions_and_a_scan_over_window_layers():
    cfg, params, _, _ = make("stack")
    kinds = (("nope", "experts"),) + (("window", "experts"),) * 3
    assert cfg.layer_kinds() == kinds and layer_pattern(cfg) == (1, (("window", "experts"),))
    assert set(params["lead"]) == {"nope.experts"} and set(params["periods"]) == {"window.experts"}
    assert params["lead"]["nope.experts"]["wq"].shape == (1, 32, 6 * 16)
    assert params["periods"]["window.experts"]["wk"].shape == (3, 1, 32, 2 * 16)
    assert params["lead"]["nope.experts"]["router"].shape == (1, 32, 16)  # the router over all 16
    assert params["lead"]["nope.experts"]["w_gate"].shape == (1, 4, 32, 16)  # four held
    cfg8, params8, _, _ = make("two_periods")
    assert layer_pattern(cfg8) == (0, kinds) and params8["periods"]["window.experts"]["wq"].shape == (2, 3, 32, 96)


SLIPS = {
    # each of what makes the architecture different, slipped on the reference's side alone
    "router_reads_the_ffn_input": dict(router_input="ffn"),
    "global_layer_rotated": {},  # the reference's UNROTATED emptied: the same leaves, q and k of layer 1 rotated
    "silu_for_relu": dict(expert_activation="silu"),
    "band_halved": dict(window=4),
    "not_renormalised": dict(router_renormalize=False),
}


@pytest.mark.parametrize("slip", list(SLIPS))
def test_each_slip_fails_the_comparison(slip):
    """The reference with ONE thing slipped moves the loss by far more than
    the comparison allows (2e-6 of it), and some gradient leaf by more than RTOL."""
    cfg, params, tokens, sizes = make("stack")
    got, g_got = system(cfg, params, tokens)
    with mock.patch.object(ref, "UNROTATED", () if slip == "global_layer_rotated" else ref.UNROTATED):
        want, g_want = reference(params, tokens, dict(sizes, **SLIPS[slip]))
    assert abs(float(got) - float(want)) > 20 * 2e-6 * abs(float(got)), (slip, float(got), float(want))
    assert max(jax.tree_util.tree_leaves(grad_errors(g_got, g_want))) > 20 * RTOL


# -- the gate ahead of the mixer -----------------------------------------------------------------------


def one_layer(share=1, shares=4, seed=0, **over):
    """One ``nope`` layer of 16 experts, 16 / shares held: (cfg, its leaves, every expert's leaves, sizes)."""
    held = 16 // shares
    sizes = dict(LAYER, n_layers=1, nope_layers=(1,), n_experts_held=held, expert_share_index=share, **over)
    cfg = TransformerConfig(dtype=jnp.float32, **sizes)
    whole_cfg = TransformerConfig(dtype=jnp.float32, **dict(sizes, n_experts_held=0, expert_share_index=0))
    whole = off_their_defaults(init_params(jax.random.PRNGKey(seed), whole_cfg))["layers"]
    whole = {k: v[0, 0] for k, v in whole.items()}
    lp = {k: (v[share * held : (share + 1) * held] if k in ("w_gate", "w_in", "w_out") else v) for k, v in whole.items()}
    return cfg, lp, whole, sizes


def run_layer(cfg, lp, x):
    fn = T._make_layer_fn(cfg, None, kind=("nope", "experts"))
    return jax.jit(lambda x, lp: fn(x, lp))(x, lp)


def test_the_eight_shares_of_a_layer_add_up_to_the_uncut_layer():
    """Eight chips, two of sixteen experts each: a share's layer output is
    ``x' + y_share`` with the attention and the residual ``x'`` the same on
    every chip; counted once, with the eight shares' expert parts, they are
    the layer of the uncut reference — what the cell's chip leaves out is what
    the other seven hold. The router counts over all 16 on every share."""
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 48, 32))
    _, _, whole, sizes = one_layer(0, shares=8)
    uncut = dict(sizes, n_experts_held=0, expert_share_index=0)
    with jax.default_matmul_precision("highest"):
        want = ref.layer(whole, x, uncut, "nope")
        # what every chip computes alike: the state after the attention
        after_attention = x + ref._attention(whole, ref._rms_norm(x, whole["ln1"], 1e-6), uncut, "nope")
        parts, rows = [], []
        for share in range(8):
            cfg, lp, _, share_sizes = one_layer(share, shares=8)
            y, aux = run_layer(cfg, lp, x)
            parts.append(y - after_attention)
            rows.append(int(aux["held"]))
            mine = dict(whole, **{k: lp[k] for k in ("w_gate", "w_in", "w_out")})
            np.testing.assert_allclose(y, ref.layer(mine, x, share_sizes, "nope"), atol=3e-5)
            assert int(jnp.sum(aux["counts"])) == 2 * 48 * 4 and aux["counts"].shape == (16,)
    np.testing.assert_allclose(after_attention + sum(parts), want, atol=1e-4)
    assert sum(rows) == 2 * 48 * 4 and len(set(rows)) > 1  # every token-expert row on exactly one share


def test_the_gates_gradient_reaches_the_layers_input_through_its_first_norm():
    """With the attention's output projection and the second norm's path cut off
    from ``x`` — ``wo`` zero, so x' = x, and the experts' input held constant —
    the layer's input still gets a gradient: the chosen weights', through
    ``h1 = N1(x)``. With the gate on the feed-forward's own input there is
    none on that path, and ``ln1``'s weight gets none at all."""
    cfg, lp, _, _ = one_layer()
    lp = dict(lp, wo=jnp.zeros_like(lp["wo"]))
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 24, 32))

    def through_the_gate(cfg):
        def out(x, ln1):
            p = dict(lp, ln1=ln1)
            tokens = x.reshape(-1, 32)
            (top_w, top_idx), _ = T._gate_ahead(p, T._norm(cfg, x, ln1), cfg) if cfg.router_input == "layer" else (
                T._route(p, jax.lax.stop_gradient(tokens), cfg)[:2], None)
            y, *_ = T._ffn_moe(p, jax.lax.stop_gradient(x), cfg, (top_w, top_idx))
            return jnp.sum(y**2)

        return jax.grad(out, argnums=(0, 1))(x, lp["ln1"])

    dx, dln1 = through_the_gate(cfg)
    assert float(jnp.max(jnp.abs(dx))) > 1e-3 and float(jnp.max(jnp.abs(dln1))) > 1e-3
    # in the whole layer: ln1's gradient with wo zero is the gate's alone, and the reference's
    with jax.default_matmul_precision("highest"):
        fn = T._make_layer_fn(cfg, None, kind=("nope", "experts"))
        mine = jax.grad(lambda ln1: jnp.sum(fn(x, dict(lp, ln1=ln1))[0] ** 2))(lp["ln1"])
        sizes = dict(LAYER, n_layers=1, nope_layers=(1,))
        theirs = jax.grad(lambda ln1: jnp.sum(ref.layer(dict(lp, ln1=ln1), x, sizes, "nope") ** 2))(lp["ln1"])
        late = dataclasses.replace(cfg, router_input="ffn")
        fn_late = T._make_layer_fn(late, None, kind=("nope", "experts"))
        none = jax.grad(lambda ln1: jnp.sum(fn_late(x, dict(lp, ln1=ln1))[0] ** 2))(lp["ln1"])
    assert float(jnp.max(jnp.abs(mine))) > 1e-3
    np.testing.assert_allclose(mine, theirs, rtol=1e-4, atol=1e-6)
    assert float(jnp.max(jnp.abs(none))) == 0.0


def test_the_feed_forward_takes_the_gate_it_is_given_and_keeps_it_for_the_backward():
    """``_ffn_moe`` under a given gate runs no router (a router of NaNs changes
    nothing) and says no load; under ``remat`` the layer is three checkpoints and
    the router's product and top-k are in the first, ahead of the mixer's, and
    NOT in the feed-forward's, whose recomputation therefore routes nothing: the
    chosen experts and weights are inputs of its checkpoint."""
    cfg, lp, _, _ = one_layer()
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 24, 32))
    h1 = T._norm(cfg, x, lp["ln1"])
    gate, (balance, counts) = T._gate_ahead(lp, h1, cfg)
    y, said = T._ffn_moe(dict(lp, router=jnp.full_like(lp["router"], jnp.nan)), x, cfg, gate)
    assert set(T._moe_said(cfg, said, gate_ahead=True)) == {"held", "live_share", "gate_zeros"} and bool(jnp.all(jnp.isfinite(y)))
    assert int(jnp.sum(counts)) == 2 * 24 * 4 and float(balance) > 0
    # the layer as three checkpoints: the router's part holds the top-k and no grouped matmul, the mixer's neither,
    # the feed-forward's the grouped matmuls and NO top-k — the chosen experts [T, k] int32 are among its inputs
    remat = dataclasses.replace(cfg, remat=True)
    fn = T._make_layer_fn(remat, None, kind=("nope", "experts"), remat_parts=True)
    parts = [e for e in jax.make_jaxpr(lambda x: fn(x, lp)[0])(x).eqns if e.primitive.name == "remat2"]
    held = [(primitives(e.params["jaxpr"]), [v.aval for v in e.invars]) for e in parts]
    assert [("top_k" in p, "ragged_dot_general" in p) for p, _ in held] == [(True, False), (False, False), (False, True)]
    assert [a for a in held[2][1] if a.shape == (48, 4) and a.dtype == jnp.int32]


def primitives(jaxpr, out=None):
    """The names of a jaxpr's primitives, sub-jaxprs included."""
    from tests.test_window_gqa import sub_jaxprs

    out = set() if out is None else out
    for eqn in jaxpr.eqns:
        out.add(eqn.primitive.name)
        for sub in sub_jaxprs(eqn):
            primitives(sub, out)
    return out


def test_relu_gated_experts_and_the_share_of_lanes_it_zeroes():
    """``moe_dropless`` and ``moe_dropless_held`` under ``jax.nn.relu`` against
    a loop over the experts, forward and backward, the held one through the
    single window and through every window (a bound below the rows held); the
    share of the computed rows' gate lanes left zero is counted by hand."""
    t, d, f, e, k = 96, 32, 16, 8, 2
    keys = jax.random.split(jax.random.PRNGKey(7), 6)
    tokens = jax.random.normal(keys[0], (t, d))
    w_gate, w_in = (jax.random.normal(key, (e, d, f)) * d**-0.5 for key in keys[1:3])
    w_out = jax.random.normal(keys[3], (e, f, d)) * f**-0.5
    top_idx = jnp.argsort(jax.random.normal(keys[4], (t, e)), axis=-1)[:, :k].astype(jnp.int32)
    top_w = jax.nn.softmax(jax.random.normal(keys[5], (t, k)), axis=-1)

    def by_hand(tokens, top_w, first, held):
        y, zeros, rows = jnp.zeros_like(tokens), 0.0, 0
        for j in range(held):
            mine = jnp.sum(jnp.where(top_idx == first + j, top_w, 0.0), axis=-1, keepdims=True)
            g = jax.nn.relu(tokens @ w_gate[first + j])
            y = y + mine * ((g * (tokens @ w_in[first + j])) @ w_out[first + j])
            chose = jnp.any(top_idx == first + j, axis=-1)
            zeros, rows = zeros + jnp.sum((g == 0) & chose[:, None]), rows + jnp.sum(chose)
        return y, zeros / (rows * f)

    with jax.default_matmul_precision("highest"):
        y, counts, zero_share = moe_dropless(tokens, top_idx, top_w, w_gate, w_in, w_out, jax.nn.relu, True)
        want, share = by_hand(tokens, top_w, 0, e)
        np.testing.assert_allclose(y, want, atol=2e-5)
        assert float(zero_share) == pytest.approx(float(share), abs=1e-6) and 0.4 < float(zero_share) < 0.6
        assert int(jnp.sum(counts)) == t * k
        assert len(moe_dropless(tokens, top_idx, top_w, w_gate, w_in, w_out)) == 2  # unasked: what it returned before
        for bound in (t * k, 32):  # one window; every window in turn (32 < the ~96 rows held)
            held = lambda tok, w, b=bound: moe_dropless_held(
                tok, top_idx, w, w_gate[2:6], w_in[2:6], w_out[2:6], 2, b, activation=jax.nn.relu, gate_zeros=True
            )
            y, n_held, zero_share = jax.jit(held)(tokens, top_w)
            want, share = by_hand(tokens, top_w, 2, 4)
            np.testing.assert_allclose(y, want, atol=2e-5)
            assert int(n_held) > 32 and float(zero_share) == pytest.approx(float(share), abs=1e-6)
            got = jax.grad(lambda tok, w: jnp.sum(held(tok, w)[0] ** 2), argnums=(0, 1))(tokens, top_w)
            ours = jax.grad(lambda tok, w: jnp.sum(by_hand(tok, w, 2, 4)[0] ** 2), argnums=(0, 1))(tokens, top_w)
            for a, b in zip(got, ours):
                np.testing.assert_allclose(a, b, atol=1e-4)
        np.testing.assert_allclose(
            swiglu(tokens, w_gate[0], w_in[0], w_out[0], jax.nn.relu),
            (jax.nn.relu(tokens @ w_gate[0]) * (tokens @ w_in[0])) @ w_out[0], atol=1e-6,
        )


# -- what it says, what it refuses, and what it leaves as it was -----------------------------------


def test_each_kind_says_its_path_once_and_the_pattern_tells_the_unrotated_kind(monkeypatch):
    from torchft_tpu import telemetry

    monkeypatch.setattr(T, "_PATHS_SAID", set())
    cfg, params, tokens, _ = make("stack", seq=32)
    kinds = ("attention_path", "layer_pattern", "expert_path")
    seen = {kind: len(telemetry.EVENTS.recent(kind)) for kind in kinds}
    for _ in range(2):
        jax.jit(lambda p: loss_fn(p, tokens, cfg, None))(params)
    paths = telemetry.EVENTS.recent("attention_path")[seen["attention_path"]:]
    said = sorted((e["n_heads"], e["n_kv_heads"], e["window"], e["rotary_dim"], e["impl"]) for e in paths)
    assert said == [(6, 2, 0, 0, "plain"), (6, 2, 8, 16, "plain")]  # the global kind rotates 0 lanes, the band all 16
    (pattern,) = telemetry.EVENTS.recent("layer_pattern")[seen["layer_pattern"]:]
    assert (pattern["lead"], pattern["period"], pattern["repeats"]) == ("nope.experts", "window.experts", 3)
    assert (pattern["experts_held"], pattern["experts"]) == (4, 16)
    (path,) = telemetry.EVENTS.recent("expert_path")[seen["expert_path"]:]
    assert (path["router_input"], path["activation"], path["experts_held"], path["top_k"]) == ("layer", "relu", 4, 4)
    assert (path["router_gate"], path["renormalize"], path["batch"], path["seq"]) == ("softmax", True, 2, 32)
    # a model with a rotated global layer too names both kinds apart
    both = TransformerConfig(dtype=jnp.float32, **SIZES["full_and_nope"])
    assert [_k[0] for _k in both.layer_kinds()] == ["full", "nope"]


def test_a_model_that_declares_none_of_the_fields_says_no_expert_path(monkeypatch):
    from torchft_tpu import telemetry

    monkeypatch.setattr(T, "_PATHS_SAID", set())
    cfg = TransformerConfig(**dict(T.PRESETS["tiny"], n_experts=4, top_k=2))
    before = len(telemetry.EVENTS.recent("expert_path"))
    loss, stats = loss_and_stats(init_params(jax.random.PRNGKey(0), cfg), jnp.zeros((1, 16), jnp.int32), cfg)
    assert telemetry.EVENTS.recent("expert_path")[before:] == [] and "gate_zero_share" not in stats


def test_the_new_parts_ops_carry_their_names_under_attn_and_moe():
    """The ``op_name`` of the compiled program's ops, what a device trace
    carries: the layer without positions under ``attn/global`` with
    ``global_core`` inside and NO rotation's sine or cosine, the window layers
    under ``attn/window`` with ``window_core``; the router under ``moe/router``
    — forward and, for its product's transpose, backward — and the ReLU's
    ``max`` under ``moe/experts``."""
    cfg, params, tokens, _ = make("stack", seq=32)
    lowered = jax.jit(jax.grad(lambda p: loss_fn(p, tokens, cfg))).lower(params)
    names = set(re.findall(r'op_name="([^"]+)"', lowered.compile().as_text()))
    under = lambda pattern: [n for n in names if re.search(pattern, n)]
    assert under(r"attn\W.*global/global_core/") and under(r"attn\W.*window/window_core/")
    assert under(r"attn\W.*window/(sin|cos)") and not under(r"attn\W.*global/(sin|cos)")
    assert under(r"jvp\(moe\)/.*router/dot_general") and under(r"transpose\(jvp\(moe\)\)/.*router/dot_general")
    assert under(r"moe\W.*router/.*top_k") and under(r"moe\W.*dispatch/") and under(r"moe\W.*combine/")
    located = set(re.findall(r'loc\("([^"]+)"', lowered.as_text(debug_info=True)))
    assert [n for n in located if re.search(r"moe\W.*experts/(jit\(relu\)|max)", n)]
    assert not [n for n in located if re.search(r"attn\W.*router", n)]  # the early gate is the moe scope's


@pytest.mark.parametrize("changes, message", [
    (dict(router_input="mixer"), "router_input must be"),
    (dict(router_input="layer", n_experts=0, n_experts_held=0), "it comes with n_experts"),
    (dict(expert_activation="gelu"), "expert_activation must be one of"),
    (dict(n_dense_layers=1), "a dense layer's own activation is missing"),
    (dict(n_experts=0, n_experts_held=0, router_input="ffn"), "it comes with n_experts"),
    (dict(nope_layers=(3,)), "each at most once"),
    (dict(nope_layers=(1,), window=8, window_layers=(1,)), "each at most once"),
    (dict(nope_layers=(1, 2), n_heads_per_layer=(6, 4)), "layers of one kind agree"),
])
def test_what_the_configuration_refuses(changes, message):
    with pytest.raises(ValueError, match=message):
        TransformerConfig(**dict(LAYER, n_layers=2, **changes))


def test_what_the_new_fields_refuse_under_ep_sp_and_pp():
    """Experts over chips name the missing mechanism; a sequence shard and a
    pipeline stage refuse the band, the groups and the pattern as they did."""
    ep = make_mesh(MeshConfig(ep=2), devices=jax.devices()[:2])
    for over in (dict(expert_activation="silu"), dict(router_input="ffn")):  # each new field by itself
        cfg, params, tokens, _ = make("nope_only", seq=32, n_experts_held=0, expert_share_index=0, top_k=2, n_kv_heads=0, **over)
        with pytest.raises(ValueError, match="ep=2 with router_input=.* a dispatch over ep that takes a gate it is given .* are missing"):
            with jax.set_mesh(ep):
                jax.jit(lambda p, t: loss_fn(p, t, cfg, ep))(params, tokens)
    cfg, params, tokens, _ = make("stack", seq=32)
    with pytest.raises(ValueError, match="stages that each hold their own kinds of layer .* are missing"):
        loss_fn(params, tokens, dataclasses.replace(cfg, pp=2))
    sp = make_mesh(MeshConfig(sp=2), devices=jax.devices()[:2])
    with pytest.raises(ValueError, match="sp=2 with a window .* are missing"), jax.set_mesh(sp):
        jax.jit(lambda p, t: loss_fn(p, t, cfg, sp))(params, tokens)


def test_a_looped_stack_takes_the_gate_ahead_and_a_pipeline_names_what_is_missing():
    """``ut_steps`` > 1 composes with the new fields where it composes with
    experts at all — a stack of one kind: the loop runs the same layers twice,
    each turn's gate from that turn's input (it runs, and every leaf gets a
    gradient). ``pp`` > 1 does not: the dropless experts' Pallas call cannot be
    traced in the pipeline's manual region, and the layer says so by name."""
    cfg, params, tokens, _ = make("nope_only", seq=32)
    looped = dataclasses.replace(cfg, ut_steps=2)
    (loss, stats), grads = jax.jit(jax.value_and_grad(loss_and_stats, has_aux=True), static_argnums=2)(params, tokens, looped)
    assert np.isfinite(float(loss)) and all(float(jnp.max(jnp.abs(g))) > 0 for g in jax.tree_util.tree_leaves(grads))
    assert stats["tokens_per_expert"].shape == (4, 16) and stats["gate_zero_share"].shape == (4,)  # 2 layers x 2 turns
    mesh = make_mesh(MeshConfig(pp=2), devices=jax.devices()[:2])
    staged = dataclasses.replace(cfg, pp=2, microbatches=2)
    staged_params = dict(params, layers=jax.tree_util.tree_map(lambda a: a.reshape((2, 1) + a.shape[2:]), params["layers"]))
    with pytest.raises(ValueError, match="pp=2 with router_input='layer'.* a grouped matmul typed for that region .* is missing"):
        with jax.set_mesh(mesh):
            jax.jit(lambda p, t: loss_fn(p, t, staged, mesh))(staged_params, tokens)


def test_the_fused_step_learns_on_the_stack_and_says_the_relus_share():
    cfg, _, tokens, _ = make("stack", seq=32)
    mesh = make_mesh(MeshConfig(), devices=jax.devices()[:1])
    ts = TrainStep(cfg, optax.adamw(1e-2), mesh)
    params = ts.init_params(jax.random.PRNGKey(0))
    opt = ts.init_opt(params)
    losses = []
    for _ in range(6):
        loss, params, opt = ts.step(params, opt, ts.shard_batch(tokens))
        losses.append(float(loss))
    assert losses[-1] < losses[0] - 0.3
    assert set(ts.last_stats) == {"tokens_per_expert", "balance_loss", "rows_held", "window_live_share", "gate_zero_share"}
    load = np.asarray(ts.last_stats["tokens_per_expert"])
    assert load.shape == (4, 16) and (load.sum(axis=1) == 2 * 32 * 4).all()  # the four layers, all 16 experts
    np.testing.assert_array_equal(ts.last_stats["rows_held"], load[:, 4:8].sum(axis=1))  # share 1 holds experts 4..7
    zeroed = np.asarray(ts.last_stats["gate_zero_share"])
    assert zeroed.shape == (4,) and (zeroed > 0.3).all() and (zeroed < 0.7).all()  # about half at init


def test_the_chain_of_grads_cuts_a_stack_of_one_kind_with_the_gate_ahead():
    """``TrainStep.grads`` as L + 2 programs on a stack of one kind: the pieces
    are the one program's gradients, the router's among them."""
    cfg, _, tokens, _ = make("nope_only", seq=32)
    mesh = make_mesh(MeshConfig(), devices=jax.devices()[:1])
    ts = TrainStep(cfg, optax.sgd(1e-2), mesh)
    params = ts.init_params(jax.random.PRNGKey(0))
    t = ts.shard_batch(tokens)
    loss, grads = ts.grads(params, t)
    assert len(grads) == cfg.n_layers + 2
    tree = ts.grads_tree(grads)
    with jax.set_mesh(mesh):
        loss1, one_program, _ = ts._value_and_grad(params, t)
    assert float(loss) == pytest.approx(float(loss1), rel=1e-6)
    for (path, got), want in zip(jax.tree_util.tree_leaves_with_path(tree), jax.tree_util.tree_leaves(one_program)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6, err_msg=jax.tree_util.keystr(path))
    assert float(jnp.max(jnp.abs(tree["layers"]["router"]))) > 0


def test_a_dp_x_fsdp_x_tp_mesh_gives_the_unsharded_loss():
    """tp = 2 divides the key/value heads (2) and the query heads (6)."""
    cfg, params, tokens, _ = make("stack", seq=32)
    tokens = jnp.concatenate([tokens, tokens[::-1]], axis=0)
    want = float(jax.jit(loss_fn, static_argnums=2)(params, tokens, cfg))
    mesh = make_mesh(MeshConfig(dp=2, fsdp=2, tp=2), devices=jax.devices()[:8])
    ts = TrainStep(cfg, optax.adamw(1e-2), mesh)
    loss, grads = ts.grads(jax.device_put(params, ts._param_shardings), ts.shard_batch(tokens))
    assert float(loss) == pytest.approx(want, rel=1e-5)
    g_want = jax.jit(jax.grad(loss_fn), static_argnums=2)(params, tokens, cfg)
    assert max(jax.tree_util.tree_leaves(grad_errors(grads, g_want))) < 5e-4


# -- an existing configuration's program is what it was ------------------------------------------------------------


# sha256 of the text that ``loss_fn``'s value and gradient lower to for two of the benchmark's configurations at their
# REHEARSAL sizes (``benchmark/tests/rehearsal.json`` + the file's own ``rehearsal`` group) and (2, 32) tokens, computed
# at f55ce30 — the parent of the PR that brought ``nope_layers``, ``router_input`` and ``expert_activation`` — and here, by
# one script: a model that declares none of the three lowers to the program it had. laguna-xs2-1g shares ``_mix_full``'s
# window and global kinds, grouped heads and ``moe_dropless_held``; qwen3-next-80b-a3b-1g ``_route``'s renormalised
# softmax, a shared expert and its gate. (The nine cells' jaxprs at their REAL sizes are held to their parents' by
# ``CELLS_PROGRAMS`` in tests/test_gated_conv_train.py, test_gdn_train.py, test_mla_rope_mtp_train.py and
# test_looped_train.py, which this PR leaves as they are.) laguna-xs2-1g re-pinned by the PR that took
# ``jnp.take_along_axis`` out of ``_route``'s sigmoid branch (30c86893…85fd at b50bcfa and before): a ``gather`` a sparse
# layer a forward pass and its ``scatter-add`` gone, ``_chosen``'s compare, select and sum over the experts in their
# place — old counts against new in ``tests/test_gdn_train.CELLS_PROGRAMS``' comment; qwen3-next-80b-a3b-1g's softmax
# gate reads ``top_k``'s own values and did not move. Nor did either by the PR that hands the delta rule's block inverse
# on as a second residual: at the rehearsal's head width, no lane tile, qwen3-next-80b-a3b-1g's mixer is the ``jax.numpy`` form.
# Both re-pinned by the PR that moves the held layer's rows by the count of live ones (``ops/layers._live_rows``; f33873fb…3d12 and
# 6a3ed706…3d9c at 92920b9 and before): a ``while`` over passes of rows into ``jax.lax.empty`` where a gather of the window's
# places stood, and ``window_live_share`` a layer — old counts against new in ``tests/test_gdn_train.CELLS_PROGRAMS``' comment.
REHEARSAL_PROGRAMS = {
    "laguna-xs2-1g": "c612dc3332214c96f79565849f9f6f5baab0e0f718558eebedb1e7ce1dc9fbbe",
    "qwen3-next-80b-a3b-1g": "6fd1c101ef255eeb789da3d66a1ae2b22a8f306efdd9bf1b98ca27d7371a627a",
}


def rehearsal_program(name):
    with open(os.path.join(ROOT, "benchmark", "configs", name + ".json")) as f:
        config = json.load(f)
    with open(os.path.join(ROOT, "benchmark", "tests", "rehearsal.json")) as f:
        tc = dict(config["program"]["transformer_config"], **json.load(f)["transformer_config"])
    tc.update((config.get("rehearsal") or {}).get("transformer_config", {}))
    cfg = TransformerConfig(**{**tc, "dtype": jnp.dtype(tc["dtype"])})
    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    with mock.patch.object(T, "_say_once", lambda *a, **k: None):
        lowered = jax.jit(jax.value_and_grad(lambda p, t: loss_fn(p, t, cfg))).lower(params, jax.ShapeDtypeStruct((2, 32), jnp.int32))
    return hashlib.sha256(lowered.as_text().encode()).hexdigest()


@pytest.mark.parametrize("name", list(REHEARSAL_PROGRAMS))
def test_a_configuration_that_declares_none_of_the_fields_lowers_to_the_program_it_had(name):
    assert rehearsal_program(name) == REHEARSAL_PROGRAMS[name]
