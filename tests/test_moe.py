"""OLMoE's block through the one transformer: dropless top-k experts, a
float32 router whose weights are not renormalised, QK-norm, and the
load-balancing term in the loss — against the plain reference
(``benchmark/reference/olmoe_decoder.py``, loaded by path: one copy).

Tolerance: both sides compute in float32 on the CPU (matmuls at "highest");
what differs is the order of the sums — rows sorted by expert and a grouped
matmul on one side, every expert over every token on the other; a scan,
remat and chunked attention on one side, none on the other — a few float32
ulps of values of order 1 accumulated over a few hundred terms: 2e-5
relative to the largest entry of a gradient leaf (measured here: under 2e-6).
A term left out, a dropped token or a renormalised router is off by orders
more; the tests below show each.
"""

import dataclasses
import functools
import importlib.util
import os
from datetime import timedelta

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from torchft_tpu.models import transformer
from torchft_tpu.models.transformer import (
    PRESETS,
    TransformerConfig,
    init_params,
    loss_and_stats,
    loss_fn,
)
from torchft_tpu.ops import layers
from torchft_tpu.ops.layers import moe_dropless, moe_dropless_held
from torchft_tpu.parallel.mesh import MeshConfig, make_mesh
from torchft_tpu.parallel.train_step import TrainStep

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RTOL = 2e-5


def _load_reference():
    path = os.path.join(ROOT, "benchmark", "reference", "olmoe_decoder.py")
    spec = importlib.util.spec_from_file_location("olmoe_decoder_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load_reference()

# two tiny sizes: few experts, and OLMoE's own count and experts per token
SIZES = {
    "e8k2": dict(
        vocab_size=97, d_model=32, n_layers=2, n_heads=4, head_dim=8, d_ff=16,
        n_experts=8, top_k=2, qk_norm=True, router_aux_loss_coef=0.01,
        rope_theta=10000.0, norm_eps=1e-5,
    ),
    "e64k8": dict(
        vocab_size=97, d_model=32, n_layers=2, n_heads=4, head_dim=8, d_ff=8,
        n_experts=64, top_k=8, qk_norm=True, router_aux_loss_coef=0.01,
        rope_theta=10000.0, norm_eps=1e-5,
    ),
}


def make(size, skewed=False):
    sizes = dict(SIZES[size])
    cfg = TransformerConfig(dtype=jnp.float32, remat=True, **sizes)
    params = init_params(jax.random.PRNGKey(3), cfg)
    layers = params["layers"]
    # norm weights off 1, or a norm applied without its weight would pass
    spread = lambda a, lo, hi: a * jnp.linspace(lo, hi, a.shape[-1])
    layers["ln1"], layers["ln2"] = layers["ln1"] * 1.3, layers["ln2"] * 0.8
    if cfg.qk_norm:
        layers["q_norm"] = spread(layers["q_norm"], 0.6, 1.5)
        layers["k_norm"] = spread(layers["k_norm"], 1.4, 0.7)
    params["final_norm"] = params["final_norm"] * 0.7
    # a livelier router than the 1/sqrt(d) init: the experts' loads differ
    layers["router"] = layers["router"] * 3.0
    if skewed:
        # one coordinate of the residual stream positive in every token, and
        # expert 0's router column large on it: expert 0 is among the k of
        # (nearly) every token, where a capacity of 1.25 x the mean load
        # would hold 1.25 k / E of them
        params["embed"] = params["embed"].at[:, 0].add(4.0)
        layers["router"] = layers["router"].at[..., 0, 0].set(6.0)
    tokens = jnp.asarray(np.random.default_rng(0).integers(0, 97, (2, 24)), jnp.int32)
    return cfg, params, tokens, sizes


def grad_errors(g_got, g_want):
    return jax.tree_util.tree_map(
        lambda a, b: float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b))), g_got, g_want
    )


def system(cfg, params, tokens):
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(loss_fn)(params, tokens, cfg)


@pytest.mark.parametrize("skewed", [False, True], ids=["balanced", "skewed"])
@pytest.mark.parametrize("size", sorted(SIZES))
def test_loss_and_every_gradient_leaf_agree_with_the_reference(size, skewed):
    cfg, params, tokens, sizes = make(size, skewed)
    got, g_got = system(cfg, params, tokens)
    want, g_want = jax.value_and_grad(ref.loss)(params, tokens, sizes)
    assert float(got) == pytest.approx(float(want), rel=RTOL)
    errs = grad_errors(g_got, g_want)
    assert set(errs["layers"]) >= {"q_norm", "k_norm", "router", "w_gate", "w_in", "w_out"}
    assert max(jax.tree_util.tree_leaves(errs)) < RTOL, errs
    load = np.asarray(loss_and_stats(params, tokens, cfg)[1]["tokens_per_expert"])
    assert load.shape == (cfg.n_layers, cfg.n_experts)
    assert (load.sum(axis=1) == tokens.size * cfg.top_k).all()  # no row is dropped
    if skewed:
        # the load a capacity would have cut: 1.25 x the mean holds 1.25 k / E
        assert (load[:, 0] >= 0.9 * tokens.size).all(), load[:, 0]
        assert load[:, 0].min() > 3 * 1.25 * tokens.size * cfg.top_k / cfg.n_experts


@pytest.mark.parametrize("size", sorted(SIZES))
def test_the_check_would_see_renormalised_weights_a_dropped_expert_or_no_qk_norm(size):
    cfg, params, tokens, sizes = make(size)
    want = float(ref.loss(params, tokens, sizes))
    assert float(system(cfg, params, tokens)[0]) == pytest.approx(want, rel=RTOL)
    far = 10 * RTOL * want  # the nearest miss below is 17 x the tolerance
    # the k weights divided by their sum: OLMoE does not (norm_topk_prob false)
    renormalised = dict(sizes, norm_topk_prob=True)
    assert abs(float(ref.loss(params, tokens, renormalised)) - want) > far
    g_got, g_off = system(cfg, params, tokens)[1], jax.grad(ref.loss)(params, tokens, renormalised)
    assert grad_errors(g_got, g_off)["layers"]["w_out"] > 1000 * RTOL
    # one expert fewer a token
    assert abs(float(ref.loss(params, tokens, dict(sizes, top_k=sizes["top_k"] - 1))) - want) > far
    # the program without its QK-norm (the two weights are then unused leaves)
    no_norm = dataclasses.replace(cfg, qk_norm=False)
    assert abs(float(system(no_norm, params, tokens)[0]) - want) > far
    # the balance term left out of the loss
    no_aux = dataclasses.replace(cfg, router_aux_loss_coef=0.0)
    assert abs(float(system(no_aux, params, tokens)[0]) - want) > far


def test_per_sequence_loss_is_what_the_worker_compares():
    """One sequence a call: the balance term is over that sequence's tokens."""
    cfg, params, tokens, sizes = make("e64k8")
    per_seq = ref.per_sequence_loss(params, tokens, sizes)
    for i in range(2):
        assert float(loss_fn(params, tokens[i : i + 1], cfg)) == pytest.approx(float(per_seq[i]), rel=RTOL)
    # f_e and P_e are shares of the call: the batch's term is not the sequences' mean
    assert float(ref.loss(params, tokens, sizes)) != pytest.approx(float(jnp.mean(per_seq)), rel=RTOL)


def test_a_zero_router_balances_at_k_and_the_term_reaches_the_router():
    cfg, params, tokens, _ = make("e64k8")
    params["layers"]["router"] = jnp.zeros_like(params["layers"]["router"])
    stats = loss_and_stats(params, tokens, cfg)[1]
    # p = 1/E for every expert: E * sum_e f_e / E = sum_e f_e = k
    assert float(stats["balance_loss"]) == pytest.approx(cfg.top_k, rel=1e-6)
    # ties go to the lowest indices: every token to experts 0..k-1, none dropped
    load = np.asarray(stats["tokens_per_expert"])
    assert (load[:, : cfg.top_k] == tokens.size).all() and (load[:, cfg.top_k :] == 0).all()
    # the cross entropy's own gradient into the router, and the term's on top
    no_aux = dataclasses.replace(cfg, router_aux_loss_coef=0.0)
    g_aux = jax.grad(loss_fn)(params, tokens, cfg)["layers"]["router"]
    g_ce = jax.grad(loss_fn)(params, tokens, no_aux)["layers"]["router"]
    assert float(jnp.max(jnp.abs(g_aux - g_ce))) > 1e-6


def test_every_expert_chosen_is_the_dense_sum_over_all_experts():
    """top_k = n_experts: the sort and the grouped matmul against an einsum."""
    rng = np.random.default_rng(1)
    t, d, f, e = 40, 16, 8, 4
    x, wg, wi, wo = (jnp.asarray(0.3 * rng.normal(size=s), jnp.float32) for s in ((t, d), (e, d, f), (e, d, f), (e, f, d)))
    p = jax.nn.softmax(jnp.asarray(rng.normal(size=(t, e)), jnp.float32), axis=-1)
    top_w, top_idx = jax.lax.top_k(p, e)

    def dense(x, wg, wi, wo):
        h = jax.nn.silu(jnp.einsum("td,edf->tef", x, wg)) * jnp.einsum("td,edf->tef", x, wi)
        return jnp.einsum("te,ted->td", p, jnp.einsum("tef,efd->ted", h, wo))

    def sparse(x, wg, wi, wo):
        y, counts = moe_dropless(x, top_idx, top_w, wg, wi, wo)
        assert counts.shape == (e,)
        return y

    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(sparse(x, wg, wi, wo), dense(x, wg, wi, wo), rtol=1e-5, atol=1e-6)
        loss = lambda fn: (lambda *a: jnp.sum(fn(*a) ** 2))
        got = jax.grad(loss(sparse), argnums=(0, 1, 2, 3))(x, wg, wi, wo)
        want = jax.grad(loss(dense), argnums=(0, 1, 2, 3))(x, wg, wi, wo)
    assert max(grad_errors(got, want)) < RTOL


# -- the sigmoid gate's k weights, read without a gather -------------------------------------


def route_with_a_gather(lp, tokens, cfg):
    """The sigmoid branch of ``_route`` as it stood while it read the chosen
    experts' weights with ``jnp.take_along_axis`` — a gather of T·k scalars,
    1.34 ms a layer a pass on the chip at [16384, 256] — written out here as
    the plain reference of what the select in its place has to give."""
    scores = jax.nn.sigmoid(jnp.dot(tokens, lp["router"], preferred_element_type=jnp.float32))
    biased = scores + lp["router_bias"].astype(jnp.float32) if "router_bias" in lp else scores
    _, top_idx = jax.lax.top_k(biased, cfg.top_k)
    top_w = jnp.take_along_axis(scores, top_idx, axis=-1)
    if cfg.router_renormalize:
        top_w = top_w / (jnp.sum(top_w, axis=-1, keepdims=True) + cfg.router_norm_eps)
    return top_w * cfg.routed_scaling_factor, top_idx


def sigmoid_router(experts, k, bias, renormalize, scale, tied, tokens=64, d=32):
    """(config, the router's leaves, tokens [T, d], a [T, k] mix for a scalar
    of the weights). ``tied``: every odd expert is its even neighbour's copy —
    column and bias — so every token's biased scores tie in pairs."""
    cfg = TransformerConfig(**{
        **SIZES["e8k2"], "n_experts": experts, "top_k": k, "router_gate": "sigmoid",
        "router_renormalize": renormalize, "routed_scaling_factor": scale,
    })
    keys = jax.random.split(jax.random.PRNGKey(experts + k), 4)
    lp = {"router": 3.0 * jax.random.normal(keys[0], (d, experts)) * d**-0.5}
    if bias:
        lp["router_bias"] = 0.2 * jax.random.normal(keys[1], (experts,))
    if tied:
        lp = {name: leaf.at[..., 1::2].set(leaf[..., 0::2]) for name, leaf in lp.items()}
    return cfg, lp, jax.random.normal(keys[2], (tokens, d)), jax.random.normal(keys[3], (tokens, k))


@pytest.mark.parametrize("tied", [False, True], ids=["distinct", "tied"])
@pytest.mark.parametrize("renormalize,scale", [(True, 2.446), (False, 2.446), (True, 1.0)], ids=["renorm_x2.446", "asis_x2.446", "renorm_x1"])
@pytest.mark.parametrize("bias", [True, False], ids=["bias", "nobias"])
@pytest.mark.parametrize("experts,k", [(32, 4), (256, 8), (16, 4)])
def test_the_sigmoid_gates_weights_and_gradients_are_the_gathers_bit_for_bit(experts, k, bias, renormalize, scale, tied):
    """``_route``'s sigmoid branch against :func:`route_with_a_gather`: the same
    experts in the same order, the weights bit-equal, and the gradient of a
    scalar of the weights to the tokens and to the router bit-equal — one term
    of each of the select's sums is not zero. With tied biased scores the lower
    index comes first, as ``jax.lax.top_k`` says it does."""
    cfg, lp, tokens, mix = sigmoid_router(experts, k, bias, renormalize, scale, tied)
    got_w, got_idx, probs = transformer._route(lp, tokens, cfg)
    want_w, want_idx = route_with_a_gather(lp, tokens, cfg)
    np.testing.assert_array_equal(got_idx, want_idx)
    np.testing.assert_array_equal(got_w, want_w)
    np.testing.assert_allclose(jnp.sum(probs, axis=-1), 1.0, rtol=1e-6)
    if renormalize:
        np.testing.assert_allclose(jnp.sum(got_w, axis=-1), scale, rtol=1e-6)
    if tied:  # a token's choice is made of whole pairs, the even one first, at one weight
        assert bool(jnp.all(got_idx[:, 0::2] % 2 == 0)) and bool(jnp.all(got_idx[:, 1::2] == got_idx[:, 0::2] + 1))
        np.testing.assert_array_equal(got_w[:, 0::2], got_w[:, 1::2])

    def scalar(route):
        return lambda tokens, router: jnp.sum(mix * route({**lp, "router": router}, tokens, cfg)[0] ** 2)

    got = jax.grad(scalar(transformer._route), argnums=(0, 1))(tokens, lp["router"])
    want = jax.grad(scalar(route_with_a_gather), argnums=(0, 1))(tokens, lp["router"])
    for g, w in zip(got, want):
        assert float(jnp.max(jnp.abs(w))) > 0
        np.testing.assert_array_equal(g, w)


def gathers_of_scores(jaxpr, tokens, experts, primitives=("gather", "scatter-add"), above=""):
    """The ``gather`` and ``scatter-add`` equations (or those of ``primitives``)
    anywhere in ``jaxpr`` whose operand is a float32 [tokens, experts], as
    (primitive, the named scopes around it: an equation's name stack is told
    from its own jaxpr, so the enclosing equations' stand before it)."""
    from tests.test_window_gqa import sub_jaxprs

    found = []
    for eqn in jaxpr.eqns:
        stack = above + "/" + str(eqn.source_info.name_stack)
        if eqn.primitive.name in primitives and (eqn.invars[0].aval.shape, eqn.invars[0].aval.dtype) == ((tokens, experts), jnp.float32):
            found.append((eqn.primitive.name, stack))
        for sub in sub_jaxprs(eqn):
            found += gathers_of_scores(sub, tokens, experts, primitives, stack)
    return found


def test_the_sigmoid_gates_program_holds_no_gather_of_the_scores():
    """Value and gradient of the gate at a small size: no ``gather`` reads and
    no ``scatter-add`` writes a [T, E] float32; with the gather put back the
    walk finds one of each under the router's name, so it sees them where they
    are. Compiled, the read alone and its transpose give the gather's bits
    (around them a fusion may order the renormalisation's sums its own way:
    the eager cases above hold the whole branch to the reference)."""
    cfg, lp, tokens, mix = sigmoid_router(32, 4, bias=True, renormalize=True, scale=2.446, tied=False)

    def program(route):
        def scalar(tokens, router):
            with jax.named_scope("router"):
                return jnp.sum(mix * route({**lp, "router": router}, tokens, cfg)[0] ** 2)
        return jax.make_jaxpr(jax.value_and_grad(scalar, argnums=(0, 1)))(tokens, lp["router"]).jaxpr

    assert gathers_of_scores(program(transformer._route), 64, 32) == []
    found = gathers_of_scores(program(route_with_a_gather), 64, 32)
    assert sorted(name for name, _ in found) == ["gather", "scatter-add"] and all("router" in stack for _, stack in found)

    scores = jax.nn.sigmoid(tokens @ lp["router"])
    top_idx = jax.lax.top_k(scores + lp["router_bias"], cfg.top_k)[1]
    gather = lambda scores, top_idx: jnp.take_along_axis(scores, top_idx, axis=-1)
    np.testing.assert_array_equal(jax.jit(transformer._chosen)(scores, top_idx), jax.jit(gather)(scores, top_idx))
    got, want = (jax.jit(jax.grad(lambda s: jnp.sum(mix * read(s, top_idx))))(scores) for read in (transformer._chosen, gather))
    assert float(jnp.max(jnp.abs(want))) > 0
    np.testing.assert_array_equal(got, want)


def test_the_tpu_kernel_and_the_xla_form_are_the_same_grouped_matmul():
    """The Pallas kernel the TPU runs (here in the interpreter) against
    ``jax.lax.ragged_dot``: values and both gradients, with rows that do not
    fill a row tile (the wrapper pads them) and an empty group."""
    from torchft_tpu.ops.layers import _grouped_matmul_tpu

    rng = np.random.default_rng(2)
    m, k, n, e = 200, 128, 256, 4
    rows = jnp.asarray(0.3 * rng.normal(size=(m, k)), jnp.float32)
    w = jnp.asarray(0.3 * rng.normal(size=(e, k, n)), jnp.float32)
    counts = jnp.asarray([90, 0, 70, 40], jnp.int32)
    kernel = lambda r, w: _grouped_matmul_tpu(r, w, counts, interpret=True)
    xla = lambda r, w: jax.lax.ragged_dot(r, w, counts)
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(kernel(rows, w), xla(rows, w), rtol=1e-5, atol=1e-6)
        loss = lambda fn: (lambda r, w: jnp.sum(jnp.sin(fn(r, w))))
        got = jax.grad(loss(kernel), argnums=(0, 1))(rows, w)
        want = jax.grad(loss(xla), argnums=(0, 1))(rows, w)
    assert max(grad_errors(got, want)) < RTOL


@pytest.mark.parametrize("dtype,tile", [(jnp.bfloat16, (512, 1024, 1024)), (jnp.float32, (512, 512, 512))])
def test_the_kernels_tile_is_sized_in_bytes(monkeypatch, dtype, tile):
    """The swept tile is for 2-byte operands; float32 ones take half the
    contraction and half the columns (at the full tile the chip's compiler
    refuses the kernel for VMEM: the float32 program of
    ``benchmark/check_laguna.py``, PR 41)."""
    from jax.experimental.pallas.ops.tpu import megablox
    from torchft_tpu.ops.layers import _grouped_matmul_tpu

    asked = []

    def recorder(rows, w, counts, preferred_element_type, tiling, interpret):
        asked.append(tiling)
        return jax.lax.ragged_dot(rows, w, counts)

    monkeypatch.setattr(megablox, "gmm", recorder)
    counts = jnp.asarray([512, 512], jnp.int32)
    _grouped_matmul_tpu(jnp.ones((1024, 2048), dtype), jnp.ones((2, 2048, 1024), dtype), counts)
    assert asked == [tile]


def test_two_shapes_in_one_program_trace_with_cache_miss_explanations_on():
    """``benchmark/run.py`` sets JAX_EXPLAIN_CACHE_MISSES; jax 0.9.0's explanation
    raises on the second shape of a ``platform_dependent`` branch it has seen
    (PR 26's first chip call of the kernel path died there)."""
    from torchft_tpu.ops.layers import _grouped_matmul

    counts = jnp.asarray([3, 5], jnp.int32)
    two = jax.jit(lambda x, w1, w2: _grouped_matmul(_grouped_matmul(x, w1, counts), w2, counts))
    with jax.explain_cache_misses(True):
        out = two(jnp.ones((8, 4)), jnp.ones((2, 4, 6)), jnp.ones((2, 6, 4)))
    assert out.shape == (8, 4) and float(out[0, 0]) == 24.0


# -- experts held under a share: the rows the layer holds, and no tensor of T·k rows ----------------

T_HELD, K_HELD, E_ALL, HELD, D_HELD, F_HELD = 48, 4, 16, 4, 32, 16


def held_reference(tokens, top_idx, top_w, w_gate, w_in, w_out, first_expert):
    """Every held expert over every token in float32, kept where the token chose it."""
    y = jnp.zeros(tokens.shape, jnp.float32)
    for e in range(w_gate.shape[0]):
        out = (jax.nn.silu(tokens @ w_gate[e]) * (tokens @ w_in[e])) @ w_out[e]
        gate = jnp.sum(jnp.where(top_idx == first_expert + e, top_w, 0.0), axis=1, keepdims=True)
        y = y + gate * out
    return y


def held_before_pr44(tokens, top_idx, top_w, w_gate, w_in, w_out, first_expert, row_bound):
    """``moe_dropless_held`` as it stood at dc040bf, plain (autodiff for its
    hand-written gradients): all T·k slots sorted and inverted, a window's
    computed rows spread back over every slot — [T·k, d], a zero row where a
    slot is not in the window — and an ``einsum`` over a token's k."""
    t, k = top_idx.shape
    held = w_gate.shape[0]
    m = min(row_bound, t * k)
    local = top_idx.reshape(t * k) - first_expert
    key = jnp.where((local >= 0) & (local < held), local, held)
    order = jnp.argsort(key, stable=True)
    inv = jnp.argsort(order)
    counts = jnp.sum(key[:, None] == jnp.arange(held, dtype=key.dtype), axis=0, dtype=jnp.int32)
    ends = jnp.cumsum(counts)
    n_held = ends[-1]
    order = jnp.pad(order, (0, -(t * k) % m))

    def window(start):
        slots = jax.lax.dynamic_slice_in_dim(order, start, m)
        at = jnp.where(inv < n_held, inv - start, -1)
        rows = tokens[slots // k]
        edges = jnp.clip(jnp.concatenate([jnp.zeros((1,), ends.dtype), ends]), start, start + m)
        sizes = edges[1:] - edges[:-1]
        h = jax.nn.silu(layers._grouped_matmul(rows, w_gate, sizes)) * layers._grouped_matmul(rows, w_in, sizes)
        out = layers._grouped_matmul(h, w_out, sizes)
        out = jnp.where((start + jnp.arange(m))[:, None] < n_held, out, jnp.zeros_like(out))
        padded = jnp.concatenate([out, jnp.zeros((1, out.shape[1]), out.dtype)])
        back = padded[jnp.where((at >= 0) & (at < m), at, m)].reshape(t, k, -1)  # [T, k, d]
        return jnp.einsum("tkd,tk->td", back, top_w.astype(back.dtype))

    if m == t * k:
        return window(0), n_held

    def every_window():
        y, _ = jax.lax.scan(lambda y, s: (y + window(s), None), jnp.zeros_like(tokens), jnp.arange(0, t * k, m))
        return y

    return jax.lax.cond(n_held <= m, lambda: window(0), every_window), n_held


def held_operands(dtype=jnp.float32):
    keys = jax.random.split(jax.random.PRNGKey(5), 5)
    return (
        jax.random.normal(keys[0], (T_HELD, D_HELD)).astype(dtype),
        jax.nn.softmax(2.0 * jax.random.normal(keys[1], (T_HELD, K_HELD)), axis=-1).astype(dtype),
        (jax.random.normal(keys[2], (HELD, D_HELD, F_HELD)) * D_HELD**-0.5).astype(dtype),
        (jax.random.normal(keys[3], (HELD, D_HELD, F_HELD)) * D_HELD**-0.5).astype(dtype),
        (jax.random.normal(keys[4], (HELD, F_HELD, D_HELD)) * F_HELD**-0.5).astype(dtype),
    )


def held_routing(case):
    """(top_idx [T, k], first_expert, row_bound): k distinct experts of 16 a token."""
    rng = np.random.default_rng(11)
    idx = np.stack([rng.permutation(E_ALL)[:K_HELD] for _ in range(T_HELD)])
    first, bound = 0, 2 * T_HELD * K_HELD * HELD // E_ALL  # twice the rows at balance, as the model sizes it
    if case == "overflows_the_first_window":
        bound = 8  # ~48 rows held: the `lax.cond` takes `every_window`
    elif case == "a_token_with_all_k_held":
        idx[0] = np.arange(K_HELD)
    elif case == "a_token_with_none_held":
        idx[1] = HELD + np.arange(K_HELD)
    elif case == "an_expert_with_no_rows":
        for row in idx:  # whoever chose expert 2 takes an absent one it had not chosen
            row[row == 2] = next(e for e in range(HELD, E_ALL) if e not in row)
    elif case == "first_expert_above_zero":
        first = 8
    elif case == "no_row_held":
        idx = np.stack([HELD + rng.permutation(E_ALL - HELD)[:K_HELD] for _ in range(T_HELD)])
    elif case == "one_window_of_every_slot":
        bound = T_HELD * K_HELD
    elif case == "overflows_a_window_of_two_and_a_half_passes":
        bound = 40  # ~48 rows held: `every_window`, whose windows move their rows as one gather whatever PASSES says
    elif case == "several_passes_a_move":
        pass  # the balanced case, its 96 slots moved 16 a pass
    else:
        assert case == "balanced", case
    return jnp.asarray(idx, jnp.int32), first, bound


HELD_CASES = [
    "balanced", "overflows_the_first_window", "a_token_with_all_k_held", "a_token_with_none_held",
    "an_expert_with_no_rows", "first_expert_above_zero", "no_row_held", "one_window_of_every_slot",
    "overflows_a_window_of_two_and_a_half_passes", "several_passes_a_move",
]
# rows a pass of the loop that moves a window's live rows, where a case sets it under the window's slots
PASSES = {"overflows_a_window_of_two_and_a_half_passes": 16, "several_passes_a_move": 16}


@pytest.mark.parametrize("case", HELD_CASES)
def test_the_held_path_agrees_with_the_reference_and_with_the_path_it_replaced(case, monkeypatch):
    """``y``, the rows held and the gradient of every operand — tokens, gates
    and the three weights — against every held expert over every token in
    float32, and against the path before PR 44 with its [T·k, d] spread."""
    top_idx, first, bound = held_routing(case)
    monkeypatch.setattr(layers, "_MOVE_ROWS", PASSES.get(case, layers._MOVE_ROWS))
    operands = held_operands()
    probe = jnp.cos(jnp.arange(T_HELD * D_HELD, dtype=jnp.float32)).reshape(T_HELD, D_HELD)
    want_rows = int(jnp.sum((top_idx >= first) & (top_idx < first + HELD)))
    if case.startswith("overflows"):
        assert want_rows > bound
    if case == "no_row_held":
        assert want_rows == 0

    def scalar(fn):
        def loss(*ops):
            out = fn(*ops)
            y, rows = out if isinstance(out, tuple) else (out, None)
            return jnp.sum(y * probe), (y, rows)

        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4), has_aux=True))

    with jax.default_matmul_precision("highest"):
        (_, (y, rows)), got = scalar(
            lambda *ops: moe_dropless_held(ops[0], top_idx, *ops[1:], first_expert=first, row_bound=bound)
        )(*operands)
        (_, (y_ref, _)), want = scalar(
            lambda *ops: held_reference(ops[0], top_idx, *ops[1:], first_expert=first)
        )(*operands)
        (_, (y_old, rows_old)), old = scalar(
            lambda *ops: held_before_pr44(ops[0], top_idx, *ops[1:], first_expert=first, row_bound=bound)
        )(*operands)
    assert int(rows) == int(rows_old) == want_rows
    np.testing.assert_allclose(y, y_ref, atol=2e-5)
    np.testing.assert_allclose(y, y_old, atol=2e-5)
    for theirs in (want, old):
        for name, a, b in zip(("tokens", "top_w", "w_gate", "w_in", "w_out"), got, theirs):
            scale = float(jnp.max(jnp.abs(b))) or 1.0  # no row held: every gradient is zero
            assert float(jnp.max(jnp.abs(a - b))) / scale < RTOL, (case, name)


def test_the_held_path_keeps_bfloat16_rows_and_sums_a_tokens_rows_in_float32():
    """bfloat16 operands: the rows stay bfloat16 and a token's k products are
    summed in float32 and rounded once, as the ``einsum`` it replaced did —
    the two agree to an ulp of bfloat16, where a sum kept in bfloat16 would
    be off by k of them."""
    top_idx, first, bound = held_routing("a_token_with_all_k_held")
    operands = held_operands(jnp.bfloat16)
    y, _ = jax.jit(lambda *ops: moe_dropless_held(ops[0], top_idx, *ops[1:], first_expert=first, row_bound=bound))(*operands)
    old, _ = jax.jit(lambda *ops: held_before_pr44(ops[0], top_idx, *ops[1:], first_expert=first, row_bound=bound))(*operands)
    exact = held_reference(*(o.astype(jnp.float32) for o in operands[:1]), top_idx, *(o.astype(jnp.float32) for o in operands[1:]), first)
    assert y.dtype == jnp.bfloat16
    scale = float(jnp.max(jnp.abs(exact)))
    assert float(jnp.max(jnp.abs(y.astype(jnp.float32) - old.astype(jnp.float32)))) <= 2**-7 * scale
    assert float(jnp.max(jnp.abs(y.astype(jnp.float32) - exact))) <= 4 * 2**-7 * scale


@functools.partial(jax.jit, static_argnums=3)
def live_rows(src, idx, n_live, rows_a_pass):
    return layers._live_rows(src, idx, n_live, rows_a_pass)


@pytest.mark.parametrize("d", [256, 384])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("n_live", [0, 1, 15, 16, 17, 40])
def test_a_move_moves_the_live_rows_and_ends_with_them(n_live, dtype, d):
    """``_live_rows`` at 40 places, 16 a pass (two passes and an overlapping
    half): the live rows are the gather's, whatever the count — none, one, a
    pass's edge and the rows beside it, all; no pass runs past the live rows
    (off the TPU ``jax.lax.empty`` is zeros: a row no pass wrote reads 0); and a
    row of ``src`` that only dead places name may hold NaN."""
    rng = np.random.default_rng(n_live)
    idx = rng.permutation(64)[:40]
    src = rng.standard_normal((64, d)) + 3.0  # no zero row
    src[idx[n_live:]] = np.nan  # the dead places' rows
    src, idx = jnp.asarray(src, dtype), jnp.asarray(idx, jnp.int32)
    got = live_rows(src, idx, jnp.int32(n_live), 16)
    assert got.shape == (40, d) and got.dtype == dtype
    np.testing.assert_array_equal(got[:n_live], src[idx[:n_live]])
    assert bool(jnp.all(jnp.isfinite(got[:n_live].astype(jnp.float32))))
    moved = min(-(-n_live // 16) * 16, 40)  # whole passes; the third starts at place 24
    unwritten = got[moved if moved <= 32 else 40 :]
    assert bool(jnp.all(unwritten == 0))


def test_a_token_with_no_held_row_may_hold_nan_and_nothing_reads_it(monkeypatch):
    """Token 1 chose absent experts alone: its slots are dead places of the
    window, and with NaN in its row ``y`` and every gradient are what they
    were — its own rows zero — forward and backward, 16 rows a pass."""
    monkeypatch.setattr(layers, "_MOVE_ROWS", 16)
    top_idx, first, bound = held_routing("a_token_with_none_held")
    operands = held_operands()
    poisoned = (operands[0].at[1].set(jnp.nan), *operands[1:])
    probe = jnp.cos(jnp.arange(T_HELD * D_HELD, dtype=jnp.float32)).reshape(T_HELD, D_HELD)

    @jax.jit
    def run(*ops):
        loss = lambda *ops: jnp.sum(moe_dropless_held(ops[0], top_idx, *ops[1:], first_expert=first, row_bound=bound)[0] * probe)
        return jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4))(*ops)

    (want, g_want), (got, g_got) = run(*operands), run(*poisoned)
    assert float(got) == float(want)
    for a, b in zip(g_got, g_want):
        np.testing.assert_array_equal(a, b)
    assert not g_got[0][1].any() and not g_got[1][1].any()


def _every_aval(jaxpr):
    """The variables an equation of ``jaxpr`` binds, and those of every jaxpr
    in an equation's parameters (a `cond`'s branches, a `scan`'s body, a
    `custom_vjp`'s call, a `platform_dependent`'s lowerings)."""
    def inside(value):
        if hasattr(value, "eqns"):
            yield from _every_aval(value)
        elif hasattr(value, "jaxpr"):
            yield from inside(value.jaxpr)
        elif isinstance(value, (tuple, list)):
            for v in value:
                yield from inside(v)

    for eqn in jaxpr.eqns:
        for var in eqn.outvars:
            yield var.aval
        for value in eqn.params.values():
            yield from inside(value)


@pytest.mark.parametrize("which", ["forward", "gradient"])
def test_no_tensor_of_all_the_slots_is_as_wide_as_a_row(which):
    """The rehearsal's widths (16 experts, 4 held, 4 a token, rows of 32) at
    64 tokens and a window of 64 of the 256 slots: nothing in the traced
    program of the held layer — forward, or `jax.grad` with respect to every
    operand — has T·k rows (or T x k) of width d; vectors may be that long.
    The path it replaced, walked the same way, has them."""
    t, k, d = 64, 4, 32
    rng = np.random.default_rng(3)
    top_idx = jnp.asarray(np.stack([rng.permutation(16)[:k] for _ in range(t)]), jnp.int32)
    operands = (jnp.ones((t, d)), jnp.ones((t, k)), jnp.ones((4, d, 16)), jnp.ones((4, d, 16)), jnp.ones((4, 16, d)))

    def traced(fn):
        value = lambda *ops: jnp.sum(fn(ops[0], top_idx, *ops[1:], first_expert=4, row_bound=64)[0])
        run = value if which == "forward" else jax.grad(jax.checkpoint(value), argnums=(0, 1, 2, 3, 4))
        return jax.make_jaxpr(run)(*operands).jaxpr

    def all_slots_wide(jaxpr):
        return sorted({
            a.shape for a in _every_aval(jaxpr)
            if len(getattr(a, "shape", ())) >= 2 and a.shape[-1] == d and int(np.prod(a.shape[:-1])) >= t * k
        })

    assert all_slots_wide(traced(moe_dropless_held)) == []
    assert all_slots_wide(traced(held_before_pr44))  # the walk sees them where they are


def test_the_held_layer_runs_its_tpu_lowering_in_the_interpreter_forward_and_backward(monkeypatch):
    """The grouped matmuls AND the one-hot product that sums a token's rows
    as the TPU runs them — JAX's Pallas kernels, here interpreted — against
    the lowering off the TPU: ``y`` and every gradient, with a window that
    does not fill a row tile and token tiles with no row."""
    gmm_tpu, sum_tpu = layers._grouped_matmul_tpu, layers._rows_to_tokens_tpu
    top_idx, first, bound = held_routing("an_expert_with_no_rows")
    operands = held_operands()
    probe = jnp.sin(jnp.arange(T_HELD * D_HELD, dtype=jnp.float32)).reshape(T_HELD, D_HELD)

    def run():
        loss = lambda *ops: jnp.sum(
            moe_dropless_held(ops[0], top_idx, *ops[1:], first_expert=first, row_bound=bound)[0] * probe
        )
        with jax.default_matmul_precision("highest"):
            return jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4))(*operands)

    want = run()
    calls = []

    def on_tpu(*args, tpu, default):
        calls.append(1)
        return tpu(*args)

    monkeypatch.setattr(layers.jax.lax, "platform_dependent", on_tpu)
    monkeypatch.setattr(layers, "_grouped_matmul_tpu", lambda *a: gmm_tpu(*a, interpret=True))
    monkeypatch.setattr(layers, "_rows_to_tokens_tpu", lambda *a: sum_tpu(*a, interpret=True))
    got = run()
    assert len(calls) >= 6  # three grouped matmuls, the combine, and the backward's products
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
    assert max(grad_errors(got[1], want[1])) < RTOL


def test_the_sum_of_a_tokens_rows_traces_at_two_shapes_with_cache_miss_explanations_on():
    """As ``_grouped_matmul`` above: the combine's product is traced at a row
    width and at a lane tile (the gates' gradient) in one program."""
    onehot = jnp.arange(6)[:, None] == jnp.arange(layers._TOKEN_TILE)
    sizes = jnp.asarray([6], jnp.int32)
    two = jax.jit(lambda a, b: (layers._rows_to_tokens(onehot, a, sizes, 8), layers._rows_to_tokens(onehot, b, sizes, 8)))
    with jax.explain_cache_misses(True):
        wide, narrow = two(jnp.ones((6, 16)), jnp.ones((6, 4)))
    assert wide.shape == (8, 16) and narrow.shape == (8, 4)
    assert float(wide[5, 0]) == 1.0 and float(wide[6, 0]) == 0.0  # a row a token, none past the sixth


def test_remat_on_and_off_agree():
    cfg, params, tokens, _ = make("e64k8")
    on, g_on = system(cfg, params, tokens)
    off, g_off = system(dataclasses.replace(cfg, remat=False), params, tokens)
    assert float(on) == pytest.approx(float(off), rel=1e-6)
    assert max(jax.tree_util.tree_leaves(grad_errors(g_on, g_off))) < RTOL


def test_the_chunked_head_carries_the_balance_term(monkeypatch):
    cfg, params, tokens, sizes = make("e8k2")
    plain, g_plain = system(cfg, params, tokens)
    monkeypatch.setattr(transformer, "_LOSS_CHUNK_ELEMS", 2 * 5 * cfg.vocab_size)  # chunks of 5 positions
    chunked, g_chunked = system(cfg, params, tokens)
    assert float(chunked) == pytest.approx(float(plain), rel=1e-6)
    assert float(chunked) == pytest.approx(float(ref.loss(params, tokens, sizes)), rel=RTOL)
    assert max(jax.tree_util.tree_leaves(grad_errors(g_chunked, g_plain))) < RTOL
    no_aux = dataclasses.replace(cfg, router_aux_loss_coef=0.0)
    assert abs(float(system(no_aux, params, tokens)[0]) - float(chunked)) > 1e-3


def test_what_is_refused_under_pp_and_ep():
    cfg, params, tokens, _ = make("e8k2")
    with pytest.raises(ValueError, match="not carried across pipeline stages"):
        loss_fn(params, tokens, dataclasses.replace(cfg, pp=2))
    # experts over chips still run the top-2 capacity dispatch: no other k, no term
    mesh = make_mesh(MeshConfig(ep=2), devices=jax.devices()[:2])
    for bad in (dict(top_k=4, router_aux_loss_coef=0.0), dict(top_k=2)):
        refused = dataclasses.replace(cfg, **bad)
        with pytest.raises(ValueError, match="experts over chips"), jax.set_mesh(mesh):
            jax.jit(lambda p, t: loss_fn(p, t, refused, mesh))(params, tokens)


def test_the_preset_is_olmoe_as_published():
    cfg = TransformerConfig(**PRESETS["olmoe-1b-7b"])
    shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    n = sum(int(np.prod(l.shape)) for l in jax.tree_util.tree_leaves(shapes))
    per_layer = 4 * 2048 * 2048 + 64 * 3 * 2048 * 1024 + 2048 * 64 + 4 * 2048  # attention, experts, router, four norms
    assert n == 16 * per_layer + 2 * 50304 * 2048 + 2048 == 6_919_161_856
    assert (cfg.n_experts, cfg.top_k, cfg.qk_norm, cfg.router_aux_loss_coef) == (64, 8, True, 0.01)


# -- through TrainStep and FTTrainer -------------------------------------------

TRAIN = dict(SIZES["e8k2"], vocab_size=128)


@pytest.fixture(scope="module")
def train_step():
    mesh = make_mesh(MeshConfig(), devices=jax.devices()[:1])
    return TrainStep(TransformerConfig(dtype=jnp.float32, **TRAIN), optax.adamw(1e-2), mesh)


def batches(n):
    rng = np.random.default_rng(0)
    return [jnp.asarray(rng.integers(0, TRAIN["vocab_size"], (2, 16)), jnp.int32) for _ in range(n)]


def fused_losses(ts, n):
    params = ts.init_params(jax.random.PRNGKey(0))
    opt = ts.init_opt(params)
    out = []
    for tokens in batches(n):
        loss, params, opt = ts.step(params, opt, ts.shard_batch(tokens))
        out.append(float(loss))
    return out


def test_the_fused_step_learns_and_keeps_the_last_steps_statistics(train_step):
    params = train_step.init_params(jax.random.PRNGKey(0))
    opt = train_step.init_opt(params)
    tokens = train_step.shard_batch(batches(1)[0])
    losses = []
    for _ in range(8):
        out = train_step.step(params, opt, tokens)
        assert len(out) == 3  # the signature the benchmark's loop calls
        loss, params, opt = out
        losses.append(float(loss))
    assert losses[-1] < losses[0] - 0.5
    stats = train_step.last_stats
    assert set(stats) == {"tokens_per_expert", "balance_loss"}
    load = np.asarray(stats["tokens_per_expert"])
    assert load.shape == (2, 8) and (load.sum(axis=1) == 2 * 16 * 2).all()
    loss, grads = train_step.grads(params, tokens)  # (loss, grads), as FTTrainer calls it
    # in pieces, the head's first (this stack is cut a layer at a time); one helper gives the parameters' shape
    assert len(grads) == 2 + 2 and set(grads[0]) == {"final_norm", "out"} and set(grads[-1]) == {"embed"}
    assert jax.tree_util.tree_structure(train_step.grads_tree(grads)) == jax.tree_util.tree_structure(params)


def test_a_dense_model_has_no_statistics():
    dense = {k: v for k, v in TRAIN.items() if k not in ("n_experts", "top_k", "router_aux_loss_coef")}
    mesh = make_mesh(MeshConfig(), devices=jax.devices()[:1])
    ts = TrainStep(TransformerConfig(dtype=jnp.float32, **dense), optax.adamw(1e-2), mesh)
    params = ts.init_params(jax.random.PRNGKey(0))
    loss, params, opt = ts.step(params, ts.init_opt(params), ts.shard_batch(batches(1)[0]))
    assert np.isfinite(float(loss)) and ts.last_stats == {}


def test_ft_steps_at_world_size_one_are_the_fused_steps_and_record_the_counters(train_step, monkeypatch):
    """The gradients make the trip through ``ddp``'s buckets (several: the
    smallest bucket the knob allows) and come back what they were."""
    from torchft_tpu.collectives import CollectivesTcp
    from torchft_tpu.coordination import LighthouseServer
    from torchft_tpu.manager import Manager
    from torchft_tpu.parallel.ft import FTTrainer
    from torchft_tpu.store import StoreServer
    from torchft_tpu.telemetry.tracing import TRACER

    monkeypatch.setenv("TORCHFT_WIRE_BUCKET_BYTES", str(1 << 16))
    want = fused_losses(train_step, 3)
    lighthouse = LighthouseServer(bind="[::]:0", min_replicas=1)
    store = StoreServer()
    manager = Manager(
        collectives=CollectivesTcp(timeout=timedelta(seconds=10)),
        load_state_dict=None, state_dict=None, min_replica_size=1, replica_id="moe_0",
        store_addr=store.address(), lighthouse_addr=lighthouse.address(),
        rank=0, world_size=1, timeout=timedelta(seconds=10),
    )
    try:
        trainer = FTTrainer(manager, train_step)
        trainer.init(jax.random.PRNGKey(0))
        got = []
        for tokens in batches(3):
            out = trainer.step(tokens)
            assert len(out) == 2 and out[1] is True  # (loss, committed)
            got.append(out[0])
    finally:
        manager.shutdown(wait=False)
        store.shutdown()
        lighthouse.shutdown()
    # the same programs' arithmetic, split in two and through the host: to rounding
    assert got == pytest.approx(want, rel=1e-6)
    syncs = [s["attrs"] for s in TRACER.recent("loss_sync") if "max_load" in s.get("attrs", {})]
    assert len(syncs) >= 3
    attrs = syncs[-1]
    assert attrs["mean_load"] == 2 * 16 * 2 / 8 and attrs["min_load"] <= attrs["mean_load"] <= attrs["max_load"]
    assert 1.9 < attrs["balance_loss"] < 8.0
