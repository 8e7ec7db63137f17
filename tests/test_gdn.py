"""Gated DeltaNet as the sequence mixer of three layers in four, a gated softmax
layer of grouped heads rotated on a part of their lanes, a softmax router
renormalised over the chosen and a gated shared expert through the one
transformer — against the plain reference
(``benchmark/reference/qwen3_next_decoder.py``, loaded by path: one copy) and the
core against the delta rule taken one position after another.
``tests/test_gdn_train.py`` holds ``TrainStep``, the Manager, the names in the
lowered program and the other cells' programs: a file of its own, so that a
worker of the tier-1 run gets half of the compiles.

Tolerance of the float32 comparisons: both sides compute in float32 on the CPU
(matmuls at "highest"); what differs is the order of the sums — the program
takes the rule in chunks of 64 positions where the reference takes positions,
sorts rows by expert where the reference masks. Measured here: the loss to 2e-7
of itself, gradient leaves to 1.3e-4 of their largest entry over the seeds below
(the convolution's taps and the key projection, through the L2 norm and decays of
tens of nats a position) — float32's own distance there: against the reference
in float64 the program reads 5.2e-5 and the float32 reference 7.2e-5 on the same
leaf (``gdn.dense``, seed 3). A slip in the structure reads 1e-2 and more.
"""

import dataclasses
import functools
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torchft_tpu.models import transformer as T
from torchft_tpu.models.transformer import TransformerConfig, init_params, layer_pattern, loss_fn
from torchft_tpu.ops.kda import gdn_chunked, kda_recurrent
from torchft_tpu.ops.layers import rms_norm
from torchft_tpu.parallel.mesh import MeshConfig, make_mesh

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RTOL = 3e-4
BF16_BAND = 3e-2


def _load_reference():
    path = os.path.join(ROOT, "benchmark", "reference", "qwen3_next_decoder.py")
    spec = importlib.util.spec_from_file_location("qwen3_next_decoder_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load_reference()

BASE = dict(vocab_size=64, d_model=32, norm_eps=1e-6, norm_zero_centered=True)
GDN = dict(linear_n_heads=4, linear_n_key_heads=2, linear_head_dim=8, conv_kernel=4)
ATTENTION = dict(
    n_heads=4, n_kv_heads=2, head_dim=16, rotary_dim=4, rope_pairing="half", rope_theta=1e7,
    qk_norm=True, qk_norm_per_head=True, attn_output_gate=True,
)
EXPERTS = dict(
    moe_d_ff=16, n_experts=16, n_experts_held=4, expert_share_index=1, top_k=4, n_shared_experts=1,
    shared_expert_gate=True, router_gate="softmax", router_renormalize=True,
)
# the cell's stack at tiny widths: one period, three Gated DeltaNet layers and the gated softmax layer, experts in all
STACK = dict(BASE, **GDN, **ATTENTION, **EXPERTS, n_layers=4, gdn_layers=(1, 2, 3))
SIZES = {
    "gdn.dense": dict(BASE, **GDN, n_heads=2, head_dim=16, d_ff=64, n_layers=2, gdn_layers=(1, 2)),  # one kind: the plain scan
    "gated.dense": dict(BASE, **ATTENTION, d_ff=64, n_layers=2),
    "stack": STACK,
}


def off_their_defaults(params):
    """Norm weights off their start (zero under ``1 + w``, one for the mixer's
    own), a router with loads that differ: or a norm without its weight, or a
    plain one taken for a zero-centred one, would pass."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(params)
    out = []
    for i, (path, a) in enumerate(leaves):
        name = path[-1].key
        if name in ("ln1", "ln2", "q_norm", "k_norm", "final_norm", "o_norm"):
            a = a + 0.3 * jnp.sin(jnp.arange(a.size, dtype=jnp.float32).reshape(a.shape) + i)
        elif name == "router":
            a = a * 3.0
        out.append(a)
    return jax.tree_util.tree_unflatten(treedef, out)


def make(size, seq=40, remat=False, seed=3, **changes):
    sizes = dict(SIZES[size], **changes)
    cfg = TransformerConfig(dtype=jnp.float32, remat=remat, **sizes)
    params = off_their_defaults(init_params(jax.random.PRNGKey(seed), cfg))
    tokens = jnp.asarray(np.random.default_rng(seed).integers(0, 64, (2, seq)), jnp.int32)
    return cfg, params, tokens, sizes


def grad_errors(g_got, g_want):
    return jax.tree_util.tree_map(
        lambda a, b: float(jnp.max(jnp.abs(a - b)) / (jnp.max(jnp.abs(b)) + 1e-30)), g_got, g_want
    )


# One jitted function a file, so that cases which differ in a seed or a value, and tests that take one configuration
# at one shape, share a trace (it is keyed by ``cfg``, the shapes and the precision; tracing and lowering are half of
# a cold run's seconds). A test that patches what TRACING reads — a module's constant, a path's choice — builds its own.
_loss_and_grads = jax.jit(jax.value_and_grad(loss_fn), static_argnums=2)


def system(cfg, params, tokens):
    with jax.default_matmul_precision("highest"):
        return _loss_and_grads(params, tokens, cfg)


# -- the core against the rule one position after another ------------------------------------------


def core_inputs(seq, decay, hk=2, hv=4, d=8, seed=0):
    rng = np.random.default_rng(seed)
    unit = lambda x: x / jnp.sqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)
    draw = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    q, k = unit(draw(2, seq, hk, d)) * d**-0.5, unit(draw(2, seq, hk, d))
    g = -jnp.asarray(rng.uniform(0, decay, size=(2, seq, hv)), jnp.float32)
    beta = jnp.asarray(rng.uniform(0, 1, size=(2, seq, hv)), jnp.float32)
    return q, k, draw(2, seq, hv, d), g, beta, draw(2, hv, d, d)


def by_position(q, k, v, g, beta, S0):
    """``kda_recurrent`` with value head j reading key head j // 2 and the head's one decay over its lanes."""
    rep = v.shape[2] // q.shape[2]
    return kda_recurrent(jnp.repeat(q, rep, axis=2), jnp.repeat(k, rep, axis=2), v, jnp.broadcast_to(g[..., None], v.shape), beta, S0)


def paths_to_kernels(jaxpr, path=(), out=None):
    """{a ``pallas_call``'s name: the set of chains of primitives around its calls}."""
    from tests.test_window_gqa import sub_jaxprs

    out = {} if out is None else out
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.setdefault(eqn.params["name"], set()).add(path)
        else:
            for sub in sub_jaxprs(eqn):
                paths_to_kernels(sub, path + (eqn.primitive.name,), out)
    return out


def enters_the_kernel(*inputs, **kw):
    """Whether ``gdn_chunked`` hands these inputs to the Pallas kernel pair — and if so with no ``cond`` around it."""
    paths = paths_to_kernels(jax.make_jaxpr(lambda *a: gdn_chunked(*a, **kw))(*inputs).jaxpr)
    assert not any("cond" in path for around in paths.values() for path in around)
    return "gdn_fwd" in paths


WIDE = 128  # the kernels' head width: interpreted here


@pytest.mark.parametrize("d", [8, WIDE], ids=["jax.numpy", "gdn_kernel"])
@pytest.mark.parametrize("seq", [64, 100, 192])
@pytest.mark.parametrize("decay", [0.1, 3.0, 25.0])  # nats a position at most: mild, a trained model's, the published init's
def test_the_chunked_core_is_the_recurrence_value_and_every_gradient(seq, decay, d):
    """``gdn_chunked`` (16 -> 32 heads' reading at 2 -> 4) against the rule one
    position after another, outputs, final state and the gradient of all six
    inputs; a sequence of whole chunks, one with a padded tail, and decays up
    to 25 nats a POSITION, where the per-channel kernels' two-factor form
    (80 nats over 16 positions) would not serve. Heads 8 wide are the
    ``jax.numpy`` form; heads 128 wide the Pallas kernel pair with one decay a
    head (``ops/pallas/kda.gdn_forward`` / ``gdn_backward``, interpreted), at
    every decay alike: a lone chunk padded to the two a grid step takes (64),
    a tail padded (100), a pair and a half (192)."""
    inputs = core_inputs(seq, decay, d=d)
    assert enters_the_kernel(*inputs[:5], initial_state=inputs[5]) == (d == WIDE)
    probe = jnp.asarray(np.random.default_rng(9).normal(size=inputs[2].shape), jnp.float32)

    def scalar(fn):
        def f(*a):
            o, S = fn(*a)
            return jnp.sum(o * probe) + jnp.sum(S * S), (o, S)
        return f

    grads = lambda fn: jax.jit(jax.grad(scalar(fn), argnums=tuple(range(6)), has_aux=True))
    with jax.default_matmul_precision("highest"):
        g_got, (o_got, S_got) = grads(lambda *a: gdn_chunked(*a[:5], initial_state=a[5]))(*inputs)
        g_want, (o_want, S_want) = grads(by_position)(*inputs)
    np.testing.assert_allclose(o_got, o_want, atol=2e-5)
    np.testing.assert_allclose(S_got, S_want, atol=2e-5)
    assert max(grad_errors(g_got, g_want)) < 2e-5, grad_errors(g_got, g_want)


def test_a_pairs_decay_is_summed_over_its_own_positions():
    """Thousands of nats ahead of a pair that decays little: the pair's factor
    is exact to float32's relative step, where the difference of two running
    sums would carry their absolute one (1e-4 at 2 000)."""
    from torchft_tpu.ops.kda import _sums_between

    g = jnp.concatenate([jnp.full((30,), -70.0), jnp.asarray([-0.25, -0.125, -0.5]), jnp.full((31,), -70.0)])[:, None]
    between = np.asarray(_sums_between(g.astype(jnp.float32)))
    assert between[32, 29] == -0.875 and between[31, 30] == -0.125 and between[10, 10] == 0.0 and between[5, 9] == 0.0
    assert between[33, 0] == pytest.approx(-70.0 * 29 - 0.875 - 70.0, rel=1e-6)


@pytest.mark.parametrize("d", [8, WIDE], ids=["jax.numpy", "gdn_kernel"])
def test_the_decays_gradient_is_at_the_pair_sums_precision(d):
    """The same stretch through the whole core, both forms: the three mild
    positions' pairs are all that is left of the chunk, and the gradient of
    THEIR decays agrees with the recurrence's to a few float32 steps (measured
    3e-7 and 4e-7 of the largest) — with the pairs' sums taken as differences
    of running sums it read 4e-5 (PERF.md §6, PR 54). The kernel takes each sum
    over the positions it spans as a masked triangular product in VMEM."""
    q, k, v, _, beta, S0 = core_inputs(64, 1.0, d=d)
    line = jnp.concatenate([jnp.full((30,), -70.0), jnp.asarray([-0.25, -0.125, -0.5]), jnp.full((31,), -70.0)])
    g = jnp.broadcast_to(line[None, :, None], beta.shape).astype(jnp.float32)
    assert enters_the_kernel(q, k, v, g, beta) == (d == WIDE)
    probe = jnp.asarray(np.random.default_rng(9).normal(size=v.shape), jnp.float32)
    dg = lambda fn: jax.jit(jax.grad(lambda g: jnp.sum(fn(q, k, v, g, beta, S0)[0] * probe)))(g)
    with jax.default_matmul_precision("highest"):
        got, want = dg(lambda *a: gdn_chunked(*a[:5], initial_state=a[5])), dg(by_position)
    mild = slice(30, 33)
    assert float(jnp.max(jnp.abs(want[:, mild]))) > 1e-2
    assert float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want))) < 3e-6


def test_the_kernel_in_bfloat16_stays_in_the_band_of_the_jax_numpy_form():
    """bfloat16 q, k, v at the kernels' width: the kernel pair against the
    float32 recurrence and against ``_chunked`` on the same operands, outputs
    and the gradients of all five inputs, inside the band
    ``tests/test_kda.py::test_chunked_kda_in_bfloat16`` states for the
    per-channel rule (1e-2 absolute on outputs of order 0.3, 3e-2 of a
    gradient's largest entry)."""
    from torchft_tpu.ops import kda

    q, k, v, g, beta, _ = core_inputs(192, 3.0, d=WIDE)
    q, k, v, g, beta = (x[:1] for x in (q, k, v, g, beta))
    qb, kb, vb = (x.astype(jnp.bfloat16) for x in (q, k, v))
    assert enters_the_kernel(qb, kb, vb, g, beta) and not enters_the_kernel(qb, kb, v, g, beta)  # mixed dtypes keep jax.numpy

    def jax_numpy(q, k, v, g, beta):
        q, k = (jnp.repeat(x, 2, axis=2) for x in (q, k))
        return kda._chunked(q, k, v, g[..., None], beta, jnp.zeros((1, 4, WIDE, WIDE), jnp.float32), 64)

    scalar = lambda fn: lambda *a: (lambda o: (jnp.sum(jnp.sin(o.astype(jnp.float32))), o))(fn(*a)[0])  # noqa: E731
    run = lambda fn, *a: jax.jit(jax.grad(scalar(fn), argnums=(0, 1, 2, 3, 4), has_aux=True))(*a)
    got, o = run(gdn_chunked, qb, kb, vb, g, beta)
    theirs, o_theirs = run(jax_numpy, qb, kb, vb, g, beta)
    want, o_want = run(lambda *a: by_position(*a, None), *(x.astype(jnp.float32) for x in (qb, kb, vb)), g, beta)
    assert o.dtype == jnp.bfloat16 and got[0].dtype == got[1].dtype == jnp.bfloat16 and got[3].dtype == jnp.float32
    off = lambda a, b: float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))))
    assert 1e-5 < off(o, o_want) < 1e-2 and off(o, o_theirs) < 1e-2
    for mine, other, ref in zip(got, theirs, want):
        scale = float(jnp.max(jnp.abs(ref)))
        assert off(mine, ref) < 3e-2 * scale and off(mine, other) < 3e-2 * scale


@pytest.mark.parametrize("d", [8, WIDE], ids=["jax.numpy", "gdn_kernel"])
def test_the_core_carries_a_state_from_block_to_block(d):
    """192 positions at once, and as 128 then 64 with the first block's final
    state handed to the second (the mixer's scan over blocks)."""
    q, k, v, g, beta, _ = (x[:1] for x in core_inputs(192, 3.0, d=d))
    chunked = jax.jit(gdn_chunked)
    with jax.default_matmul_precision("highest"):
        whole, end = chunked(q, k, v, g, beta)
        first, mid = chunked(*(a[:, :128] for a in (q, k, v, g, beta)))
        second, end2 = chunked(*(a[:, 128:] for a in (q, k, v, g, beta)), initial_state=mid)
    np.testing.assert_allclose(jnp.concatenate([first, second], axis=1), whole, atol=2e-6)
    np.testing.assert_allclose(end2, end, atol=2e-6)


@pytest.mark.parametrize("hk, hv", [(1, 8), (2, 2), (1, 2)])
def test_the_kernel_at_other_groupings_of_value_heads_over_key_heads(hk, hv):
    """A key head whose eight value heads span two grid steps (its gradients
    summed after the kernel), as many key heads as value heads, and two value
    heads a step: outputs and q's and k's gradients against the recurrence."""
    inputs = tuple(x[:1] for x in core_inputs(64, 3.0, hk=hk, hv=hv, d=WIDE))
    assert enters_the_kernel(*inputs[:5])
    probe = jnp.asarray(np.random.default_rng(9).normal(size=inputs[2].shape), jnp.float32)
    grads = lambda fn: jax.jit(jax.grad(lambda *a: jnp.sum(fn(*a)[0] * probe), argnums=(0, 1, 3)))(*inputs)
    with jax.default_matmul_precision("highest"):
        got, want = grads(lambda *a: gdn_chunked(*a[:5], initial_state=a[5])), grads(by_position)
    assert max(grad_errors(got, want)) < 2e-5, grad_errors(got, want)


def test_the_value_heads_read_the_key_head_of_their_group():
    """Value heads 0 and 1 read key head 0, heads 2 and 3 key head 1: moving key head 1 moves the last two alone."""
    q, k, v, g, beta, S0 = core_inputs(64, 1.0)
    o = gdn_chunked(q, k, v, g, beta)[0]
    moved = gdn_chunked(q, k.at[:, :, 1].multiply(-1.0).at[:, :, 1, 0].add(0.3), v, g, beta)[0]
    change = np.asarray(jnp.max(jnp.abs(o - moved), axis=(0, 1, 3)))
    assert np.all(change[:2] == 0.0) and np.all(change[2:] > 1e-3)
    with pytest.raises(AssertionError, match="do not divide"):
        gdn_chunked(q, k, v[:, :, :3], g[..., :3], beta[..., :3])


# -- the second residual: ``gdn_bwd`` reads ``M^-1`` where it formed it again (PR 64) ------------------------------


def kernels_inputs(dtype, seq=256):
    """One sequence of two key heads under four value heads as ``gdn_forward`` takes them, a state, and both cotangents."""
    from torchft_tpu.ops.kda import _wide

    q, k, v, g, beta, state = (x[:1] for x in core_inputs(seq, 3.0, d=WIDE, seed=5))
    rng = np.random.default_rng(17)
    do, d_end = (jnp.asarray(rng.normal(size=x.shape), jnp.float32) for x in (v, state))
    return (_wide(q.astype(dtype)), _wide(k.astype(dtype)), _wide(v.astype(dtype)), g, beta), state, _wide(do.astype(dtype)), 0.1 * d_end


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
def test_the_backward_given_the_forwards_inverse_is_the_one_that_forms_it_again(dtype, monkeypatch):
    """``tests/test_kda.py``'s check of the per-channel pair, on ``gdn_forward`` /
    ``gdn_backward``: an inverse a VALUE head, four of them the one grid step's."""
    from tests.test_kda import the_backward_given_the_forwards_inverse_is_the_one_that_forms_it_again as check

    check(monkeypatch, "gdn", 4, *kernels_inputs(dtype))


@pytest.mark.parametrize("dtype, parents", [(jnp.float32, (84, 150)), (jnp.bfloat16, (176, 262))], ids=["float32", "bfloat16"])
def test_the_backward_kernels_body_holds_the_inverses_products_no_more(dtype, parents, monkeypatch):
    from tests.test_kda import the_backward_kernels_body_holds_the_inverses_products_no_more as check

    check(monkeypatch, "gdn", 4, parents, *kernels_inputs(dtype, seq=128))


# -- the program against the reference ------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def reference_of(size):
    """The reference's loss and gradients at one of SIZES, jitted once: the seeds share its trace."""
    return jax.jit(jax.value_and_grad(lambda p, t: ref.loss(p, t, SIZES[size])))


@pytest.mark.parametrize("seed", [3, 4, 5])
@pytest.mark.parametrize("size", list(SIZES))
def test_loss_and_every_gradient_leaf_agree_with_the_reference(size, seed):
    cfg, params, tokens, sizes = make(size, remat=True, seed=seed, seq=72)
    got, g_got = system(cfg, params, tokens)
    want, g_want = reference_of(size)(params, tokens)
    assert float(got) == pytest.approx(float(want), rel=2e-6)
    errs = grad_errors(g_got, g_want)
    assert max(jax.tree_util.tree_leaves(errs)) < RTOL, errs


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


def test_the_reference_finds_each_layers_parameters_in_the_programs_tree():
    _, params, _, sizes = make("stack")
    layers = ref.layer_params(params, sizes)
    assert [("w_ba" in w, "wq" in w and "w_ba" not in w, "shared_scale" in w) for w in layers] == [(True, False, True)] * 3 + [(False, True, True)]
    np.testing.assert_array_equal(layers[1]["a_log"], params["periods"]["gdn.experts"]["a_log"][0, 1])
    np.testing.assert_array_equal(layers[3]["q_norm"], params["periods"]["full.experts"]["q_norm"][0, 0])


# -- the two mixers by themselves ---------------------------------------------------------------


def gdn_leaves(cfg, key=0):
    lp = jax.tree_util.tree_map(lambda a: a[0, 0], init_params(jax.random.PRNGKey(key), cfg)["layers"])
    return dict(lp, o_norm=lp["o_norm"] + 0.3 * jnp.sin(jnp.arange(8.0)))


@pytest.mark.parametrize("dtype, tol, grad_tol", [(jnp.float32, 2e-5, 1e-4), (jnp.bfloat16, 6e-2, 0.25)])
@pytest.mark.parametrize("block", [16, 1024])  # three blocks that hand the state and the taps on; one block
def test_the_gdn_mixer_and_its_gradients_against_the_reference(block, dtype, tol, grad_tol, monkeypatch):
    """``_mix_gdn`` against the reference's mixer, output and the gradient of
    every leaf and of the input, with the sequence in one block and in three
    (the carried state and the convolution's last taps): float32 to the order
    of the sums, bfloat16 to a few percent of the output's largest entry and to
    a fifth of a gradient leaf's (the key's taps, behind the L2 norm of eight
    lanes rounded to eight bits: 0.19)."""
    monkeypatch.setattr(T, "_KDA_BLOCK", block)
    sizes = SIZES["gdn.dense"]
    cfg = TransformerConfig(dtype=jnp.float32, **sizes)
    lp = gdn_leaves(cfg)
    h = jax.random.normal(jax.random.PRNGKey(5), (2, 48, 32))
    probe = jax.random.normal(jax.random.PRNGKey(6), (2, 48, 32))
    f32 = T._F32_LEAVES
    cast = lambda tree: {k: a if k in f32 else a.astype(dtype) for k, a in tree.items()}

    def mine(lp, h):
        y, said = T._mix_gdn(cfg, cast(lp), h.astype(dtype))
        return jnp.sum(y.astype(jnp.float32) * probe), (y.astype(jnp.float32), said)

    def theirs(lp, h):
        y = ref._gdn(lp, h, sizes, 1e-6)
        return jnp.sum(y * probe), (y, None)

    with jax.default_matmul_precision("highest"):
        (g_got, (y_got, said)), (g_want, (y_want, _)) = (
            jax.jit(lambda lp, h, f=f: (jax.grad(f, argnums=(0, 1), has_aux=True)(lp, h)))(lp, h) for f in (mine, theirs)
        )
    assert float(jnp.max(jnp.abs(y_got - y_want)) / jnp.max(jnp.abs(y_want))) < tol
    assert max(jax.tree_util.tree_leaves(grad_errors(g_got, g_want))) < grad_tol
    ba = h @ lp["w_ba"]
    np.testing.assert_allclose(said["decay_min"], jnp.min(ref._decay(lp, ba[..., 4:])), rtol=2e-2 if dtype == jnp.bfloat16 else 1e-5)
    np.testing.assert_allclose(said["beta_mean"], jnp.mean(jax.nn.sigmoid(ba[..., :4])), rtol=2e-2 if dtype == jnp.bfloat16 else 1e-5)


def parents_mix_gdn(cfg, lp, h):
    """``transformer._mix_gdn`` as 080a434 had it: q | k | v and ``h·w_z`` inside the checkpointed block."""
    b, s, d = h.shape
    heads, key_heads, hd, taps = cfg.linear_n_heads, cfg.linear_key_heads, cfg.linear_head_dim, cfg.conv_kernel
    kch, vch = key_heads * hd, heads * hd
    f32 = jnp.float32
    blk = T._KDA_BLOCK if s % T._KDA_BLOCK == 0 else s

    def block(carry, hb):
        state, before = carry
        qkv = jnp.concatenate([hb @ lp["wq"], hb @ lp["wk"], hb @ lp["wv"]], axis=-1)
        filters = jnp.concatenate([lp["conv_q"], lp["conv_k"], lp["conv_v"]], axis=-1)
        mixed = jax.nn.silu(T.short_conv(qkv, filters, before))
        q = mixed[..., :kch].reshape(b, blk, key_heads, hd)
        k = mixed[..., kch : 2 * kch].reshape(b, blk, key_heads, hd)
        v = mixed[..., 2 * kch :].reshape(b, blk, heads, hd)
        ba = jnp.dot(hb, lp["w_ba"], preferred_element_type=f32)
        beta = jax.nn.sigmoid(ba[..., :heads])
        g = -jnp.exp(lp["a_log"].astype(f32)) * jax.nn.softplus(ba[..., heads:] + lp["dt_bias"].astype(f32))
        out_gate = jax.nn.silu(jnp.dot(hb, lp["w_z"], preferred_element_type=f32)).astype(hb.dtype).reshape(b, blk, heads, hd)
        q = (T._unit_l2(q) * hd**-0.5).astype(v.dtype)
        k = T._unit_l2(k).astype(v.dtype)
        o, state = gdn_chunked(q, k, v, g, beta, initial_state=state)
        o = rms_norm(o, lp["o_norm"], cfg.norm_eps) * out_gate
        return (state, qkv[:, blk - (taps - 1) :]), (o.reshape(b, blk, vch) @ lp["wo"], jnp.min(g), jnp.mean(beta))

    start = (jnp.zeros((b, heads, hd, hd), f32), jnp.zeros((b, taps - 1, 2 * kch + vch), h.dtype))
    blocks = jnp.moveaxis(h.reshape(b, s // blk, blk, d), 1, 0)
    _, (out, decay_min, beta_mean) = jax.lax.scan(jax.checkpoint(block), start, blocks)
    return jnp.moveaxis(out, 0, 1).reshape(b, s, d), {"decay_min": jnp.min(decay_min), "beta_mean": jnp.mean(beta_mean)}


# d 48 -> q, k 16 (2 key heads of 8), v and z 32 (4 value heads), [b | a] 8: each product's width its own
MIXER = dict(SIZES["gdn.dense"], d_model=48, n_layers=1, gdn_layers=(1,))


@pytest.mark.parametrize("seq", [32, 40], ids=["two-blocks", "not-a-multiple"])
def test_the_gdn_mixer_with_its_projections_ahead_of_the_scan_is_the_parents(seq, monkeypatch):
    """q, k, v and ``h·w_z`` taken once over the whole sequence and handed to
    the blocks as ``xs`` are the numbers the parent's block computed for
    itself: output, what the mixer says of its decays and write strengths, and
    the gradient to the input and every leaf, at two blocks and at a length
    that is one block because it is no multiple — to float32's rounding, as
    ``tests/test_kda.py`` has it for ``_mix_kda``."""
    from tests.test_kda import largest_difference, mixer_leaves, value_and_gradients

    monkeypatch.setattr(T, "_KDA_BLOCK", 16)
    cfg = TransformerConfig(dtype=jnp.float32, **MIXER)
    lp = mixer_leaves(cfg)
    h, probe = (jax.random.normal(jax.random.PRNGKey(i), (2, seq, 48)) for i in (5, 6))
    (y, said), grads = value_and_gradients(lambda lp, h: T._mix_gdn(cfg, lp, h), lp, h, probe)
    (y_parent, said_parent), grads_parent = value_and_gradients(lambda lp, h: parents_mix_gdn(cfg, lp, h), lp, h, probe)
    assert set(grads[0]) >= {"wq", "wk", "wv", "w_z", "w_ba", "wo", "conv_q", "conv_k", "conv_v", "a_log", "dt_bias", "o_norm"}
    assert largest_difference(y, y_parent) < 2e-6
    assert largest_difference(grads, grads_parent) < 1e-5
    assert largest_difference(said, said_parent) < 1e-6 and set(said) == {"decay_min", "beta_mean"}


def projections_of_the_input(jaxpr, d, widths, in_scan=False, out=None):
    """(inside a ``lax.scan``'s body or not, the output's shape) of every
    ``dot_general`` anywhere in ``jaxpr`` that contracts an activation's ``d``
    features into one of ``widths``: a projection of the layer's input, as
    against a weight's gradient (which contracts positions), the input's
    (which contracts the width) or a product of another rank."""
    from tests.test_window_gqa import sub_jaxprs

    out = [] if out is None else out
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            lhs, rhs = (v.aval.shape for v in eqn.invars)
            (contract_l, contract_r), _ = eqn.params["dimension_numbers"]
            shape = eqn.outvars[0].aval.shape
            if (
                len(lhs) >= 3 and contract_l == (len(lhs) - 1,) and lhs[-1] == d and rhs == (d, shape[-1])
                and contract_r == (0,) and shape[-1] in widths
            ):
                out.append((in_scan, shape))
        for sub in sub_jaxprs(eqn):
            projections_of_the_input(sub, d, widths, in_scan or eqn.primitive.name == "scan", out)
    return out


@pytest.mark.parametrize("mixer", ["kda", "gdn"])
def test_the_projections_stand_once_at_the_full_sequence_and_in_neither_scan_body(mixer, monkeypatch):
    """The traced value and gradient of a linear mixer at four blocks: each of
    q, k, v (and Gated DeltaNet's z) is ONE product of the whole sequence, on
    the block-major view the scan takes, and no such product stands in the
    body of the forward scan or of the backward's (where the block's checkpoint
    runs the block again): the backward no longer pays them a second time. The
    parent's program, by the same reading, holds each twice and inside."""
    from tests import test_kda

    monkeypatch.setattr(T, "_KDA_BLOCK", 16)
    if mixer == "kda":
        cfg = TransformerConfig(dtype=jnp.float32, n_layers=1, kda_layers=(1,), **test_kda.MIXER)
        mine, parents, widths = T._mix_kda, test_kda.parents_mix_kda, [32, 32, 32]
    else:
        cfg = TransformerConfig(dtype=jnp.float32, **MIXER)
        mine, parents, widths = (lambda *a: T._mix_gdn(*a)[0]), (lambda *a: parents_mix_gdn(*a)[0]), [16, 16, 32, 32]
    lp = test_kda.mixer_leaves(cfg)
    h = jnp.zeros((2, 64, 48))
    found = {
        name: projections_of_the_input(
            jax.make_jaxpr(jax.value_and_grad(lambda lp, h: jnp.sum(mix(cfg, lp, h)), argnums=(0, 1)))(lp, h).jaxpr, 48, set(widths)
        )
        for name, mix in (("mine", mine), ("parents", parents))
    }
    assert sorted(found["mine"]) == sorted((False, (4, 2, 16, w)) for w in widths)
    assert sorted(found["parents"]) == sorted((True, (2, 16, w)) for w in widths * 2)


def test_the_gdn_stack_is_causal_and_a_head_has_one_decay():
    cfg, params, tokens, _ = make("gdn.dense")
    hidden = jax.jit(lambda t: T._hidden_states(params, t, cfg)[0])
    t = 17
    changed = tokens.at[:, t].set((tokens[:, t] + 1) % 64)
    moved = np.asarray(jnp.max(jnp.abs(hidden(tokens) - hidden(changed)), axis=(0, 2)))
    assert np.all(moved[:t] == 0.0) and moved[t] > 1e-3 and moved[t + 1] > 0.0  # the taps and the state carry it on
    leaves = params["layers"]
    assert leaves["a_log"].shape == leaves["dt_bias"].shape == (1, 2, 4) and leaves["w_ba"].shape == (1, 2, 32, 8)
    assert leaves["wq"].shape == leaves["wk"].shape == (1, 2, 32, 16) and leaves["wv"].shape == leaves["w_z"].shape == (1, 2, 32, 32)
    assert bool(jnp.all(leaves["dt_bias"] == 1.0)) and bool(jnp.all(jnp.exp(leaves["a_log"]) <= 16.0))


def attention_leaves(cfg, key=0):
    lp = jax.tree_util.tree_map(lambda a: a[0, 0], init_params(jax.random.PRNGKey(key), cfg)["layers"])
    wave = lambda phase: 0.3 * jnp.sin(jnp.arange(16.0) + phase)
    return dict(lp, q_norm=wave(0.0), k_norm=wave(1.0))


def test_the_gated_softmax_layer_against_the_reference_and_what_it_is_not():
    """``_mix_full`` under ``attn_output_gate`` is the reference's attention —
    a head's lanes ``[q | gate]``, q and k normed head by head under ``1 + w``,
    4 of 16 lanes rotated, the core's output through ``sigmoid(gate)`` — and is
    NOT the layer with the gate dropped, with the query's and the gate's lanes
    taken as two halves of the projection, with a plain-weight norm, or with
    the whole head rotated."""
    sizes = SIZES["gated.dense"]
    cfg = TransformerConfig(dtype=jnp.float32, **sizes)
    lp = attention_leaves(cfg)
    assert lp["wq"].shape == (32, 2 * 4 * 16) and lp["wo"].shape == (4 * 16, 32)
    h = jax.random.normal(jax.random.PRNGKey(9), (2, 24, 32))
    with jax.default_matmul_precision("highest"):
        got = T._mix_full(cfg, None, False, lp, h)
        want = ref._attention(lp, h, sizes, 1e-6)
        halves = jnp.concatenate([lp["wq"].reshape(32, 4, 2, 16)[:, :, 0].reshape(32, 64), lp["wq"].reshape(32, 4, 2, 16)[:, :, 1].reshape(32, 64)], axis=1)
        others = {
            "no gate": T._mix_full(dataclasses.replace(cfg, attn_output_gate=False), None, False, dict(lp, wq=halves[:, :64]), h),
            "q | gate as halves": T._mix_full(cfg, None, False, dict(lp, wq=halves), h),
            "plain norm": T._mix_full(dataclasses.replace(cfg, norm_zero_centered=False), None, False, lp, h),
            "whole head rotated": T._mix_full(dataclasses.replace(cfg, rotary_dim=0), None, False, lp, h),
        }
    scale = float(jnp.max(jnp.abs(want)))
    assert float(jnp.max(jnp.abs(got - want))) < 1e-5 * scale
    for name, other in others.items():
        assert float(jnp.max(jnp.abs(got - other))) > 1e-2 * scale, name


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_the_zero_centred_norm_scales_by_one_plus_its_weight(dtype):
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 64)).astype(dtype)
    w = 0.002 * jnp.cos(jnp.arange(64.0))  # under bfloat16's step at 1: 1 + w is 1 there
    got = rms_norm(x, w.astype(dtype), 1e-6, zero_centered=True).astype(jnp.float32)
    xf = x.astype(jnp.float32)
    want = xf / jnp.sqrt(jnp.mean(xf * xf, -1, keepdims=True) + 1e-6) * (1.0 + w.astype(dtype).astype(jnp.float32))
    np.testing.assert_allclose(got, want, rtol=1e-6 if dtype == jnp.float32 else 4e-3)
    plain = rms_norm(x, jnp.ones(64, dtype), 1e-6).astype(jnp.float32)
    assert float(jnp.max(jnp.abs(got - plain))) > 0  # the small weight is there, in bfloat16 too
    np.testing.assert_array_equal(rms_norm(x, jnp.zeros(64, dtype), 1e-6, zero_centered=True), rms_norm(x, jnp.ones(64, dtype), 1e-6))
    zc = init_params(jax.random.PRNGKey(0), TransformerConfig(dtype=jnp.float32, **SIZES["stack"]))
    assert float(jnp.max(jnp.abs(zc["final_norm"]))) == 0.0 and float(jnp.max(jnp.abs(zc["periods"]["full.experts"]["q_norm"]))) == 0.0
    assert bool(jnp.all(zc["periods"]["gdn.experts"]["o_norm"] == 1.0)) and float(jnp.max(jnp.abs(zc["periods"]["gdn.experts"]["ln2"]))) == 0.0


# -- the gate, the shared expert and the share ----------------------------------------------------


def expert_leaves(d=32, e=16, f=16, key=0):
    keys = jax.random.split(jax.random.PRNGKey(key), 8)
    n = lambda k, *shape, fan: jax.random.normal(k, shape) * fan**-0.5
    return {
        "router": 3.0 * n(keys[0], d, e, fan=d),
        "w_gate": n(keys[1], e, d, f, fan=d), "w_in": n(keys[2], e, d, f, fan=d), "w_out": n(keys[3], e, f, d, fan=f),
        "shared_gate": n(keys[4], d, f, fan=d), "shared_in": n(keys[5], d, f, fan=d), "shared_out": n(keys[6], f, d, fan=f),
        "shared_scale": n(keys[7], d, 1, fan=d),
    }


def share_of(whole, share, held=4):
    return {k: (v[held * share : held * share + held] if k in ("w_gate", "w_in", "w_out") else v) for k, v in whole.items()}


def test_the_four_shares_of_an_expert_layer_add_up_to_the_uncut_one():
    """Four chips, four of sixteen experts each: the four shares' routed parts,
    with the gated shared expert that every share computes alike counted ONCE,
    add up to the uncut 16-expert reference, and every token-expert row is
    computed on exactly one share."""
    whole = expert_leaves()
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 48, 32))
    with jax.default_matmul_precision("highest"):
        want = ref._experts(whole, x, dict(STACK, n_experts_held=0, expert_share_index=0))
        shared = ref._shared_gate(whole, x) * ref._swiglu(x, whole["shared_gate"], whole["shared_in"], whole["shared_out"])
        parts, rows = [], []
        for share in range(4):
            cfg = TransformerConfig(dtype=jnp.float32, **dict(STACK, expert_share_index=share))
            lp = share_of(whole, share)
            y, (_, counts, held, _, gate) = jax.jit(lambda lp, x, cfg=cfg: T._ffn_moe(lp, x, cfg))(lp, x)
            np.testing.assert_allclose(y, ref._experts(lp, x, dict(STACK, expert_share_index=share)), atol=5e-5)
            np.testing.assert_allclose(gate, jnp.mean(ref._shared_gate(whole, x)), rtol=1e-5)
            parts.append(y - shared)
            rows.append(int(held))
            assert int(jnp.sum(counts)) == 2 * 48 * 4  # the router counts over all 16, on every share
    np.testing.assert_allclose(sum(parts) + shared, want, atol=5e-5)
    assert sum(rows) == 2 * 48 * 4 and len(set(rows)) > 1


@pytest.mark.parametrize("kind", [("gdn", "experts"), ("full", "experts")])
def test_the_four_shares_of_a_whole_layer_add_up_to_the_uncut_layer(kind):
    """The share test of the guide's §4 on a whole layer of each kind: what
    every share computes — the residual, the mixer, the gated shared expert —
    counted once, plus the four shares' routed parts, is the uncut reference's
    layer."""
    uncut = dict(STACK, n_experts_held=0, expert_share_index=0)
    cfg0 = TransformerConfig(dtype=jnp.float32, **STACK)
    key = "gdn.experts" if kind[0] == "gdn" else "full.experts"
    lp = off_their_defaults(jax.tree_util.tree_map(lambda a: a[0, 0], init_params(jax.random.PRNGKey(1), cfg0)["periods"][key]))
    whole = dict(lp, **{k: v for k, v in expert_leaves(key=3).items() if k in ("router", "w_gate", "w_in", "w_out")})
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 48, 32))
    eps = 1e-6
    with jax.default_matmul_precision("highest"):
        want = ref._layer(whole, x, uncut, kind)
        outs = []
        for share in range(4):
            cfg = TransformerConfig(dtype=jnp.float32, **dict(STACK, expert_share_index=share))
            outs.append(jax.jit(lambda lp, x, cfg=cfg: T._make_layer_fn(cfg, None, kind=kind)(x, lp)[0])(share_of(whole, share), x))
        # what every share computes alike, from the reference: the residual, the mixer, the gated shared expert
        mixer = ref._gdn if kind[0] == "gdn" else ref._attention
        mixed = x + mixer(whole, ref._rms_norm_zc(x, whole["ln1"], eps), uncut, eps)
        h = ref._rms_norm_zc(mixed, whole["ln2"], eps)
        alike = mixed + ref._shared_gate(whole, h) * ref._swiglu(h, whole["shared_gate"], whole["shared_in"], whole["shared_out"])
    np.testing.assert_allclose(sum(out - alike for out in outs) + alike, want, atol=1e-4)
    assert float(jnp.max(jnp.abs(outs[0] - outs[1]))) > 1e-3  # the shares differ: each holds other experts


def test_the_softmax_gate_renormalised_sums_to_one_and_is_todays_with_the_flag_off():
    lp = expert_leaves()
    tokens = jax.random.normal(jax.random.PRNGKey(4), (96, 32))
    off = TransformerConfig(dtype=jnp.float32, **dict(STACK, router_renormalize=False))
    on = TransformerConfig(dtype=jnp.float32, **STACK)
    w_off, idx_off, p_off = T._route(lp, tokens, off)
    w_on, idx_on, p_on = T._route(lp, tokens, on)
    probs = jax.nn.softmax(tokens @ lp["router"], axis=-1)
    top, idx = jax.lax.top_k(probs, 4)
    # the flag off: the chosen probabilities as they are, what the softmax gate always gave (OLMoE's program)
    np.testing.assert_array_equal(w_off, top)
    np.testing.assert_array_equal(idx_off, idx)
    np.testing.assert_array_equal(idx_on, idx)
    np.testing.assert_array_equal(p_on, p_off)
    assert float(jnp.min(jnp.sum(w_off, axis=-1))) < 0.9
    np.testing.assert_allclose(jnp.sum(w_on, axis=-1), 1.0, rtol=1e-6)
    np.testing.assert_allclose(w_on, top / jnp.sum(top, axis=-1, keepdims=True), rtol=1e-6)
    mine = jnp.zeros_like(probs).at[jnp.arange(96)[:, None], idx_on].set(w_on)
    np.testing.assert_allclose(mine, ref._weigh(probs, probs >= top[:, -1:]), rtol=1e-6)
    assert TransformerConfig().router_renormalize is False


def test_the_shared_experts_gate_scales_it_and_trains():
    cfg = TransformerConfig(dtype=jnp.float32, **STACK)
    lp = share_of(expert_leaves(), 1)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 24, 32))
    bare = dataclasses.replace(cfg, shared_expert_gate=False)
    with jax.default_matmul_precision("highest"):
        gated, said = T._ffn_moe(lp, x, cfg)
        plain, said_bare = T._ffn_moe({k: v for k, v in lp.items() if k != "shared_scale"}, x, bare)
        shared = ref._swiglu(x, lp["shared_gate"], lp["shared_in"], lp["shared_out"])
        np.testing.assert_allclose(plain - gated, (1.0 - ref._shared_gate(lp, x)) * shared, atol=2e-5)
    assert len(said) == 5 and len(said_bare) == 4 and 0.3 < float(said[4]) < 0.7
    grads = jax.grad(lambda lp: jnp.sum(T._ffn_moe(lp, x, cfg)[0] ** 2))(lp)
    assert grads["shared_scale"].shape == (32, 1) and float(jnp.max(jnp.abs(grads["shared_scale"]))) > 0


# -- the pattern, what it says and what it refuses ---------------------------------------------


def test_layer_kinds_and_pattern_of_the_published_model_and_of_the_cut():
    import json

    with open(os.path.join(ROOT, "benchmark", "published", "qwen3-next-80b-a3b-instruct.json")) as f:
        published = json.load(f)["config"]
    n, every = published["num_hidden_layers"], published["full_attention_interval"]
    assert (n, every, published["decoder_sparse_step"], published["mlp_only_layers"]) == (48, 4, 1, [])
    gdn = tuple(i for i in range(1, n + 1) if i % every)
    whole = TransformerConfig(**dict(STACK, n_layers=n, gdn_layers=gdn))
    period = (("gdn", "experts"),) * 3 + (("full", "experts"),)
    assert whole.layer_kinds() == period * 12 and layer_pattern(whole) == (0, period)
    with open(os.path.join(ROOT, "benchmark", "configs", "qwen3-next-80b-a3b-1g.json")) as f:
        tc = json.load(f)["program"]["transformer_config"]
    cut = TransformerConfig(**{**tc, "dtype": jnp.dtype(tc["dtype"])})
    assert cut.layer_kinds() == period and layer_pattern(cut) == (0, period)  # one whole period, scanned once
    assert (cut.linear_n_heads, cut.linear_key_heads, cut.linear_head_dim, cut.head_dim, cut.rotary_dim) == (32, 16, 128, 256, 64)
    assert cut.rotary_dim == published["partial_rotary_factor"] * published["head_dim"]
    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cut))
    assert set(params["periods"]) == {"gdn.experts", "full.experts"} and params["lead"] == {}
    assert params["periods"]["gdn.experts"]["w_ba"].shape == (1, 3, 2048, 64) and params["periods"]["full.experts"]["wq"].shape == (1, 1, 2048, 8192)
    assert sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(params)) == 625_667_136


def test_the_events_say_the_pattern_and_the_core_the_softmax_layer_took(monkeypatch):
    from torchft_tpu import telemetry

    monkeypatch.setattr(T, "_PATHS_SAID", set())
    cfg, params, tokens, _ = make("stack", seq=32)
    seen = {kind: len(telemetry.EVENTS.recent(kind)) for kind in ("attention_path", "layer_pattern", "gdn_core_path")}
    for _ in range(2):
        jax.jit(lambda p: loss_fn(p, tokens, cfg, None))(params)
    (path,) = telemetry.EVENTS.recent("attention_path")[seen["attention_path"]:]  # the one softmax layer, once
    assert (path["n_heads"], path["n_kv_heads"], path["head_dim"], path["rotary_dim"], path["impl"]) == (4, 2, 16, 4, "plain")
    (pattern,) = telemetry.EVENTS.recent("layer_pattern")[seen["layer_pattern"]:]
    assert (pattern["lead"], pattern["period"], pattern["repeats"]) == ("-", "gdn.experts,gdn.experts,gdn.experts,full.experts", 1)
    assert (pattern["experts_held"], pattern["experts"]) == (4, 16)
    (core,) = telemetry.EVENTS.recent("gdn_core_path")[seen["gdn_core_path"]:]  # three layers of one shape, once: heads 8 wide
    assert (core["core"], core["heads"], core["key_heads"], core["head_dim"], core["chunk"], core["batch"], core["block"]) == ("jax.numpy", 4, 2, 8, 64, 2, 32)


def test_heads_of_256_take_the_kernel_on_a_chip_at_the_cells_length():
    """The cell's softmax layer (16 x 256 over 2 x 256, 8 192 positions): on a
    TPU ``auto`` takes the Pallas kernel at 512 x 512 tiles (256 lanes are two
    whole lane tiles; PERF.md §6, PR 54 holds the readings), grouped heads read
    in place; on a CPU and inside a manual region the chunked scan keeps it."""
    import json
    from unittest import mock

    with open(os.path.join(ROOT, "benchmark", "configs", "qwen3-next-80b-a3b-1g.json")) as f:
        tc = json.load(f)["program"]["transformer_config"]
    assert tc["attention_impl"] == "auto" and tc["head_dim"] == 256
    cfg = TransformerConfig(**{**tc, "dtype": jnp.dtype(tc["dtype"])})
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        impl, why, blocks = T._attention_path(cfg, 8192, 2, None, grouped=True)
        assert (impl, blocks) == ("flash", (512, 512)) and why == "auto on a tpu: the fastest core measured at this (seq, head_dim)"
        assert T._attention_path(cfg, 8192, 2, None, sp_manual=True, grouped=True)[0] == "chunked"
    assert T._attention_path(cfg, 8192, 2, None, grouped=True)[::2] == ("chunked", None)


@pytest.mark.parametrize("changes, message", [
    (dict(gdn_layers=(1, 2, 5)), "gdn_layers .* name layers 1..4, each at most once"),
    (dict(gdn_layers=(1, 2, 3), kda_layers=(3,)), "each at most once"),
    (dict(linear_n_key_heads=3), "linear_n_key_heads=3 under linear_n_heads=4: the value heads of gdn_layers divide"),
    (dict(gdn_layers=(), kda_layers=(1, 2, 3)), "linear_n_key_heads=2 .*a kda layer has one count"),
    (dict(n_shared_experts=0), "shared_expert_gate scales the shared experts' output"),
    (dict(rotary_dim=4, rope_pairing="interleaved"), "a partial rotation"),
])
def test_what_the_configuration_refuses(changes, message):
    with pytest.raises(ValueError, match=message):
        TransformerConfig(**dict(STACK, **changes))


@pytest.mark.parametrize("size, message", [
    ("gdn.dense", "sp=2 with a gdn layer: the recurrent state at a sequence shard's start .* is missing"),
    ("gated.dense", "sp=2 with a window .* or grouped-query heads: ring attention .* missing"),
])
def test_a_sharded_sequence_is_refused_with_what_is_missing(size, message):
    cfg, params, tokens, _ = make(size, seq=32)
    sp = make_mesh(MeshConfig(sp=2), devices=jax.devices()[:2])
    with pytest.raises(ValueError, match=message), jax.set_mesh(sp):
        jax.jit(lambda p, t: loss_fn(p, t, cfg, sp))(params, tokens)


def test_a_pattern_of_these_layers_is_refused_under_a_pipeline_and_experts_over_chips():
    cfg, params, tokens, _ = make("stack", seq=32)
    with pytest.raises(ValueError, match="pp=2 with a declared layer pattern: .* are missing"):
        loss_fn(params, tokens, dataclasses.replace(cfg, pp=2))
    ep = make_mesh(MeshConfig(ep=2), devices=jax.devices()[:2])
    with pytest.raises(ValueError, match="ep=2"), jax.set_mesh(ep):
        jax.jit(lambda p, t: loss_fn(p, t, cfg, ep))(params, tokens)
