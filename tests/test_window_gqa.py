"""Grouped-query heads, a band among global layers and a rotation per kind of
layer through the one transformer — against the plain reference
(``benchmark/reference/laguna_decoder.py``, loaded by path: one copy), and the
three cores (plain, chunked, the Pallas kernel in interpret mode) against one
another at the band's edges.

Tolerance of the float32 comparisons with the reference: both sides compute in
float32 on the CPU (matmuls at "highest"); what differs is the order of the
sums — a group's heads repeated against a grouped einsum, rows sorted by expert
and a grouped matmul against every held expert over every token. Measured here:
the loss to 2e-7, gradient leaves to 1.6e-6 of their largest entry. RTOL is far
above that and far below what a term left out moves (the last test of the first
section shows each).
"""

import dataclasses
import hashlib
import importlib.util
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from torchft_tpu.models import transformer as T
from torchft_tpu.models.transformer import TransformerConfig, init_params, layer_pattern, loss_fn
from torchft_tpu.ops.attention import attention, chunked_attention
from torchft_tpu.ops.layers import rotary_embed, yarn_inv_freq
from torchft_tpu.ops.pallas.flash_attention import flash_attention
from torchft_tpu.parallel.mesh import MeshConfig, make_mesh
from torchft_tpu.parallel.train_step import TrainStep

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RTOL = 5e-5


def _load_reference():
    path = os.path.join(ROOT, "benchmark", "reference", "laguna_decoder.py")
    spec = importlib.util.spec_from_file_location("laguna_decoder_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load_reference()

ATTENTION = dict(
    vocab_size=64, d_model=32, n_heads=6, n_kv_heads=2, head_dim=16, d_ff=64, norm_eps=1e-6,
    rope_pairing="half", rotary_dim=8, rope_theta=500000.0, window_rope_theta=10000.0,
    yarn_factor=64.0, yarn_original_max=64, yarn_beta_fast=8.0, yarn_beta_slow=1.0,
    yarn_attention_factor=1.4158883083359672,
)
EXPERTS = dict(
    moe_d_ff=16, n_experts=16, n_experts_held=4, top_k=4, n_shared_experts=1, router_gate="sigmoid",
    router_renormalize=True, routed_scaling_factor=2.5, router_selection_bias=False,
)
# the cell's stack at tiny widths: a dense global layer, three window layers of 8 heads, a global one of 6, over 2
# key/value heads; the published list of heads runs past the layers kept, and each kept layer reads its own entry
STACK = dict(
    ATTENTION, **EXPERTS, n_layers=5, n_dense_layers=1, window=8, window_layers=(2, 3, 4),
    n_heads_per_layer=(6, 8, 8, 8, 6, 8, 8, 8), expert_share_index=1,
)
SIZES = {
    "global.dense": dict(ATTENTION, n_layers=2),
    "window.dense": dict(ATTENTION, n_layers=2, window=8, window_layers=(1, 2), n_heads_per_layer=(8, 8)),
    "window.experts": dict(ATTENTION, **EXPERTS, n_layers=1, window=8, window_layers=(1,)),
    "grouped_only": dict(vocab_size=64, d_model=32, n_heads=4, n_kv_heads=1, head_dim=16, d_ff=64, n_layers=2, rope_pairing="half", rope_theta=10000.0),
    "stack": STACK,
}


def off_their_defaults(params):
    """Norm weights off 1 and a router with loads that differ: or a norm
    without its weight would pass."""
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


def make(size, seq=40):
    sizes = dict(SIZES[size])
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


# -- the program against the reference ------------------------------------------------


@pytest.mark.parametrize("size", list(SIZES))
def test_loss_and_every_gradient_leaf_agree_with_the_reference(size):
    cfg, params, tokens, sizes = make(size)
    got, g_got = system(cfg, params, tokens)
    want, g_want = jax.jit(jax.value_and_grad(lambda p, t: ref.loss(p, t, sizes)))(params, tokens)
    assert float(got) == pytest.approx(float(want), rel=2e-6)
    errs = grad_errors(g_got, g_want)
    assert max(jax.tree_util.tree_leaves(errs)) < RTOL, errs


@pytest.mark.parametrize("impl", ["plain", "chunked", "flash"])
def test_the_stack_through_each_core(impl):
    """s128: the chunk divides it and the kernel's tile (clamped to 128) is
    the sequence; the band of 8 is then far inside one tile."""
    cfg, params, tokens, sizes = make("stack", seq=128)
    got, g_got = system(dataclasses.replace(cfg, attention_impl=impl), params, tokens)
    want, g_want = jax.jit(jax.value_and_grad(lambda p, t: ref.loss(p, t, sizes)))(params, tokens)
    assert float(got) == pytest.approx(float(want), rel=2e-6)
    assert max(jax.tree_util.tree_leaves(grad_errors(g_got, g_want))) < RTOL


def test_per_sequence_loss_is_what_the_worker_compares():
    cfg, params, tokens, sizes = make("stack")
    with jax.default_matmul_precision("highest"):
        per = ref.per_sequence_loss(params, tokens, sizes)
        mine = [float(loss_fn(params, tokens[i : i + 1], cfg)) for i in range(2)]
    np.testing.assert_allclose(per, mine, rtol=2e-6)


def test_the_stack_is_one_period_grouped_by_kind_with_each_kinds_own_heads():
    cfg, params, _, _ = make("stack")
    kinds = (("full", "dense"),) + (("window", "experts"),) * 3 + (("full", "experts"),)
    assert cfg.layer_kinds() == kinds and layer_pattern(cfg) == (0, kinds)
    groups = params["periods"]
    assert set(groups) == {"full.dense", "window.experts", "full.experts"} and params["lead"] == {}
    assert groups["window.experts"]["wq"].shape == (1, 3, 32, 8 * 16)
    assert groups["full.experts"]["wq"].shape == groups["full.dense"]["wq"].shape == (1, 1, 32, 6 * 16)
    for group in groups.values():  # two key/value heads whatever the kind
        assert group["wk"].shape[-2:] == group["wv"].shape[-2:] == (32, 2 * 16)
        assert "router_bias" not in group
    assert (cfg.mixer_heads("window"), cfg.mixer_heads("full"), cfg.kv_heads) == (8, 6, 2)


@pytest.mark.parametrize("left_out", ["band", "band_off_by_one", "group_map", "yarn", "partial", "attention_factor", "window_base"])
def test_the_check_would_see_a_term_left_out(left_out):
    """Each of what makes the architecture different, changed on the
    reference's side alone, moves the loss by far more than the comparison
    allows (2e-6 of it): a band one position wider included."""
    cfg, params, tokens, sizes = make("stack")
    wrong = {
        "band": dict(sizes, window=4096), "band_off_by_one": dict(sizes, window=9),
        "yarn": dict(sizes, yarn_factor=0.0), "partial": dict(sizes, rotary_dim=16),
        "attention_factor": dict(sizes, yarn_attention_factor=1.0), "window_base": dict(sizes, window_rope_theta=500000.0),
        "group_map": sizes,
    }[left_out]
    theirs = params
    if left_out == "group_map":
        # the window layers' 8 query heads handed over in another order, wq's columns and wo's rows alike: the
        # same function but for the key/value head a query head reads (a % 2 where the program has a // 4)
        order = np.arange(8).reshape(2, 4).T.reshape(-1)
        group = dict(params["periods"]["window.experts"])
        group["wq"] = group["wq"].reshape(1, 3, 32, 8, 16)[..., order, :].reshape(1, 3, 32, 128)
        group["wo"] = group["wo"].reshape(1, 3, 8, 16, 32)[:, :, order].reshape(1, 3, 128, 32)
        theirs = dict(params, periods=dict(params["periods"], **{"window.experts": group}))
    with jax.default_matmul_precision("highest"):
        got, want = float(loss_fn(params, tokens, cfg)), float(ref.loss(theirs, tokens, wrong))
    assert abs(got - want) > 20 * 2e-6 * abs(got), (left_out, got, want)


# -- the band's edges, the group map and the rotation ------------------------------------


def one_hot_values(b, s, h, hkv, d, seed=0):
    """q, k random; v the identity over positions: a head's output row i is its attention over the keys."""
    kq, kk = jax.random.split(jax.random.PRNGKey(seed))
    q = jax.random.normal(kq, (b, s, h, d), jnp.float32)
    k = jax.random.normal(kk, (b, s, hkv, d), jnp.float32)
    v = jnp.broadcast_to(jnp.eye(s, dtype=jnp.float32)[None, :, None, :], (b, s, hkv, s))
    return q, k, v


def core(impl, window, tile):
    if impl == "plain":
        return lambda q, k, v: attention(q, k, v, causal=True, window=window)
    if impl == "chunked":
        return lambda q, k, v: chunked_attention(q, k, v, causal=True, chunk=tile, window=window)
    return lambda q, k, v: flash_attention(q, k, v, causal=True, block_q=tile, block_k=tile, window=window, interpret=True)


@pytest.mark.parametrize("impl", ["plain", "chunked", "flash"])
@pytest.mark.parametrize("s, window, tile", [(128, 8, 32), (128, 32, 32), (128, 33, 32), (128, 100, 32), (1024, 512, 512)])
def test_position_i_minus_window_is_excluded_and_the_next_one_included(impl, s, window, tile):
    """Every row's attention is positive on the keys i - window < j <= i and
    exactly zero elsewhere — at the published 512 through the kernel's 512 x
    512 tiles, where a query tile visits two key tiles and masks both."""
    q, k, v = one_hot_values(1, s, 2, 1, 128 if impl == "flash" else 16)
    probs = np.asarray(core(impl, window, tile)(q, k, v))[0, :, :, :]  # [query, head, key]
    i, j = np.arange(s)[:, None], np.arange(s)[None, :]
    inside = (j <= i) & (j > i - window)
    for head in range(2):
        p = probs[:, head, :]
        assert (p[~inside] == 0).all()
        assert (p[inside] > 0).all()
        np.testing.assert_allclose(p.sum(axis=1), 1.0, rtol=1e-5)
    assert probs[window, 0, 0] == 0 and probs[window, 0, 1] > 0  # i - 512 out, i - 511 in
    assert probs[window - 1, 0, 0] > 0  # the last row that still sees position 0


@pytest.mark.parametrize("impl", ["plain", "chunked", "flash"])
@pytest.mark.parametrize("s, window", [(32, 512), (96, 96), (64, 1)])
def test_a_band_wider_than_the_sequence_is_causal_attention_and_one_of_a_key_is_the_value(impl, s, window):
    q, k, v = one_hot_values(1, s, 2, 2, 128 if impl == "flash" else 16)
    got = core(impl, window, 32)(q, k, v)
    want = jnp.broadcast_to(jnp.eye(s)[None, :, None, :], got.shape) if window == 1 else attention(q, k, v, causal=True)
    np.testing.assert_allclose(got, want, atol=2e-6)


@pytest.mark.parametrize("s", [40, 100])
def test_a_sequence_that_is_no_multiple_of_the_tile(s):
    """The plain core takes any length; the kernel says what it needs, and the
    program then takes the plain core (``_attention_path``)."""
    q, k, v = one_hot_values(1, s, 4, 2, 16)
    probs = np.asarray(attention(q, k, v, causal=True, window=8))[0]
    i, j = np.arange(s)[:, None], np.arange(s)[None, :]
    assert ((probs[:, 0, :] > 0) == ((j <= i) & (j > i - 8))).all()
    with pytest.raises(ValueError, match="multiple of block sizes"):
        flash_attention(q, k, v, block_q=32, block_k=32, window=8, interpret=True)
    cfg = TransformerConfig(**dict(SIZES["window.dense"], attention_impl="chunked"))
    assert T._attention_path(cfg, s, 1, None, window=8, grouped=True)[0] == "plain"


@pytest.mark.parametrize("impl", ["plain", "chunked", "flash"])
@pytest.mark.parametrize("heads, kv_heads", [(48, 8), (64, 8)])
def test_query_head_a_reads_key_value_head_a_over_the_group(impl, heads, kv_heads):
    """Values constant over a key/value head: whatever the scores, query head
    a's output is the constant of head a // (heads / kv_heads)."""
    s, d = 32, 128 if impl == "flash" else 8
    kq, kk = jax.random.split(jax.random.PRNGKey(1))
    q = jax.random.normal(kq, (1, s, heads, d), jnp.float32)
    k = jax.random.normal(kk, (1, s, kv_heads, d), jnp.float32)
    v = jnp.broadcast_to(jnp.arange(1.0, kv_heads + 1)[None, None, :, None], (1, s, kv_heads, d))
    out = np.asarray(core(impl, 16, 32)(q, k, v))
    want = 1.0 + np.arange(heads) // (heads // kv_heads)
    np.testing.assert_allclose(out, np.broadcast_to(want[None, None, :, None], out.shape), rtol=1e-5)


def test_the_yarn_table_is_the_closed_form():
    """Laguna-XS.2's global layers: 64 rotated lanes at base 500 000, factor
    64 over 4096 original positions, beta 64 and 1. c(64) = 5.66 -> lane 5,
    c(1) = 15.80 -> lane 16; the attention factor is 0.1 ln 64 + 1."""
    c = lambda n: 64 * np.log(4096 / (2 * np.pi * n)) / (2 * np.log(500000.0))
    assert (round(c(64), 2), round(c(1), 2)) == (5.66, 15.80)
    assert (int(np.floor(c(64))), int(np.ceil(c(1)))) == (5, 16)
    assert 0.1 * np.log(64) + 1 == pytest.approx(1.4158883083359672, rel=1e-15)
    table = yarn_inv_freq(64, 500000.0, 64.0, 4096, 64.0, 1.0)
    plain = 500000.0 ** (-2.0 * np.arange(32) / 64)
    assert table.shape == (32,) and table.dtype == np.float32
    np.testing.assert_allclose(table[:6], plain[:6], rtol=1e-6)  # ramp 0 up to lane 5: as they are
    np.testing.assert_allclose(table[16:], plain[16:] / 64, rtol=1e-6)  # ramp 1 from lane 16: divided by the factor
    ramp = (np.arange(6, 16) - 5) / 11
    np.testing.assert_allclose(table[6:16], plain[6:16] / 64 * ramp + plain[6:16] * (1 - ramp), rtol=1e-6)
    sizes = dict(head_dim=128, rotary_dim=64, rope_theta=500000.0, yarn_factor=64.0, yarn_original_max=4096,
                 yarn_beta_fast=64.0, yarn_beta_slow=1.0, yarn_attention_factor=1.4158883083359672, window_rope_theta=10000.0)
    theirs, scale = ref.inv_frequencies(sizes, "full")
    np.testing.assert_allclose(table, theirs, rtol=1e-6)
    assert scale == 1.4158883083359672
    np.testing.assert_allclose(yarn_inv_freq(128, 10000.0), ref.inv_frequencies(sizes, "window")[0], rtol=1e-6)
    np.testing.assert_allclose(yarn_inv_freq(128, 10000.0), 10000.0 ** (-2.0 * np.arange(64) / 128), rtol=1e-6)


def test_a_partial_rotation_turns_lane_i_with_lane_i_plus_half_and_passes_the_rest():
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 6, 2, 16), jnp.float32)
    inv_freq = np.asarray([1.0, 0.5, 0.25, 0.125], np.float32)
    out = np.asarray(rotary_embed(x, jnp.arange(6), inv_freq=inv_freq, scale=1.5))
    np.testing.assert_array_equal(out[..., 8:], np.asarray(x)[..., 8:])  # past r = 8: as they were
    np.testing.assert_allclose(out[:, 0, :, :8], 1.5 * np.asarray(x)[:, 0, :, :8], rtol=1e-6)  # position 0: the scale alone
    pos, lane = 3, 1
    a, b = np.asarray(x)[0, pos, 0, lane], np.asarray(x)[0, pos, 0, lane + 4]
    angle = pos * 0.5
    np.testing.assert_allclose(out[0, pos, 0, lane], 1.5 * (a * np.cos(angle) - b * np.sin(angle)), rtol=1e-5)
    np.testing.assert_allclose(out[0, pos, 0, lane + 4], 1.5 * (b * np.cos(angle) + a * np.sin(angle)), rtol=1e-5)
    # the rotation the program always had pairs lane 2i with lane 2i + 1, and is what it was
    old = np.asarray(rotary_embed(x, jnp.arange(6), 10000.0))
    a, b = np.asarray(x)[0, pos, 1, 2], np.asarray(x)[0, pos, 1, 3]
    angle = pos * 10000.0 ** (-2 / 16)
    np.testing.assert_allclose(old[0, pos, 1, 2], a * np.cos(angle) - b * np.sin(angle), rtol=1e-5)
    np.testing.assert_allclose(old[0, pos, 1, 3], a * np.sin(angle) + b * np.cos(angle), rtol=1e-5)


# -- the kernel, in interpret mode ----------------------------------------------------------


@pytest.mark.parametrize("b, s, h, hkv, d, window, bq, bk", [
    (1, 128, 6, 2, 16, None, 32, 32),   # grouped, the whole prefix
    (1, 128, 6, 2, 16, 40, 32, 32),     # grouped and banded, the band no multiple of the tile
    (2, 128, 8, 2, 128, 32, 32, 32),    # heads read in place (a lane tile a head), band = tile
    (1, 256, 4, 1, 128, 100, 64, 32),   # one key/value head for all, a query tile of two key tiles
    (1, 256, 4, 4, 128, 64, 32, 64),    # a band without groups, a key tile of two query tiles
    (1, 64, 2, 2, 16, 512, 32, 32),     # the band wider than the sequence
    (1, 128, 4, 2, 16, 1, 32, 32),      # a band of the key itself
    (1, 128, 32, 8, 64, None, 64, 32),  # lfm2-8b-a1b-1g's layer: 32 heads over 8 of 64 lanes, through [B, H, S, Dh]
])
def test_the_kernel_agrees_with_plain_attention_forward_and_backward(b, s, h, hkv, d, window, bq, bk):
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(ks[0], (b, s, h, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, s, hkv, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, s, hkv, d), jnp.float32)
    do = jax.random.normal(ks[3], (b, s, h, d), jnp.float32)
    want = jax.value_and_grad(lambda *a: jnp.sum(attention(*a, causal=True, window=window) * do), (0, 1, 2))(q, k, v)
    got = jax.value_and_grad(
        lambda *a: jnp.sum(flash_attention(*a, causal=True, block_q=bq, block_k=bk, window=window, interpret=True) * do),
        (0, 1, 2),
    )(q, k, v)
    for a, b_ in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(a, b_, atol=2e-5 * max(1.0, float(jnp.max(jnp.abs(b_)))))


def test_the_kernel_with_more_than_one_resident_block_of_keys(monkeypatch):
    """Past ``_RESIDENT_KEYS`` the keys arrive in several blocks: a block
    below the band is neither fetched (the index map clamps) nor computed."""
    import sys

    monkeypatch.setattr(sys.modules["torchft_tpu.ops.pallas.flash_attention"], "_RESIDENT_KEYS", 64)
    test_the_kernel_agrees_with_plain_attention_forward_and_backward(1, 256, 4, 2, 16, 40, 32, 32)


def test_what_the_kernel_refuses():
    q = jnp.zeros((1, 32, 6, 16))
    with pytest.raises(ValueError, match="groups are whole"):
        flash_attention(q, q[:, :, :4], q[:, :, :4], interpret=True)
    with pytest.raises(ValueError, match="a band is causal"):
        flash_attention(q, q, q, causal=False, window=8, interpret=True)


# digest of the jaxpr of the kernel's forward and backward calls, and sums of |o|, |dq|, |dk|, |dv| at small sizes:
# the shapes the four existing cells call the kernel with — 16 heads x 128 at b8 x s2048 (olmo1b-*, olmoe-1g) and
# the latent attention's 32 heads, keys 192 and values 128, at b2 x s8192 (kimi-linear-1g), both at 512 x 512 tiles.
# CELLS_NUMBERS and CELLS_KERNELS are the parent commit's (24d5069, and still 6356440's): the kernels trace to the
# calls they traced to before heads could be grouped or banded, letter for letter. CELLS_CALLS was moved on purpose by
# PR 47 (at 6356440: c60a781bc5ad8db8…84b0 and d02d2ac3ca2b5c0f…a630): the forward rule names the output and the row
# statistics (``checkpoint_name``), so the text of the call's ``vjp`` holds two equations more,
#     ho:bf16[8,2048,2048] = name[name=attn_core_out] bs
#     hp:f32[8,16,8,2048] = name[name=attn_core_lse] bt
# (``ia``/``ib`` over ``ce``/``cf`` at the latent shape) and every variable after them is lettered two further on; the
# diff of the old text and the new, variables renumbered, is those two lines and the two reads of ``o`` and ``lse`` by
# ``flash_bwd``. The two ``pallas_call`` equations, each printed on its own (its variables then start at ``a``), are
# the parent's to the letter: CELLS_KERNELS holds at both commits.
CELLS_CALLS = {
    (8, 2048, 16, 128, 128): "408e87229cf4f06b047adef4c584f43fa0ead0fe35063258ae3aa313a7e3f904",
    (2, 8192, 32, 192, 128): "a013f26ac293e4c790547f1954692e5989c0b72a6eeb964093f58e5cead41005",
}
CELLS_KERNELS = {
    (8, 2048, 16, 128, 128): {"flash_fwd": ["7389ffc81f497cdf"], "flash_bwd": ["dd0bca565252ddb6"]},
    (2, 8192, 32, 192, 128): {"flash_fwd": ["0a203b9237cb127a"], "flash_bwd": ["79821667815a0b73"]},
}
CELLS_NUMBERS = {
    (1, 1024, 2, 128, 128, 512, 0): [20163.15625, 18679.63671875, 15094.607421875, 15714.056640625],
    (1, 1024, 2, 192, 128, 512, 1): [19887.76953125, 22460.6796875, 18072.865234375, 15375.7861328125],
    (1, 256, 2, 64, 64, 128, 2): [4881.51953125, 3915.197021484375, 3181.8994140625, 3656.3369140625],
}


def jaxprs_in(value):
    """The jaxprs a parameter of an equation holds, open or closed, alone or in a tuple."""
    subs = (getattr(sub, "jaxpr", sub) for sub in (value if isinstance(value, (tuple, list)) else (value,)))
    return [sub for sub in subs if hasattr(sub, "eqns")]


def sub_jaxprs(eqn):
    """The jaxprs among an equation's parameters: a scan's body, a cond's
    branches, a checkpoint's, a custom rule's, a kernel's."""
    return [sub for value in eqn.params.values() for sub in jaxprs_in(value)]


def every_equation(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in sub_jaxprs(eqn):
            yield from every_equation(sub)


def kernel_equations(jaxpr):
    """Kernel name -> sorted digests of its ``pallas_call`` equations anywhere
    in ``jaxpr``, each printed on its own: an equation's text then names its
    variables from ``a`` on, whatever the program around it holds."""
    out = {}
    for eqn in every_equation(jaxpr):
        if eqn.primitive.name == "pallas_call":
            text = re.sub(r"0x[0-9a-f]+", "0x", str(eqn))
            out.setdefault(str(eqn.params["name"]), []).append(hashlib.sha256(text.encode()).hexdigest()[:16])
    return {name: sorted(digests) for name, digests in out.items()}


def _the_calls_vjp(shape):
    b, s, h, dk, dv = shape
    q = jax.ShapeDtypeStruct((b, s, h, dk), jnp.bfloat16)
    v = jax.ShapeDtypeStruct((b, s, h, dv), jnp.bfloat16)

    def both(q, k, v):
        call = lambda *a: flash_attention(*a, causal=True, block_q=512, block_k=512, interpret=False)
        return jax.vjp(call, q, k, v)[1](jnp.ones((b, s, h, dv), jnp.bfloat16))

    return jax.make_jaxpr(both)(q, q, v)


@pytest.mark.parametrize("shape", list(CELLS_CALLS))
def test_the_existing_cells_calls_of_the_kernel_are_unchanged(shape):
    text = re.sub(r"0x[0-9a-f]+", "0x", str(_the_calls_vjp(shape)))
    assert hashlib.sha256(text.encode()).hexdigest() == CELLS_CALLS[shape]


@pytest.mark.parametrize("shape", list(CELLS_KERNELS))
def test_the_existing_cells_kernel_equations_are_the_parents(shape):
    assert kernel_equations(_the_calls_vjp(shape).jaxpr) == CELLS_KERNELS[shape]


@pytest.fixture
def optimised():
    """CELLS_NUMBERS are sums an OPTIMISED program computes, to the last bit;
    ``tests/conftest.py`` turns XLA's optimisation off for programs that run
    once, and the unoptimised ones round elsewhere. The flag is read at each
    compile and is no part of an executable's key in memory, hence the clear."""
    before = jax.config.values["jax_disable_most_optimizations"]
    jax.config.update("jax_disable_most_optimizations", False)
    jax.clear_caches()
    yield
    jax.config.update("jax_disable_most_optimizations", before)


@pytest.mark.parametrize("shape", list(CELLS_NUMBERS))
def test_the_existing_cells_outputs_of_the_kernel_are_unchanged(shape, optimised):
    b, s, h, dk, dv, tile, seed = shape
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q, k = (jax.random.normal(key, (b, s, h, dk)) for key in ks[:2])
    v, do = (jax.random.normal(key, (b, s, h, dv)) for key in ks[2:])
    o, vjp = jax.vjp(lambda *a: flash_attention(*a, causal=True, block_q=tile, block_k=tile), q, k, v)
    got = [float(jnp.sum(jnp.abs(x))) for x in (o,) + vjp(do)]
    assert got == CELLS_NUMBERS[shape]


# -- the share -----------------------------------------------------------------------------------


def expert_layer(share, shares=8, seed=0):
    """One sparse layer of 16 experts, 2 held on each of 8 chips, 4 chosen a token."""
    sizes = dict(ATTENTION, **dict(EXPERTS, n_experts_held=16 // shares), n_layers=1, expert_share_index=share)
    cfg = TransformerConfig(dtype=jnp.float32, **sizes)
    keys = jax.random.split(jax.random.PRNGKey(seed), 8)
    d, e, f = 32, 16, 16
    whole = {
        "router": 3.0 * jax.random.normal(keys[0], (d, e)) * d**-0.5,
        "w_gate": jax.random.normal(keys[2], (e, d, f)) * d**-0.5,
        "w_in": jax.random.normal(keys[3], (e, d, f)) * d**-0.5,
        "w_out": jax.random.normal(keys[4], (e, f, d)) * f**-0.5,
        "shared_gate": jax.random.normal(keys[5], (d, f)) * d**-0.5,
        "shared_in": jax.random.normal(keys[6], (d, f)) * d**-0.5,
        "shared_out": jax.random.normal(keys[7], (f, d)) * f**-0.5,
    }
    held = 16 // shares
    lp = {k: (v[share * held : (share + 1) * held] if k in ("w_gate", "w_in", "w_out") else v) for k, v in whole.items()}
    return cfg, lp, whole, sizes


def test_the_eight_shares_of_a_layer_add_up_to_the_uncut_layer():
    """Eight chips, two of sixteen experts each: the routed parts of all eight
    shares plus the shared expert counted once are the layer of the uncut
    reference — what the cell's chip leaves out is what the other seven hold."""
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 48, 32))
    _, _, whole, sizes = expert_layer(0)
    with jax.default_matmul_precision("highest"):
        want = ref._experts(whole, x, dict(sizes, n_experts_held=0, expert_share_index=0))
        shared = ref._swiglu(x, whole["shared_gate"], whole["shared_in"], whole["shared_out"])
        routed, rows = [], []
        for share in range(8):
            cfg, lp, _, share_sizes = expert_layer(share)
            y, (_, counts, held, _) = jax.jit(lambda lp, x, cfg=cfg: T._ffn_moe(lp, x, cfg))(lp, x)
            routed.append(y - shared)
            rows.append(int(held))
            mine = dict(whole, **{k: lp[k] for k in ("w_gate", "w_in", "w_out")})
            np.testing.assert_allclose(y, ref._experts(mine, x, share_sizes), atol=2e-5)
            assert int(jnp.sum(counts)) == 2 * 48 * 4  # the router counts over all 16, on every share
    np.testing.assert_allclose(sum(routed) + shared, want, atol=5e-5)
    assert sum(rows) == 2 * 48 * 4 and len(set(rows)) > 1  # every token-expert row on exactly one share


# -- what it says, what it refuses, and what it leaves as it was -----------------------------------


def test_each_kind_says_its_path_once_and_the_pattern_names_the_kinds(monkeypatch):
    from torchft_tpu import telemetry

    monkeypatch.setattr(T, "_PATHS_SAID", set())
    cfg, params, tokens, _ = make("stack", seq=32)
    seen = {kind: len(telemetry.EVENTS.recent(kind)) for kind in ("attention_path", "layer_pattern")}
    for _ in range(2):
        jax.jit(lambda p: loss_fn(p, tokens, cfg, None))(params)
    paths = telemetry.EVENTS.recent("attention_path")[seen["attention_path"]:]
    said = sorted((e["n_heads"], e["n_kv_heads"], e["window"], e["rotary_dim"], e["impl"]) for e in paths)
    assert said == [(6, 2, 0, 8, "plain"), (8, 2, 8, 16, "plain")]  # once a KIND: two global layers, three window
    (pattern,) = telemetry.EVENTS.recent("layer_pattern")[seen["layer_pattern"]:]
    assert pattern["period"] == "full.dense,window.experts,window.experts,window.experts,full.experts"
    assert (pattern["layers"], pattern["lead"], pattern["experts_held"], pattern["experts"]) == (5, "-", 4, 16)


def test_a_model_that_declares_no_kind_says_what_it_said(monkeypatch):
    from torchft_tpu import telemetry

    monkeypatch.setattr(T, "_PATHS_SAID", set())
    cfg = TransformerConfig(**T.PRESETS["tiny"])
    before = len(telemetry.EVENTS.recent("attention_path"))
    loss_fn(init_params(jax.random.PRNGKey(0), cfg), jnp.zeros((1, 16), jnp.int32), cfg)
    (e,) = telemetry.EVENTS.recent("attention_path")[before:]
    assert {"impl", "block_q", "block_k", "batch", "seq", "head_dim", "value_dim", "reason"} <= set(e)
    assert not {"window", "n_kv_heads", "rotary_dim", "n_heads"} & set(e)


def test_on_a_tpu_the_banded_kernel_is_picked_from_what_can_be_observed(monkeypatch):
    cfg = TransformerConfig(**dict(STACK, head_dim=128, rotary_dim=64))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    for window in (0, 512):
        impl, why, blocks = T._attention_path(cfg, 8192, 2, None, window=window, grouped=True)
        assert (impl, blocks) == ("flash", (512, 512)) and "auto on a tpu" in why
    assert T._attention_path(cfg, 8192, 2, None, sp_manual=True, window=512, grouped=True)[0] == "chunked"
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert T._attention_path(cfg, 8192, 2, None, window=512, grouped=True)[0] == "chunked"


def test_what_a_band_and_grouped_heads_refuse_under_sp_and_pp():
    cfg, params, tokens, _ = make("stack", seq=32)
    with pytest.raises(ValueError, match="stages that each hold their own kinds of layer .* are missing"):
        loss_fn(params, tokens, dataclasses.replace(cfg, pp=2))
    sp = make_mesh(MeshConfig(sp=2), devices=jax.devices()[:2])
    for size, missing in (("window.dense", "the band's early stop"), ("grouped_only", "the group map")):
        cfg, params, tokens, _ = make(size, seq=32)
        with pytest.raises(ValueError, match=f"sp=2 with a window .* {missing} .* are missing"), jax.set_mesh(sp):
            jax.jit(lambda p, t: loss_fn(p, t, cfg, sp))(params, tokens)


@pytest.mark.parametrize("changes, message", [
    (dict(window=8), "a band has both"),
    (dict(window_layers=(1,)), "a band has both"),
    (dict(rope_pairing="pairs"), "rope_pairing must be"),
    (dict(rotary_dim=8, rope_pairing="interleaved"), "come with rope_pairing='half'"),
    (dict(rotary_dim=7), "an even number of a head's"),
    (dict(rotary_dim=32), "an even number of a head's"),
    (dict(n_heads_per_layer=(6,)), "1 entries for 2 layers"),
    (dict(n_heads_per_layer=(6, 8)), "layers of one kind agree"),
    (dict(n_heads=5, n_heads_per_layer=()), "the groups are whole"),
    (dict(window=8, window_layers=(1,), kda_layers=(1,)), "each at most once"),
])
def test_what_the_configuration_refuses(changes, message):
    with pytest.raises(ValueError, match=message):
        TransformerConfig(**dict(SIZES["global.dense"], **changes))


def test_the_fused_step_learns_on_the_stack():
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
    load = np.asarray(ts.last_stats["tokens_per_expert"])
    assert load.shape == (4, 16) and (load.sum(axis=1) == 2 * 32 * 4).all()  # the four sparse layers, all 16 experts
    np.testing.assert_array_equal(ts.last_stats["rows_held"], load[:, 4:8].sum(axis=1))  # share 1 holds experts 4..7


def test_a_dp_x_fsdp_x_tp_mesh_gives_the_unsharded_loss():
    """tp = 2 divides the key/value heads (2) and both kinds' query heads (6, 8)."""
    cfg, params, tokens, _ = make("stack", seq=32)
    tokens = jnp.concatenate([tokens, tokens[::-1]], axis=0)
    want = float(jax.jit(loss_fn, static_argnums=2)(params, tokens, cfg))
    mesh = make_mesh(MeshConfig(dp=2, fsdp=2, tp=2), devices=jax.devices()[:8])
    ts = TrainStep(cfg, optax.adamw(1e-2), mesh)
    loss, grads = ts.grads(jax.device_put(params, ts._param_shardings), ts.shard_batch(tokens))
    assert float(loss) == pytest.approx(want, rel=1e-5)
    g_want = jax.jit(jax.grad(loss_fn), static_argnums=2)(params, tokens, cfg)
    assert max(jax.tree_util.tree_leaves(grad_errors(grads, g_want))) < 5e-4
