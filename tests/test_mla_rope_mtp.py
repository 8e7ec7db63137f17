"""Latent attention with a low-rank query and a rotated shared key in every
layer, and a multi-token-prediction module that shares the tables, through the
one transformer — against the plain reference
(``benchmark/reference/joyai_flash_decoder.py``, loaded by path: one copy).
``tests/test_mla_rope_mtp_train.py`` holds the share, the named scopes, the
other cells' programs, ``TrainStep`` and the Manager: a file of its own, so that
a worker of the tier-1 run gets half of the compiles.

Tolerance of the float32 comparisons: both sides compute in float32 on the CPU
(matmuls at "highest"); what differs is the order of the sums. Measured here:
the loss to 1e-7 of itself, gradient leaves to 1.3e-6 of their largest entry.
In bfloat16 at these tiny widths (32 wide, 40 positions) the loss reads
2e-3 to 9e-3 off the float32 reference: the band is 3e-2, and it holds the
structure only loosely — what holds the structure is the float32 comparison.
"""

import dataclasses
import importlib.util
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torchft_tpu.models import transformer as T
from torchft_tpu.models.transformer import TransformerConfig, init_params, layer_pattern, loss_and_stats, loss_fn
from torchft_tpu.parallel.mesh import MeshConfig, make_mesh

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RTOL = 1e-5
BF16_BAND = 3e-2


def _load_reference():
    path = os.path.join(ROOT, "benchmark", "reference", "joyai_flash_decoder.py")
    spec = importlib.util.spec_from_file_location("joyai_flash_decoder_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load_reference()

LATENT = dict(
    vocab_size=64, d_model=32, n_heads=2, d_ff=64, norm_eps=1e-6, q_lora_rank=12, kv_lora_rank=8,
    qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8, mla_rope_theta=32000000.0,
)
EXPERTS = dict(
    moe_d_ff=16, n_experts=16, n_experts_held=4, top_k=4, n_shared_experts=1, router_gate="sigmoid",
    router_renormalize=True, routed_scaling_factor=2.5, expert_share_index=1,
)
# the cell's stack at tiny widths: a dense layer and a period of ONE sparse layer scanned twice, the module behind
STACK = dict(LATENT, **EXPERTS, n_layers=3, mla_layers=(1, 2, 3), n_dense_layers=1)
SIZES = {
    "latent": dict(LATENT, n_layers=2, mla_layers=(1, 2)),
    "experts": dict(LATENT, **EXPERTS, n_layers=2, mla_layers=(1, 2), n_dense_layers=1),  # a dense and a sparse layer, unrolled
    "module": dict(STACK, n_mtp_modules=1, mtp_loss_weight=0.3),
}


def off_their_defaults(params):
    """Norm weights off 1, a selection bias off 0, a router with loads that
    differ: or a norm without its weight, a bias that is not applied or one
    that reaches the weights would pass."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(params)
    out = []
    for i, (path, a) in enumerate(leaves):
        name = path[-1].key
        if name in ("ln1", "ln2", "kv_norm", "q_a_norm", "final_norm", "enorm", "hnorm"):
            a = a * (1 + 0.3 * jnp.sin(jnp.arange(a.size, dtype=jnp.float32).reshape(a.shape) + i))
        elif name == "router":
            a = a * 3.0
        elif name == "router_bias":
            a = 0.05 * jax.random.normal(jax.random.PRNGKey(i), a.shape)
        out.append(a)
    return jax.tree_util.tree_unflatten(treedef, out)


def make(size, seq=40, remat=False, **changes):
    """``remat``: off but where a test is about what the checkpoints keep — the
    numbers are the same and a program without them compiles in half the time."""
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


def reference(params, tokens, sizes):
    return jax.jit(jax.value_and_grad(lambda p, t: ref.loss(p, t, sizes)))(params, tokens)


# -- the program against the reference ------------------------------------------------


@pytest.mark.parametrize("size", list(SIZES))
def test_loss_and_every_gradient_leaf_agree_with_the_reference(size):
    cfg, params, tokens, sizes = make(size, remat=True)
    got, g_got = system(cfg, params, tokens)
    want, g_want = reference(params, tokens, sizes)
    assert float(got) == pytest.approx(float(want), rel=2e-6)
    errs = grad_errors(g_got, g_want)
    assert max(jax.tree_util.tree_leaves(errs)) < RTOL, errs
    for path, g in jax.tree_util.tree_leaves_with_path(g_got):  # the bias chooses and does not weigh
        if path[-1].key == "router_bias":
            assert float(jnp.max(jnp.abs(g))) == 0.0


def test_bfloat16_compute_stays_inside_the_stated_band():
    cfg, params, tokens, sizes = make("module")
    got = float(jax.jit(loss_fn, static_argnums=2)(params, tokens, dataclasses.replace(cfg, dtype=jnp.bfloat16)))
    assert abs(got - float(ref.loss(params, tokens, sizes))) < BF16_BAND


def test_the_chunked_head_twice_on_one_table_gives_the_sum_of_both_uses(monkeypatch):
    """The cell's head (``_chunked_nll``, its custom gradient) called by the
    main stack and by the module on the one ``out`` table: every leaf's
    gradient — the two tables' among them — is the main term's plus the
    weight times the module's, each taken by itself on the reference."""
    monkeypatch.setattr(T, "_LOSS_CHUNK_ELEMS", 2 * 16 * 64)  # chunks of 16 positions; 40 = 2.5 of them
    cfg, params, tokens, sizes = make("module", remat=True)
    got, g_got = system(cfg, params, tokens)
    with jax.default_matmul_precision("highest"):
        two = lambda p: jnp.stack(ref.losses(p, tokens, sizes))
        (main, mtp), by_term = jax.jit(lambda p: (two(p), jax.jacrev(two)(p)))(params)
    g_main, g_mtp = (jax.tree_util.tree_map(lambda a: a[i], by_term) for i in range(2))
    assert float(got) == pytest.approx(float(main) + 0.3 * float(mtp), rel=2e-6)
    both = jax.tree_util.tree_map(lambda a, b: a + 0.3 * b, g_main, g_mtp)
    assert max(jax.tree_util.tree_leaves(grad_errors(g_got, both))) < RTOL
    for table in ("out", "embed"):  # the module does reach both tables, and the main term does not reach the module
        assert float(jnp.max(jnp.abs(g_mtp[table]))) > 0
    assert all(float(jnp.max(jnp.abs(g))) == 0 for g in jax.tree_util.tree_leaves(g_main["mtp"]))


def test_per_sequence_loss_is_what_the_worker_compares():
    cfg, params, tokens, sizes = make("module")
    one = jax.jit(lambda p, t: loss_fn(p, t, cfg))
    with jax.default_matmul_precision("highest"):
        per = jax.jit(lambda p, t: ref.per_sequence_loss(p, t, sizes))(params, tokens)
        mine = [float(one(params, tokens[i : i + 1])) for i in range(2)]
    np.testing.assert_allclose(per, mine, rtol=2e-6)


def test_a_weight_of_zero_is_the_main_loss_and_no_gradient_into_the_module():
    cfg, params, tokens, sizes = make("module", mtp_loss_weight=0.0)
    (got, stats), grads = jax.jit(jax.value_and_grad(loss_and_stats, has_aux=True), static_argnums=2)(params, tokens, cfg)
    without = dict(params)
    del without["mtp"]
    plain = float(jax.jit(loss_fn, static_argnums=2)(without, tokens, dataclasses.replace(cfg, n_mtp_modules=0)))
    assert float(got) == plain and "mtp_loss" not in stats
    assert all(float(jnp.max(jnp.abs(g))) == 0 for g in jax.tree_util.tree_leaves(grads["mtp"]))
    weighted, stats = jax.jit(loss_and_stats, static_argnums=2)(params, tokens, dataclasses.replace(cfg, mtp_loss_weight=0.3))
    assert float(weighted) == pytest.approx(float(stats["main_loss"]) + 0.3 * float(stats["mtp_loss"]), rel=1e-6)
    assert float(stats["main_loss"]) == pytest.approx(plain, rel=1e-6)
    assert stats["tokens_per_expert"].shape == (3, 16)  # the two sparse layers of the stack, the module's last


_PROGRAMS_LOSS = {}  # the program's side is the same in every case: computed once


@pytest.mark.parametrize("left_out", ["key_not_rotated", "query_norm", "targets_off_by_one", "halves_swapped"])
def test_the_check_would_see_a_term_left_out(left_out):
    """Each of what makes the architecture different, changed on the
    reference's side alone, moves the loss by far more than the comparison
    allows (2e-6 of it)."""
    cfg, params, tokens, sizes = make("module")
    theirs, wrong, patched = params, sizes, {}
    if left_out == "key_not_rotated":
        rotate = ref._rotate
        patched["_rotate"] = lambda x, table: x if x.shape[2] == 1 else rotate(x, table)
    elif left_out == "query_norm":
        ones = lambda tree: {k: ones(v) if isinstance(v, dict) else jnp.ones_like(v) if k == "q_a_norm" else v for k, v in tree.items()}
        theirs = ones(params)
    elif left_out == "targets_off_by_one":
        nll = ref._nll
        patched["_nll"] = lambda logits, toks, ahead: nll(logits, toks, 1 if ahead == 2 else ahead)
    else:
        w = params["mtp"]["eh_proj"]
        theirs = dict(params, mtp=dict(params["mtp"], eh_proj=jnp.concatenate([w[32:], w[:32]])))
    saved = {name: getattr(ref, name) for name in patched}
    try:
        for name, fn in patched.items():
            setattr(ref, name, fn)
        with jax.default_matmul_precision("highest"):
            if "got" not in _PROGRAMS_LOSS:
                _PROGRAMS_LOSS["got"] = float(jax.jit(loss_fn, static_argnums=2)(params, tokens, cfg))
            got, want = _PROGRAMS_LOSS["got"], float(jax.jit(lambda p, t: ref.loss(p, t, wrong))(theirs, tokens))
    finally:
        for name, fn in saved.items():
            setattr(ref, name, fn)
    assert abs(got - want) > 20 * 2e-6 * abs(got), (left_out, got, want)


# -- the rotation and the low-rank query ----------------------------------------------------


def test_the_rotation_takes_the_querys_last_lanes_and_the_one_shared_key(monkeypatch):
    """``rotary_embed`` is handed the query's last ``qk_rope_head_dim`` lanes of
    every head and the shared key part ONCE (one head wide, before it is
    broadcast); lanes 2i and 2i + 1 turn by ``p · theta^(-2i/r)``."""
    seen = []
    rotary = T.rotary_embed
    monkeypatch.setattr(T, "rotary_embed", lambda x, pos, *a, **kw: seen.append((x.shape, kw)) or rotary(x, pos, *a, **kw))
    cfg, params, tokens, _ = make("latent")
    loss_fn(params, tokens, cfg)
    assert [shape for shape, _ in seen] == [(2, 40, 2, 4), (2, 40, 1, 4)]  # the scanned layer, traced once: q's rope lanes, the one key
    assert all(kw == {"theta": 32000000.0} for _, kw in seen)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 6, 1, 4))
    out = np.asarray(rotary(x, jnp.arange(6), 32000000.0))
    np.testing.assert_allclose(out, ref._rotate(x, ref.inv_frequencies(dict(LATENT))), rtol=1e-5, atol=1e-6)
    a, b = np.asarray(x)[0, 3, 0, 2], np.asarray(x)[0, 3, 0, 3]
    angle = 3 * 32000000.0 ** (-2 / 4)
    np.testing.assert_allclose(out[0, 3, 0, 2], a * np.cos(angle) - b * np.sin(angle), rtol=1e-5)
    np.testing.assert_allclose(out[0, 3, 0, 3], a * np.sin(angle) + b * np.cos(angle), rtol=1e-5)


def test_positions_are_seen_with_the_rotation_and_not_without():
    """One latent layer: the last position's attention is a sum over the keys
    before it, so without positions the ORDER of the earlier tokens does not
    reach it; with the rotation it does."""
    moved = {}
    for theta in (0.0, 1000.0):
        cfg, params, tokens, _ = make("latent", n_layers=1, mla_layers=(1,), mla_rope_theta=theta)
        shuffled = jnp.concatenate([tokens[:, :-1][:, ::-1], tokens[:, -1:]], axis=1)
        last = lambda t: T._hidden_states(params, t, cfg)[0][:, -1]
        moved[theta] = float(jnp.max(jnp.abs(last(tokens) - last(shuffled))))
    assert moved[0.0] < 1e-5 < 1e-2 < moved[1000.0]


# -- what it says, what it names, what it refuses ---------------------------------------------------


def test_the_pattern_is_a_dense_layer_and_one_scanned_kind_and_the_events_say_so(monkeypatch):
    from torchft_tpu import telemetry

    monkeypatch.setattr(T, "_PATHS_SAID", set())
    cfg, params, tokens, _ = make("module", seq=32)
    assert layer_pattern(cfg) == (1, (("mla", "experts"),))
    assert set(params) == {"embed", "final_norm", "out", "lead", "periods", "mtp"}
    assert params["periods"]["mla.experts"]["wq_b"].shape == (2, 1, 12, 2 * 12) and "wq" not in params["lead"]["mla.dense"]
    assert set(params["mtp"]) == {"enorm", "hnorm", "eh_proj", "layer", "final_norm"}
    assert params["mtp"]["eh_proj"].shape == (64, 32) and params["mtp"]["layer"]["w_gate"].shape == (4, 32, 16)
    seen = {kind: len(telemetry.EVENTS.recent(kind)) for kind in ("attention_path", "layer_pattern")}
    for _ in range(2):
        jax.jit(lambda p: loss_fn(p, tokens, cfg, None))(params)
    (path,) = telemetry.EVENTS.recent("attention_path")[seen["attention_path"]:]  # once: every layer is of the one shape
    assert (path["head_dim"], path["value_dim"], path["q_lora_rank"], path["rotary_dim"], path["impl"]) == (12, 8, 12, 4, "plain")
    (pattern,) = telemetry.EVENTS.recent("layer_pattern")[seen["layer_pattern"]:]
    assert (pattern["lead"], pattern["period"], pattern["repeats"]) == ("mla.dense", "mla.experts", 2)
    assert (pattern["mtp"], pattern["mtp_weight"], pattern["experts_held"]) == ("mla.experts", 0.3, 4)


def test_what_the_module_and_the_rotated_key_refuse():
    cfg, params, tokens, _ = make("module", seq=32)
    with pytest.raises(ValueError, match="chain that hands module k's hidden state .* is missing"):
        dataclasses.replace(cfg, n_mtp_modules=2)
    with pytest.raises(ValueError, match="describe a model with mla_layers"):
        TransformerConfig(q_lora_rank=8)
    one_kind = dict(SIZES["latent"], n_mtp_modules=1)
    with pytest.raises(ValueError, match="multi-token-prediction module: .* a pipeline exit .* is missing"):
        c = TransformerConfig(dtype=jnp.float32, pp=2, **one_kind)
        loss_fn(init_params(jax.random.PRNGKey(0), c), tokens, c)
    sp = make_mesh(MeshConfig(sp=2), devices=jax.devices()[:2])
    rotated, p, _, _ = make("latent", seq=32)
    with pytest.raises(ValueError, match="sp=2 with a rotated shared key: .* are missing"), jax.set_mesh(sp):
        jax.jit(lambda p, t: loss_fn(p, t, rotated, sp))(p, tokens)
