"""A learned key selector in front of the grouped-query core — a lightning
indexer's scores, the k-th largest a query, the core under the selection, the
selector's own KL term — through the one transformer, against the plain
reference (``benchmark/reference/keye_sparse_decoder.py``, loaded by path: one
copy), and the kernels against ``ops.attention``.

A selection flips on rounding, so the comparison has two parts. (a) THE SETS:
every pair the program selects and the reference does not, or the reverse, has
a reference score within ``EPS`` of its row's spread from the row's reference
threshold (``ref.sets_apart``). (b) THE VALUES GIVEN THE SETS: with the
program's sets handed to the reference (``selection=``), loss (each part) and
every gradient leaf agree. Both sides compute in float32 on the CPU (matmuls at
"highest"); what differs is the order of the sums, so ``EPS`` here is 1e-5 of
the spread where a bfloat16 indexer on the chip needs a few 2^-9
(``benchmark/check_keye.py``), and RTOL is far above what the order of sums
moves and far below what one slipped term does (the slips' test shows each).
"""

import contextlib
import dataclasses
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from tests.test_block_diffusion import grad_errors, off_their_defaults
from torchft_tpu.models import transformer as T
from torchft_tpu.models.transformer import TransformerConfig, init_params, loss_and_stats, loss_fn, selections
from torchft_tpu.parallel.mesh import MeshConfig, make_mesh
from torchft_tpu.parallel.train_step import TrainStep

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RTOL = 5e-5
EPS = 1e-5


def _load_reference():
    path = os.path.join(ROOT, "benchmark", "reference", "keye_sparse_decoder.py")
    spec = importlib.util.spec_from_file_location("keye_sparse_decoder_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load_reference()

# the cell's layer at tiny widths: 4 query heads over 2 key/value heads, q and k normed head by head and rotated over
# the whole head, a selector of 4 heads of 8 over one key head that keeps 16 keys a query, 8 experts of which 4 are
# held (share 1) and 2 are chosen a row, renormalised
LAYER = dict(
    vocab_size=64, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2, head_dim=16, d_ff=16, moe_d_ff=16, norm_eps=1e-6,
    qk_norm=True, qk_norm_per_head=True, rope_pairing="half", rope_theta=10000.0,
    n_experts=8, n_experts_held=4, expert_share_index=1, top_k=2, router_gate="softmax", router_renormalize=True,
    sparse_topk=16, indexer_heads=4, indexer_head_dim=8,
)
INDEXER = ("idx_wq", "idx_wk", "idx_ww")


def make(seq=64, **over):
    sizes = dict(LAYER, **over)
    cfg = TransformerConfig(dtype=jnp.float32, remat=True, **sizes)
    params = off_their_defaults(init_params(jax.random.PRNGKey(3), cfg))
    tokens = jnp.asarray(np.random.default_rng(0).integers(0, 64, (2, seq)), jnp.int32)
    return cfg, params, tokens, sizes


# jitted once for the file (``tests/test_gdn.py`` says why)
_loss_and_grads = jax.jit(jax.value_and_grad(loss_and_stats, has_aux=True), static_argnums=2)
_sets = jax.jit(selections, static_argnums=2)


def system(cfg, params, tokens):
    with jax.default_matmul_precision("highest"):
        (loss, stats), grads = _loss_and_grads(params, tokens, cfg)
        return loss, stats, grads, _sets(params, tokens, cfg)


def reference(params, tokens, sizes, slip=None, given=None, grads=True):
    """(loss, gradients, (next-token loss, terms [L], sets, scores) a sequence)
    of the reference on ``tokens``; ``given`` [L, B, S, S] replaces its sets;
    without ``grads`` the gradients are None (a forward pass compiles faster)."""

    def loss(p):
        def one(args):
            seq, sets = args
            h, terms, seen, scores = ref.hidden(p, seq, sizes, slip, sets)
            logp = jax.nn.log_softmax(h[:-1] @ p["out"], axis=-1)
            lm = -jnp.mean(jnp.take_along_axis(logp, seq[1:, None], axis=-1))
            return lm + jnp.sum(terms), (lm, terms, seen, scores)

        sets = None if given is None else jnp.moveaxis(given, 1, 0)
        each, said = jax.lax.map(one, (tokens, sets)) if sets is not None else jax.lax.map(lambda t: one((t, None)), tokens)
        return jnp.mean(each), said

    with jax.default_matmul_precision("highest"):
        if not grads:
            return _forward_only(loss, params)
        (value, said), grads = jax.jit(jax.value_and_grad(loss, has_aux=True))(params)
    return value, grads, said


def _forward_only(loss, params):
    value, said = jax.jit(loss)(params)
    return value, None, said


# -- the program against the reference: (a) the sets, (b) the values given the sets ---------------------------


@pytest.mark.parametrize("seq, top", [(64, 16)])
def test_the_sets_agree_within_the_band_and_the_values_given_the_sets(seq, top):
    cfg, params, tokens, sizes = make(seq=seq, sparse_topk=top)
    got, stats, g_got, sets = system(cfg, params, tokens)
    # (a) against the reference's OWN sets
    _, _, (_, _, own, scores) = reference(params, tokens, sizes, grads=False)
    own, scores = jnp.moveaxis(own, 0, 1), jnp.moveaxis(scores, 0, 1)  # [L, B, S, S]
    differ, outside = ref.sets_apart(scores, own, sets, EPS)
    assert int(outside) == 0 and int(differ) <= 4, (int(differ), int(outside))
    taken = np.asarray(jnp.sum(sets, axis=-1))
    assert (taken >= np.minimum(np.arange(seq) + 1, top)).all() and (np.asarray(sets) <= np.tri(seq, dtype=bool)).all()
    assert float(stats["selected_mean"]) == pytest.approx(taken.mean(), rel=1e-6)
    assert float(stats["selected_over_k"]) == (taken > np.minimum(np.arange(seq) + 1, top)).sum()
    # (b) the reference under the PROGRAM's sets
    want, g_want, (lm, terms, _, _) = reference(params, tokens, sizes, given=sets)
    assert float(got) == pytest.approx(float(want), rel=2e-6)
    assert float(stats["lm_loss"]) == pytest.approx(float(jnp.mean(lm)), rel=2e-6)
    np.testing.assert_allclose(stats["indexer_loss"], jnp.mean(terms, axis=0), rtol=2e-5)
    errs = grad_errors(g_got, g_want)
    assert max(jax.tree_util.tree_leaves(errs)) < RTOL, errs
    assert all(float(jnp.max(jnp.abs(g))) > 0 for g in jax.tree_util.tree_leaves(g_got))


@pytest.mark.parametrize("slip", ref.SLIPS)
def test_each_slip_fails_the_comparison_by_name(slip):
    """The reference with ONE thing slipped: the sets (a), the loss or — the two
    that only move where a gradient goes — a gradient leaf (b) is off by far
    more than the comparison allows."""
    cfg, params, tokens, sizes = make()
    got, _, g_got, sets = system(cfg, params, tokens)
    if slip in ("indexer_sees_gradient", "target_not_detached"):
        want, g_want, _ = reference(params, tokens, sizes, slip, given=sets)
        assert float(got) == pytest.approx(float(want), rel=2e-6)
        errs = grad_errors(g_got, g_want)
        moved = {k for k, e in errs["layers"].items() if e > 20 * RTOL}
        assert moved and not moved & set(INDEXER), (slip, errs)
        return
    if slip in ("no_selection", "topk_by_head", "indexer_unrotated"):  # another rule for the sets: nothing to hand over
        want, _, (_, _, theirs, scores) = reference(params, tokens, sizes, slip, grads=False)
        if slip != "topk_by_head":  # that one keeps the indexer's sets and ignores them in the core
            _, outside = ref.sets_apart(jnp.moveaxis(scores, 0, 1), jnp.moveaxis(theirs, 0, 1), sets, EPS)
            assert int(outside) > 100, slip
    else:
        want, _, _ = reference(params, tokens, sizes, slip, given=sets, grads=False)
    assert abs(float(got) - float(want)) > 20 * 2e-6 * abs(float(got)), (slip, float(got), float(want))


def test_the_selectors_leaves_learn_from_its_term_alone_and_the_others_from_the_next_token_loss_alone():
    cfg, params, tokens, _ = make()
    def both(p):  # one forward pass, a backward pass a part
        parts, vjp = jax.vjp(lambda p: [jnp.sum(loss_and_stats(p, tokens, cfg)[1][n]) for n in ("lm_loss", "indexer_loss")], p)
        return vjp([jnp.ones_like(parts[0]), jnp.zeros_like(parts[1])])[0], vjp([jnp.zeros_like(parts[0]), jnp.ones_like(parts[1])])[0]

    lm, term = jax.jit(both)(params)
    for name, g in lm["layers"].items():
        assert (float(jnp.max(jnp.abs(g))) == 0) == (name in INDEXER), name
    for name, g in term["layers"].items():
        assert (float(jnp.max(jnp.abs(g))) > 0) == (name in INDEXER), name
    assert all(float(jnp.max(jnp.abs(term[k]))) == 0 and float(jnp.max(jnp.abs(lm[k]))) > 0 for k in ("embed", "out", "final_norm"))


def test_up_to_k_positions_the_next_token_part_is_the_dense_causal_layers_bit_for_bit():
    """S <= k: every row selects every causal key, so the table is the causal mask."""
    cfg, params, tokens, sizes = make(seq=16)
    dense = TransformerConfig(dtype=jnp.float32, **{k: v for k, v in sizes.items() if k not in ("sparse_topk", "indexer_heads", "indexer_head_dim")})
    without = dict(params, layers={k: v for k, v in params["layers"].items() if k not in INDEXER})
    got = jax.jit(lambda p: loss_and_stats(p, tokens, cfg)[1]["lm_loss"])(params)
    assert float(got) == float(jax.jit(lambda p: loss_fn(p, tokens, dense))(without))
    assert bool(jnp.all(_sets(params, tokens, cfg) == jnp.tri(16, dtype=bool)))


def test_an_empty_batch_traces_and_runs_through_the_selector_the_held_experts_and_the_head(monkeypatch):
    """``benchmark/worker.py`` checks two sequences of the first batch; of a batch of ONE the second slice is empty:
    no kernel has a grid of none (the path is plain), the held expert layer has no row to sort, and the head — chunked
    at the cell's 16 384 x 18 992 logits a sequence — has nothing to chunk."""
    monkeypatch.setattr(T, "_LOSS_CHUNK_ELEMS", 1024)  # a full batch of this size takes the chunked head
    cfg, params, tokens, _ = make()
    assert T._attention_path(cfg, 64, 0, None, sparse=True)[:2] == ("plain", "an empty batch")
    loss, stats = jax.jit(lambda p, t: loss_and_stats(p, t, cfg))(params, tokens[:0])
    assert loss.shape == () and int(jnp.sum(stats["rows_held"])) == 0
    for impl in ("flash", "auto"):  # whatever path a full batch would take
        jax.eval_shape(lambda p, t: loss_fn(p, t, dataclasses.replace(cfg, attention_impl=impl)), params, tokens[:0])


def test_the_eight_shares_of_a_layer_add_up_to_the_uncut_layer():
    """Eight chips, 2 of 16 experts each, 4 chosen a row and renormalised: a
    share's layer output is ``x' + y_share`` with the selection, the core and
    the residual ``x'`` the same on every chip; counted once, with the eight
    shares' expert parts, they are the layer of the uncut reference. ONE
    compiled layer serves the eight: share s is share 0 of the layer whose
    router's columns are turned by 2·s (choosing and renormalising do not
    depend on an expert's column; ``LAYER`` itself holds share 1 everywhere else)."""
    sizes = dict(LAYER, n_layers=1, n_experts=16, top_k=4, n_experts_held=0, expert_share_index=0, moe_d_ff=8)
    whole_cfg = TransformerConfig(dtype=jnp.float32, **sizes)
    whole = {k: v[0, 0] for k, v in off_their_defaults(init_params(jax.random.PRNGKey(0), whole_cfg))["layers"].items()}
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 32, 32))
    parts, rows = [], []
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda w, x: ref.layer(w, x, sizes)[0])(whole, x[0])
        att = jax.jit(lambda w, x: ref._mix(w, ref._rms_norm(x, w["ln1"], 1e-6), sizes, None, None)[0])(whole, x[0])
        after_attention = x[0] + att @ whole["wo"]
        mine = TransformerConfig(dtype=jnp.float32, **dict(sizes, n_experts_held=2, expert_share_index=0))
        fn = jax.jit(T._make_layer_fn(mine, None, kind=("full", "experts")))
        for share in range(8):
            lp = {k: (v[share * 2 : (share + 1) * 2] if k in ("w_gate", "w_in", "w_out") else v) for k, v in whole.items()}
            y, aux = fn(x, dict(lp, router=jnp.roll(whole["router"], -2 * share, axis=1)))
            parts.append(y[0] - after_attention)
            rows.append(int(aux["held"]))
    np.testing.assert_allclose(after_attention + sum(parts), want, atol=1e-4)
    assert sum(rows) == 32 * 4


# -- training -----------------------------------------------------------------------------------------------------


def test_fused_steps_lower_both_parts_and_the_chain_of_grads_is_the_one_programs():
    cfg, params, tokens, _ = make()
    assert T.cuts_by_layer(cfg)
    mesh = make_mesh(MeshConfig(), devices=jax.devices()[:1])
    ts = TrainStep(cfg, optax.adamw(1e-2), mesh)
    t = ts.shard_batch(tokens)
    loss, grads = ts.grads(params, t)  # L + 2 programs: head, a layer's L times, tail
    assert len(grads) == cfg.n_layers + 2
    (loss1, _), one_program = _loss_and_grads(params, tokens, cfg)  # ``jax.grad`` of the one program
    assert float(loss) == pytest.approx(float(loss1), rel=1e-6)
    for (path, got), want in zip(jax.tree_util.tree_leaves_with_path(ts.grads_tree(grads)), jax.tree_util.tree_leaves(one_program)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6, err_msg=jax.tree_util.keystr(path))
    params = ts.init_params(jax.random.PRNGKey(0))
    opt = ts.init_opt(params)
    first = None
    for _ in range(12):
        loss, params, opt = ts.step(params, opt, t)
        first = first or dict(ts.last_stats)
    assert float(ts.last_stats["lm_loss"]) < float(first["lm_loss"]) - 0.5
    assert float(jnp.sum(ts.last_stats["indexer_loss"])) < float(jnp.sum(first["indexer_loss"]))
    assert {"lm_loss", "indexer_loss", "selected_mean", "selected_over_k", "sparse_tiles_live_share", "rows_held"} <= set(ts.last_stats)


def test_the_ft_loop_says_the_selector_on_loss_sync_and_nothing_of_another_model(monkeypatch):
    import types

    from torchft_tpu.parallel import ft

    said, on_span = [], {}
    monkeypatch.setattr(ft.tracing, "annotate", lambda name, **stats: said.append((name, stats)) or contextlib.nullcontext())
    span = types.SimpleNamespace(set=lambda **kw: on_span.update(kw))
    stats = dict(
        lm_loss=jnp.float32(4.5), indexer_loss=jnp.asarray([0.25, 0.5]), selected_mean=jnp.float32(15.5),
        selected_over_k=jnp.float32(3.0), sparse_tiles_live_share=jnp.asarray([1.0, 0.5]),
    )
    trainer = types.SimpleNamespace(_ts=types.SimpleNamespace(last_stats=stats))
    ft.FTTrainer._record_sparse_counters(trainer, 7, span)
    ((name, counters),) = said
    assert name == "sparse.counters" and counters == on_span
    assert counters == dict(step=7, lm_loss=4.5, indexer_loss=0.75, selected_mean=15.5, selected_over_k=3.0, sparse_tiles_live_share=0.75)
    trainer._ts.last_stats = {"tokens_per_expert": 0}
    ft.FTTrainer._record_sparse_counters(trainer, 8, span)
    assert len(said) == 1


# -- what it says and what it refuses ---------------------------------------------------------------------------------


def test_the_path_says_the_selector_and_how_the_selection_reaches_the_core(monkeypatch):
    from torchft_tpu import telemetry

    monkeypatch.setattr(T, "_PATHS_SAID", set())
    cfg, params, tokens, _ = make(seq=128, n_layers=1)
    before = len(telemetry.EVENTS.recent("attention_path"))
    jax.eval_shape(lambda p: loss_fn(p, tokens, dataclasses.replace(cfg, attention_impl="flash")), params)
    jax.eval_shape(lambda p: loss_fn(p, tokens, cfg), params)
    flash, plain = telemetry.EVENTS.recent("attention_path")[before:]
    assert (flash["impl"], flash["block_q"], flash["block_k"], flash["sparse_topk"], flash["indexer"]) == ("flash", 128, 128, 16, "4x8")
    assert flash["selection"].startswith("int8 tiles") and "bisected" in flash["threshold"]
    assert (plain["impl"], plain["selection"], plain["n_kv_heads"]) == ("plain", "a dense [B, S, S] table", 2)


@pytest.mark.parametrize("changes, message", [
    (dict(indexer_heads=0), "a key selector has all three"),
    (dict(indexer_head_dim=7), "pairs an even number of lanes"),
    (dict(sparse_topk=0), "a key selector has all three"),
    (dict(window=8, window_layers=(1,)), "a selection inside a band"),
    (dict(nope_layers=(2,)), "a selector a declared kind"),
    (dict(conv_layers=(1,)), r"with \['conv'\] layers"),
    (dict(gdn_layers=(1,), linear_head_dim=8, linear_n_heads=2), r"with \['gdn'\] layers"),
    (dict(mla_layers=(2,), kv_lora_rank=8, qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8), "a selector in front of a latent core"),
    (dict(diffusion_block=4), r"a selector over \[noised ; clean\]"),
    (dict(ut_steps=2), "a term a loop step over shared selector weights"),
    (dict(n_mtp_modules=1), "a selector of its own; its leaves and its term in the loss are missing"),
    (dict(pp=2), "does not cross pipeline stages"),
    (dict(attention_impl="chunked"), "a table of selected pairs a block is missing"),
    (dict(attn_output_gate=True), "from an ungated, un-normed branch"),
])
def test_what_the_configuration_refuses(changes, message):
    with pytest.raises(ValueError, match=message):
        TransformerConfig(**dict(LAYER, **changes))


def test_what_a_trace_refuses_the_ring_and_a_mesh():
    cfg, _, _, _ = make()
    with pytest.raises(ValueError, match="a threshold agreed across the ring"):
        T._attention_path(cfg, 64, 1, make_mesh(MeshConfig(sp=2), devices=jax.devices()[:2]), sparse=True)
    with pytest.raises(ValueError, match="their manual region over dp / fsdp / tp"):
        T._attention_path(cfg, 64, 2, make_mesh(MeshConfig(dp=2), devices=jax.devices()[:2]), sparse=True)


def test_without_a_selector_nothing_of_it_is_traced_or_held():
    plain = {k: v for k, v in LAYER.items() if k not in ("sparse_topk", "indexer_heads", "indexer_head_dim")}
    cfg = TransformerConfig(dtype=jnp.float32, **plain)
    params = init_params(jax.random.PRNGKey(0), cfg)
    assert not set(INDEXER) & set(params["layers"]) and not set(INDEXER) & set(T.param_specs(cfg)["layers"])
    tokens = jnp.zeros((1, 16), jnp.int32)
    assert set(jax.eval_shape(lambda p: loss_and_stats(p, tokens, cfg), params)[1]) == {"tokens_per_expert", "balance_loss", "rows_held", "window_live_share"}
    with_it = TransformerConfig(dtype=jnp.float32, **LAYER)
    assert set(INDEXER) <= set(T.param_specs(with_it)["layers"])
    assert init_params(jax.random.PRNGKey(0), with_it)["layers"]["idx_wq"].shape == (1, 2, 32, 32)


def test_three_equal_position_streams_under_mrope_are_the_programs_rotation():
    """On text the temporal, height and width positions of a token are equal,
    and ``mrope_section`` [16, 24, 24] then gives every lane pair i the angle
    ``t·theta^(-2i/128)``: the rotate-half rotation the program applies."""
    from torchft_tpu.ops.layers import rotary_embed, yarn_inv_freq

    t = jnp.arange(40)
    angle = ref.mrope_angles(jnp.stack([t, t, t]), [16, 24, 24], 1e7, 128)
    np.testing.assert_allclose(angle, t[:, None] * jnp.asarray(yarn_inv_freq(128, 1e7))[None, :], rtol=1e-6)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 40, 2, 128))
    a, b = x[..., :64], x[..., 64:]
    cos, sin = jnp.cos(angle)[None, :, None], jnp.sin(angle)[None, :, None]
    by_hand = jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)
    np.testing.assert_allclose(rotary_embed(x, t, inv_freq=yarn_inv_freq(128, 1e7)), by_hand, atol=1e-5)
    apart = ref.mrope_angles(jnp.stack([t, 2 * t, 3 * t]), [16, 24, 24], 1e7, 128)  # streams that differ: each section its own
    np.testing.assert_allclose(apart[:, 16:40], 2 * angle[:, 16:40], rtol=1e-6)
    np.testing.assert_allclose(apart[:, 40:], 3 * angle[:, 40:], rtol=1e-6)
