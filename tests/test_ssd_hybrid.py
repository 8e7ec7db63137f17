"""A hybrid of Mamba-2 state-space mixers and unrotated softmax layers over a
tied table under Granite's four multipliers: ``ops/kda.ssd_chunked`` against
the recurrence, the mixer's block scan, the program against the benchmark's
plain reference (``benchmark/reference/granite_hybrid_decoder.py``, loaded by
path: one copy) with each of ``benchmark/check_granite.py``'s ten slips failing,
the tied table's gradient, the multipliers at 1, training, and what is refused.
CPU, float32, tiny sizes: every test ends in seconds."""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from torchft_tpu.models import transformer as T
from torchft_tpu.models.transformer import TransformerConfig, init_params, loss_and_stats, loss_fn
from torchft_tpu.ops.kda import ssd_chunked, ssd_core, ssd_recurrent
from torchft_tpu.parallel.mesh import MeshConfig, make_mesh
from torchft_tpu.parallel.train_step import TrainStep

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load(*parts):
    path = os.path.join(ROOT, "benchmark", *parts)
    spec = importlib.util.spec_from_file_location(parts[-1][:-3] + "_under_test", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


REF = load("reference", "granite_hybrid_decoder.py")
CHECK = load("check_granite.py")

# ten layers in the published order — five ssd, one unrotated softmax layer, four ssd — at tiny widths
SIZES = dict(
    vocab_size=64, d_model=16, n_layers=10, n_heads=4, n_kv_heads=2, head_dim=8, d_ff=32,
    ssd_layers=(1, 2, 3, 4, 5, 7, 8, 9, 10), nope_layers=(6,), ssd_state_dim=8, ssd_head_dim=4, ssd_n_heads=8, ssd_expand=2,
    embed_scale=12.0, residual_scale=0.22, attn_scale=1 / 16, logits_scale=8.0, tie_embeddings=True, norm_eps=1e-5,
)


def make(seq=37, batch=2, **over):
    sizes = {**SIZES, **over}
    cfg = TransformerConfig(dtype=jnp.float32, **sizes)
    params = init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (batch, seq), 0, cfg.vocab_size)
    return cfg, params, tokens, sizes


def worst(got, want):
    """The largest difference of two trees' leaves, each relative to the wanted leaf's largest entry."""
    rel = jax.tree_util.tree_map(lambda a, b: float(jnp.max(jnp.abs(a - b)) / (jnp.max(jnp.abs(b)) + 1e-12)), got, want)
    return max(jax.tree_util.tree_leaves(rel))


# -- the core ----------------------------------------------------------------------------------------------


@pytest.mark.parametrize("chunk", [4, 8])
@pytest.mark.parametrize("nats", [0.3, 16.0], ids=["mild", "sixteen_nats"])
def test_the_chunked_rule_is_the_recurrence_in_output_state_and_every_gradient(chunk, nats):
    """21 positions (no multiple of either chunk), an initial state, decays up to ``nats`` a position."""
    r = np.random.RandomState(0)
    b, s, h, p, n = 2, 21, 3, 4, 8
    q, k = (jnp.asarray(r.randn(b, s, n), jnp.float32) for _ in range(2))
    v = jnp.asarray(r.randn(b, s, h, p), jnp.float32)
    g = -nats * jnp.asarray(r.rand(b, s, h), jnp.float32)
    s0 = jnp.asarray(r.randn(b, h, n, p), jnp.float32)

    def scalar(rule):
        def of(q, k, v, g, s0):
            o, end = rule(q, k, v, g, s0)
            return jnp.sum(o * jnp.cos(o)) + jnp.sum(end * end), (o, end)

        return jax.value_and_grad(of, argnums=(0, 1, 2, 3, 4), has_aux=True)

    with jax.default_matmul_precision("highest"):
        (_, (o, end)), grads = scalar(lambda q, k, v, g, s0: ssd_chunked(q, k, v, g, chunk=chunk, initial_state=s0))(q, k, v, g, s0)
        (_, (o_ref, end_ref)), grads_ref = scalar(ssd_recurrent)(q, k, v, g, s0)
    assert o.shape == (b, s, h, p) and end.shape == (b, h, n, p)
    np.testing.assert_allclose(o, o_ref, atol=2e-5)
    np.testing.assert_allclose(end, end_ref, atol=2e-5)
    assert worst(grads, grads_ref) < 2e-5


def test_the_block_scan_carries_the_state_and_the_taps(monkeypatch):
    """Three blocks of 8 positions against one call over all 24: the same output and the same final state."""
    cfg, params, _, _ = make()
    lp = jax.tree_util.tree_map(lambda a: a[0], params["lead"]["ssd.dense"])
    h = jax.random.normal(jax.random.PRNGKey(2), (2, 24, cfg.d_model), jnp.float32)
    with jax.default_matmul_precision("highest"):
        whole, said = T._mix_ssd(cfg, lp, h)
        monkeypatch.setattr(T, "_KDA_BLOCK", 8)
        blocks, said_blocks = T._mix_ssd(cfg, lp, h)
    np.testing.assert_allclose(blocks, whole, atol=2e-6)
    assert float(said_blocks["ssd_state_rms"]) == pytest.approx(float(said["ssd_state_rms"]), rel=1e-5)
    assert float(said_blocks["ssd_decay_min"]) == float(said["ssd_decay_min"])


# -- the program against the reference -----------------------------------------------------------------------------


def program_and_reference(cfg, params, tokens, sizes):
    with jax.default_matmul_precision("highest"):
        got = jax.jit(jax.value_and_grad(lambda p: loss_fn(p, tokens, cfg)))(params)
        want = jax.jit(jax.value_and_grad(lambda p: jnp.mean(REF.per_sequence_loss(p, tokens, sizes))))(params)
    return got, want


@pytest.fixture(scope="module")
def programs():
    cfg, params, tokens, sizes = make()
    return (cfg, params, tokens, sizes), program_and_reference(cfg, params, tokens, sizes)


def test_the_program_is_the_reference_in_loss_and_every_gradient_leaf(programs):
    (cfg, params, _, _), ((loss, grads), (loss_ref, grads_ref)) = programs
    assert T.layer_pattern(cfg) == (6, (("ssd", "dense"),))  # six layers one by one, the last four scanned
    assert abs(float(loss) - float(loss_ref)) < 2e-6
    assert jax.tree_util.tree_structure(grads) == jax.tree_util.tree_structure(params)
    assert worst(grads, grads_ref) < 2e-5


@pytest.mark.parametrize("name", sorted(CHECK.SLIPS))
def test_each_slip_of_the_reference_fails_by_twenty_times_the_agreement(programs, name):
    """The loss agrees to 2e-6 and the gradients to 2e-5 of a leaf's largest entry: a slip moves one of them 20 times that."""
    (_, params, tokens, sizes), ((loss, grads), _) = programs
    with CHECK.slipped(REF, name, sizes) as slipped_sizes, jax.default_matmul_precision("highest"):
        loss_s, grads_s = jax.jit(jax.value_and_grad(lambda p: jnp.mean(REF.per_sequence_loss(p, tokens, slipped_sizes))))(params)
    assert max(abs(float(loss) - float(loss_s)) / 2e-6, worst(grads, grads_s) / 2e-5) >= 20.0


def test_the_chunked_head_scales_its_logits_and_its_cotangent(programs, monkeypatch):
    (cfg, params, tokens, _), ((loss, grads), _) = programs
    monkeypatch.setattr(T, "_LOSS_CHUNK_ELEMS", 64 * 8)  # five chunks of eight positions
    with jax.default_matmul_precision("highest"):
        loss_c, grads_c = jax.jit(jax.value_and_grad(lambda p: loss_fn(p, tokens, cfg)))(params)
    assert abs(float(loss) - float(loss_c)) < 2e-6 and worst(grads_c, grads) < 2e-5


# -- the tied table and the multipliers ---------------------------------------------------------------------


def test_the_tied_tables_gradient_is_the_sum_of_an_untied_pairs():
    cfg, params, tokens, _ = make()
    assert "out" not in params and "out" not in T.param_specs(cfg)
    untied_cfg = TransformerConfig(dtype=jnp.float32, **{**SIZES, "tie_embeddings": False})
    untied = {**params, "out": params["embed"].T}
    assert set(init_params(jax.random.PRNGKey(0), untied_cfg)) == set(untied)
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.jit(jax.value_and_grad(lambda p: loss_fn(p, tokens, cfg)))(params)
        loss_u, grads_u = jax.jit(jax.value_and_grad(lambda p: loss_fn(p, tokens, untied_cfg)))(untied)
    assert float(loss) == float(loss_u)
    np.testing.assert_allclose(grads["embed"], grads_u["embed"] + grads_u["out"].T, rtol=1e-5, atol=1e-8)
    assert worst({k: v for k, v in grads.items() if k != "embed"}, {k: v for k, v in grads_u.items() if k not in ("embed", "out")}) < 1e-6


def test_the_eight_vocabulary_slices_logits_side_by_side_are_the_uncut_models_logits():
    """Eight chips, eight of 64 rows of the ONE table each (the cell's share: 12 544 of 100 352). Ids drawn from the
    rows slice 3 holds: the slice's model — its own rows embed and unembed, ``logits_scale`` and all — gives the uncut
    model's logits at its rows, and the eight slices' heads on that hidden state, side by side, are the uncut logits
    row for row: what the cell's chip leaves out of the softmax is what the other seven hold."""
    cfg, params, _, _ = make()
    held, k = cfg.vocab_size // 8, 3
    local = jax.random.randint(jax.random.PRNGKey(5), (2, 24), 0, held)
    slice_cfg = TransformerConfig(dtype=jnp.float32, **{**SIZES, "vocab_size": held})
    rows = lambda j: {**params, "embed": params["embed"][j * held : (j + 1) * held]}
    with jax.default_matmul_precision("highest"):
        uncut = jax.jit(lambda p, t: T.forward(p, t, cfg))(params, local + k * held)
        mine = jax.jit(lambda p, t: T.forward(p, t, slice_cfg))(rows(k), local)
        hidden, _ = jax.jit(lambda p, t: T._hidden_states(p, t, slice_cfg))(rows(k), local)
        side_by_side = jnp.concatenate(
            [T._scaled_logits(slice_cfg, hidden @ T._out_table(rows(j), slice_cfg)) for j in range(8)], axis=-1
        )
    np.testing.assert_allclose(mine, uncut[..., k * held : (k + 1) * held], atol=2e-6)
    np.testing.assert_allclose(side_by_side, uncut, atol=2e-6)


DENSE = dict(vocab_size=64, d_model=16, n_layers=2, n_heads=2, head_dim=16, d_ff=32)


@pytest.mark.parametrize(
    "field", [dict(embed_scale=1.0), dict(residual_scale=1.0), dict(attn_scale=0.25), dict(logits_scale=1.0)], ids=lambda f: next(iter(f)),
)
def test_each_multiplier_at_one_gives_todays_programs_loss_bit_for_bit(field):
    """On a dense configuration: 1, 1, ``head_dim**-0.5`` and 1 are what the program always did."""
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 24), 0, 64)
    today = TransformerConfig(dtype=jnp.float32, **DENSE)
    params = init_params(jax.random.PRNGKey(0), today)
    stated = TransformerConfig(dtype=jnp.float32, **DENSE, **field)
    assert float(jax.jit(loss_fn, static_argnums=2)(params, tokens, stated)) == float(jax.jit(loss_fn, static_argnums=2)(params, tokens, today))


def test_each_multiplier_off_one_moves_the_loss():
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 24), 0, 64)
    today = TransformerConfig(dtype=jnp.float32, **DENSE)
    params = init_params(jax.random.PRNGKey(0), today)
    base = float(loss_fn(params, tokens, today))
    for field in (dict(embed_scale=12.0), dict(residual_scale=0.22), dict(attn_scale=1 / 64), dict(logits_scale=8.0)):
        assert abs(float(loss_fn(params, tokens, TransformerConfig(dtype=jnp.float32, **DENSE, **field))) - base) > 1e-4, field


# -- training, statistics, the chain ----------------------------------------------------------------------------


def test_twenty_fused_steps_on_one_batch_lower_the_loss_and_say_the_regime():
    cfg, _, tokens, _ = make()
    mesh = make_mesh(MeshConfig(), devices=jax.devices()[:1])
    ts = TrainStep(cfg, optax.adamw(1e-2), mesh)
    params = ts.init_params(jax.random.PRNGKey(0))
    assert "out" not in params
    opt = ts.init_opt(params)
    losses = []
    for _ in range(20):
        loss, params, opt = ts.step(params, opt, ts.shard_batch(tokens))
        losses.append(float(loss))
    assert losses[-1] < losses[0] - 0.5, losses
    stats = ts.last_stats
    assert stats["ssd_decay_min"].shape == stats["ssd_dt_mean"].shape == (9,) and stats["ssd_state_rms"].shape == ()
    assert float(jnp.max(stats["ssd_decay_min"])) < 0 < float(jnp.min(stats["ssd_dt_mean"])) and float(stats["ssd_state_rms"]) > 0


def test_the_core_says_its_form_and_chunk_once(monkeypatch):
    from torchft_tpu import telemetry

    monkeypatch.setattr(T, "_PATHS_SAID", set())
    cfg, params, tokens, _ = make()
    before = len(telemetry.EVENTS.recent("ssd_core_path"))
    for _ in range(2):
        loss_and_stats(params, tokens, cfg)
    said = telemetry.EVENTS.recent("ssd_core_path")[before:]
    assert len(said) == 1
    assert {k: said[0][k] for k in ("core", "chunk", "heads", "state", "block")} == dict(core="jax.numpy", chunk=37, heads=8, state=8, block=37)
    # ``core`` is what ``ops/kda.ssd_core`` answers for the block's call — a state 8 wide is no lane tile: the kernels'
    # widths, and the event saying ``ssd_kernel``, are ``tests/test_ssd_kernel.py``'s
    of = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)  # noqa: E731
    assert said[0]["core"] == ssd_core(of(2, 37, 8), of(2, 37, 8), of(2, 37, 8, 4), 37)
    pattern = telemetry.EVENTS.recent("layer_pattern")[-1]
    assert pattern["lead"].count("ssd.dense") == 5 and "nope.dense" in pattern["lead"] and pattern["tied"] is True


def test_a_stack_of_ssd_layers_alone_cuts_by_layer_and_a_tied_table_does_not():
    """``TrainStep.grads`` as L + 2 programs over state-space layers of one kind: the pieces are the one
    program's gradients. A tied table stays one program, and ``grads_chain`` refuses it by name."""
    one_kind = {**SIZES, "n_layers": 3, "ssd_layers": (1, 2, 3), "nope_layers": (), "tie_embeddings": False}
    cfg = TransformerConfig(dtype=jnp.float32, **one_kind)
    assert T.cuts_by_layer(cfg)
    mesh = make_mesh(MeshConfig(), devices=jax.devices()[:1])
    ts = TrainStep(cfg, optax.sgd(1e-2), mesh)
    params = ts.init_params(jax.random.PRNGKey(0))
    t = ts.shard_batch(jax.random.randint(jax.random.PRNGKey(1), (2, 24), 0, 64))
    loss, grads = ts.grads(params, t)
    assert len(grads) == cfg.n_layers + 2
    with jax.set_mesh(mesh):
        loss1, one_program, _ = ts._value_and_grad(params, t)
    assert float(loss) == pytest.approx(float(loss1), rel=1e-6)
    assert worst(ts.grads_tree(grads), one_program) < 1e-5
    tied = TransformerConfig(dtype=jnp.float32, **{**one_kind, "tie_embeddings": True})
    assert not T.cuts_by_layer(tied) and not T.cuts_by_layer(make()[0])
    with pytest.raises(ValueError, match="tie_embeddings"):
        T.grads_chain(tied)


# -- what is refused, by name -------------------------------------------------------------------------------


@pytest.mark.parametrize(
    "over, says",
    [
        (dict(ssd_n_groups=2), "ssd_n_groups=2"),
        (dict(ssd_n_heads=4), "is not ssd_expand=2 x d_model=16"),
        (dict(ssd_state_dim=0), "all three sizes"),
        (dict(pp=2), "pp=2 with ssd layers"),
        (dict(ut_steps=2), "ut_steps=2 with"),
        (dict(n_mtp_modules=1), "multi-token-prediction module"),
        (dict(diffusion_block=4), "diffusion_block=4 with"),
        (dict(ssd_layers=(1, 6)), "each at most once"),
        (dict(logits_scale=0.0), "logits_scale=0.0"),
    ],
    ids=lambda x: x if isinstance(x, str) else "",
)
def test_what_a_state_space_stack_cannot_run_with_is_refused_by_name(over, says):
    with pytest.raises(ValueError, match=says):
        TransformerConfig(dtype=jnp.float32, **{**SIZES, **over})


@pytest.mark.parametrize(
    "over, says",
    [
        (dict(tie_embeddings=True, pp=2), "pp=2 with embed_scale"),
        (dict(logits_scale=8.0, pp=2), "pp=2 with embed_scale"),
        (dict(embed_scale=12.0, n_mtp_modules=1), "with a multi-token-prediction module or a looped"),
        (dict(residual_scale=0.22, ut_steps=2), "with a multi-token-prediction module or a looped"),
    ],
    ids=["tied_pp", "logits_pp", "embed_mtp", "residual_loop"],
)
def test_where_the_multipliers_and_the_tied_table_are_not_applied_is_refused_by_name(over, says):
    with pytest.raises(ValueError, match=says):
        TransformerConfig(dtype=jnp.float32, **{**DENSE, "n_layers": 2, **over})


def test_a_sequence_shard_of_a_state_space_layer_is_refused_by_name():
    cfg, _, _, _ = make()
    mesh = make_mesh(MeshConfig(sp=2), devices=jax.devices()[:2])
    with pytest.raises(ValueError, match="sp=2 with a ssd layer"):
        T._make_layer_fn(cfg, mesh, kind=("ssd", "dense"))
