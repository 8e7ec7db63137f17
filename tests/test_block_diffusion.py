"""Block-diffusion training — a sequence of S clean ids run as 2·S rows
``[noised ; clean]`` under the staircase mask, positions that repeat, a loss of
``m/t`` on the noised rows alone — through the one transformer, against the
plain reference (``benchmark/reference/sdar_moe_decoder.py``, loaded by path:
one copy) and against the DEFINITION, block by block.

Tolerance of the float32 comparisons with the reference: both sides compute in
float32 on the CPU (matmuls at "highest"); what differs is the order of the
sums — a group's heads repeated against a grouped einsum, rows sorted by expert
and a grouped matmul against every held expert over every row, a batch against
one sequence at a time. RTOL is far above what that moves and far below what one
slipped term does (the slips' test shows each).
"""

import contextlib
import dataclasses
import importlib.util
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from torchft_tpu.models import transformer as T
from torchft_tpu.models.transformer import TransformerConfig, init_params, loss_and_stats, loss_fn
from torchft_tpu.ops.attention import attention, block_diffusion_mask
from torchft_tpu.parallel.mesh import MeshConfig, make_mesh
from torchft_tpu.parallel.train_step import TrainStep

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RTOL = 5e-5


def _load_reference():
    path = os.path.join(ROOT, "benchmark", "reference", "sdar_moe_decoder.py")
    spec = importlib.util.spec_from_file_location("sdar_moe_decoder_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load_reference()

# the cell's layer at tiny widths: q and k normed head by head, rotated over the whole head, 16 experts of which 4 are
# held (share 1: experts 4..7) and 4 are chosen a row, the softmax over all 16 renormalised over the chosen
LAYER = dict(
    vocab_size=64, d_model=32, n_layers=2, n_heads=4, head_dim=16, d_ff=16, moe_d_ff=16, norm_eps=1e-6,
    qk_norm=True, qk_norm_per_head=True, rope_pairing="half", rope_theta=1000000.0,
    n_experts=16, n_experts_held=4, expert_share_index=1, top_k=4, router_gate="softmax", router_renormalize=True,
    diffusion_block=4, diffusion_t_min=1e-3, diffusion_seed=7,
)


def off_their_defaults(params):
    """Norm weights off 1 and a router with loads that differ: or a norm
    without its weight could pass."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(params)
    out = []
    for i, (path, a) in enumerate(leaves):
        name = path[-1].key
        if name in ("ln1", "ln2", "final_norm", "q_norm", "k_norm"):
            a = a * (1 + 0.3 * jnp.sin(jnp.arange(a.size, dtype=jnp.float32).reshape(a.shape) + i))
        elif name == "router":
            a = a * 3.0
        out.append(a)
    return jax.tree_util.tree_unflatten(treedef, out)


def make(seq=32, **over):
    sizes = dict(LAYER, **over)
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


def reference(params, tokens, sizes, slip=None):
    def loss(p, t):
        with jax.default_matmul_precision("highest"):
            return jnp.mean(ref.per_sequence_loss(p, t, sizes, slip))

    return jax.jit(jax.value_and_grad(loss))(params, tokens)


# -- the mask ------------------------------------------------------------------------------------------

# S 8, D 4: rows 0-7 noised (blocks 0, 1), rows 8-15 clean. Written by hand from the three rules.
TABLE = """
1111 0000 0000 0000
1111 0000 0000 0000
1111 0000 0000 0000
1111 0000 0000 0000
0000 1111 1111 0000
0000 1111 1111 0000
0000 1111 1111 0000
0000 1111 1111 0000
0000 0000 1111 0000
0000 0000 1111 0000
0000 0000 1111 0000
0000 0000 1111 0000
0000 0000 1111 1111
0000 0000 1111 1111
0000 0000 1111 1111
0000 0000 1111 1111
"""


def test_the_mask_at_s8_d4_is_the_table_written_by_hand():
    want = np.array([[int(c) for c in line.replace(" ", "")] for line in TABLE.split("\n") if line], bool)
    assert want.shape == (16, 16)
    np.testing.assert_array_equal(np.asarray(block_diffusion_mask(8, 4)), want)
    row = jnp.arange(16)
    np.testing.assert_array_equal(np.asarray(ref.seen(row[:, None], row[None, :], 8, 4)), want)
    # every row sees a key, a clean row no noised one, and S² + 4·S pairs are seen of the (2·S)²
    assert want.any(axis=1).all() and not want[8:, :8].any() and want.sum() == 8 * 8 + 4 * 8


@pytest.mark.parametrize("seq, block", [(8, 4), (32, 4), (32, 8), (64, 1)])
def test_the_pairs_seen_are_what_the_count_says(seq, block):
    """A noised row sees D noised keys and the clean keys before its block, a
    clean row its block's and those before: S·D + D·(n(n-1)/2)·D + D·(n(n+1)/2)·D
    = S² + S·D pairs with n = S/D blocks — (S² + 4·S) at the cell's D 4, of (2·S)²."""
    assert int(block_diffusion_mask(seq, block).sum()) == seq * seq + seq * block


# -- the 2·S formulation against the definition, block by block -------------------------------------------


def plain_forward(params, ids, positions, mask, sizes):
    """Logits [R, V] of the rows ``ids`` at ``positions`` under ``mask``
    [R, R], from the reference's own pieces: one plain forward pass."""
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    heads, hd, eps = sizes["n_heads"], sizes["head_dim"], sizes["norm_eps"]
    kv = sizes.get("n_kv_heads") or heads
    x = f32(params["embed"])[ids]
    r = len(ids)
    for i in range(sizes["n_layers"]):
        w = {k: f32(v[0, i]) for k, v in params["layers"].items()}
        h = ref._rms_norm(x, w["ln1"], eps)
        q = ref._rotate(ref._rms_norm((h @ w["wq"]).reshape(r, heads, hd), w["q_norm"], eps), positions, sizes["rope_theta"])
        k = ref._rotate(ref._rms_norm((h @ w["wk"]).reshape(r, kv, hd), w["k_norm"], eps), positions, sizes["rope_theta"])
        k, v = (jnp.repeat(a, heads // kv, axis=1) for a in (k, (h @ w["wv"]).reshape(r, kv, hd)))
        scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(jnp.float32(hd))
        probs = jax.nn.softmax(jnp.where(mask[None], scores, -jnp.inf), axis=-1)
        x = x + jnp.einsum("hqk,khd->qhd", probs, v).reshape(r, heads * hd) @ w["wo"]
        h2 = ref._rms_norm(x, w["ln2"], eps)
        x = x + ref._experts(w, h2, ref.gate(w, h2, sizes), sizes)
    return ref._rms_norm(x, f32(params["final_norm"]), eps) @ f32(params["out"])


@pytest.mark.parametrize("block, kv_heads", [(4, 0), (8, 2)])
def test_the_two_s_rows_are_the_definition_block_by_block(block, kv_heads):
    """The definition: block b of the noised sequence is predicted from the
    CLEAN blocks before it and its own noised ids — a plain forward over
    ``[x^{<b} ; x̃^b]`` in which the clean prefix is block-causal and the noised
    block sees all of it and itself in both directions. One pass over 2·S rows
    under the staircase gives every block's logits at once, and the same
    weighted loss, to 2e-6."""
    cfg, params, tokens, sizes = make(seq=16, diffusion_block=block, n_kv_heads=kv_heads)
    forward = jax.jit(lambda ids, pos, mask: plain_forward(params, ids, pos, mask, sizes))
    seq = tokens[0]
    s = len(seq)
    with jax.default_matmul_precision("highest"):
        t, m = ref.noise(seq, sizes)
        noised = jnp.where(m, cfg.mask_id, seq)
        h, _, _ = ref.hidden(params, seq, sizes)
        at_once = h @ params["out"]
        total = 0.0
        for b in range(s // block):
            lo, hi = b * block, (b + 1) * block
            ids = jnp.concatenate([seq[:lo], noised[lo:hi]])
            pos = jnp.arange(hi)
            blk = pos // block
            logits = forward(ids, pos, blk[None, :] <= blk[:, None])[lo:]
            np.testing.assert_allclose(at_once[lo:hi], logits, atol=2e-5, rtol=2e-6)
            nll = -jnp.take_along_axis(jax.nn.log_softmax(logits, axis=-1), seq[lo:hi, None], axis=-1)[:, 0]
            total += float(jnp.sum(m[lo:hi] / t[b] * nll))
        want = total / s
        assert float(ref.sequence_loss(params, seq, sizes)) == pytest.approx(want, rel=2e-6)
        assert float(loss_fn(params, tokens[:1], cfg)) == pytest.approx(want, rel=2e-6)


# -- the program against the reference -------------------------------------------------------------------


@pytest.mark.parametrize("block", [4, 8])
@pytest.mark.parametrize("kv_heads", [0, 1])  # groups of 1 and of 4
def test_loss_and_every_gradient_leaf_agree_with_the_reference(block, kv_heads):
    cfg, params, tokens, sizes = make(diffusion_block=block, n_kv_heads=kv_heads)
    got, g_got = system(cfg, params, tokens)
    want, g_want = reference(params, tokens, sizes)
    assert float(got) == pytest.approx(float(want), rel=2e-6)
    errs = grad_errors(g_got, g_want)
    assert max(jax.tree_util.tree_leaves(errs)) < RTOL, errs
    assert all(float(jnp.max(jnp.abs(g))) > 0 for g in jax.tree_util.tree_leaves(g_got))


@pytest.mark.parametrize("slip", ref.SLIPS)
def test_each_slip_fails_the_comparison(slip):
    """The reference with ONE thing slipped moves the loss by far more than the
    comparison allows (2e-6 of it), and some gradient leaf by more than RTOL."""
    cfg, params, tokens, sizes = make()
    got, g_got = system(cfg, params, tokens)
    want, g_want = reference(params, tokens, sizes, slip)
    assert abs(float(got) - float(want)) > 20 * 2e-6 * abs(float(got)), (slip, float(got), float(want))
    assert max(jax.tree_util.tree_leaves(grad_errors(g_got, g_want))) > 20 * RTOL


def test_per_sequence_loss_is_what_the_worker_compares():
    cfg, params, tokens, sizes = make()
    with jax.default_matmul_precision("highest"):
        per = ref.per_sequence_loss(params, tokens, sizes)
        mine = [float(loss_fn(params, tokens[i : i + 1], cfg)) for i in range(2)]
    np.testing.assert_allclose(per, mine, rtol=2e-6)


def test_without_remat_and_under_the_other_policy_the_numbers_are_the_same():
    cfg, params, tokens, sizes = make()
    want, g_want = reference(params, tokens, sizes)
    for changes in (dict(remat=False), dict(remat_policy="dots")):
        got, g_got = system(dataclasses.replace(cfg, **changes), params, tokens)
        assert float(got) == pytest.approx(float(want), rel=2e-6)
        assert max(jax.tree_util.tree_leaves(grad_errors(g_got, g_want))) < RTOL


def test_a_leading_dense_layer_runs_the_pattern_path():
    """``n_dense_layers`` makes the stack a declared pattern (a dense layer,
    then expert layers): the 2·S rows, the positions and the noised half's
    final norm hold on that path too. Held against the program of one kind by
    the loss the head reads off a zeroed stack: with ``wo`` and the experts'
    output zero both are the embedding's rows under the final norm."""
    cfg, params, tokens, _ = make()
    mixed = dataclasses.replace(cfg, n_dense_layers=1)
    p = init_params(jax.random.PRNGKey(3), mixed)
    loss, stats = jax.jit(lambda p: loss_and_stats(p, tokens, mixed))(p)
    assert np.isfinite(float(loss)) and stats["tokens_per_expert"].shape == (1, 16)
    assert int(stats["tokens_per_expert"].sum()) == 2 * 2 * 32 * 4  # one expert layer, 2·S rows a sequence


# -- the share tied to the model ----------------------------------------------------------------------------


def test_the_eight_shares_of_a_layer_add_up_to_the_uncut_layer():
    """Eight chips, 16 of 128 experts each, 8 chosen a row and renormalised, on
    the 2·S rows of a sequence: a share's layer output is ``x' + y_share`` with
    the attention under the staircase and the residual ``x'`` the same on every
    chip; counted once, with the eight shares' expert parts, they are the layer
    of the uncut reference. The router counts over all 128 on every share, and
    every row-expert pair lands on exactly one."""
    sizes = dict(LAYER, n_layers=1, n_experts=128, top_k=8, n_experts_held=0, expert_share_index=0, moe_d_ff=8)
    whole_cfg = TransformerConfig(dtype=jnp.float32, **sizes)
    whole = {k: v[0, 0] for k, v in off_their_defaults(init_params(jax.random.PRNGKey(0), whole_cfg))["layers"].items()}
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 32, 32))  # one sequence: 2·S rows, S 16
    parts, rows = [], []
    with jax.default_matmul_precision("highest"):
        want = ref.layer(whole, x[0], sizes)
        after_attention = x[0] + ref._attention(whole, ref._rms_norm(x[0], whole["ln1"], 1e-6), sizes, None)
        for share in range(8):
            mine = dict(sizes, n_experts_held=16, expert_share_index=share)
            lp = {k: (v[share * 16 : (share + 1) * 16] if k in ("w_gate", "w_in", "w_out") else v) for k, v in whole.items()}
            fn = T._make_layer_fn(TransformerConfig(dtype=jnp.float32, **mine), None, kind=("full", "experts"))
            y, aux = jax.jit(fn)(x, lp)
            parts.append(y[0] - after_attention)
            rows.append(int(aux["held"]))
            np.testing.assert_allclose(y[0], ref.layer(lp, x[0], mine), atol=3e-5)
            assert int(jnp.sum(aux["counts"])) == 32 * 8 and aux["counts"].shape == (128,)
    np.testing.assert_allclose(after_attention + sum(parts), want, atol=1e-4)
    assert sum(rows) == 32 * 8 and len(set(rows)) > 1


# -- the kernel through the model ---------------------------------------------------------------------------


def test_the_model_through_the_kernel_is_the_model_through_the_dense_mask():
    """S 128, heads of one lane tile, groups of 2: ``attention_impl`` "flash"
    runs the staircase kernel (interpreted here) over 256 rows at tiles of 128."""
    over = dict(head_dim=128, n_heads=2, n_kv_heads=1, n_layers=1)
    cfg, params, tokens, sizes = make(seq=128, **over)
    want, g_want = system(cfg, params, tokens[:1])
    got, g_got = system(dataclasses.replace(cfg, attention_impl="flash"), params, tokens[:1])
    assert float(got) == pytest.approx(float(want), rel=2e-6)
    assert max(jax.tree_util.tree_leaves(grad_errors(g_got, g_want))) < RTOL


def test_the_live_tiles_of_the_cell():
    """512 x 512 at S 8192: n = 16 tiles a half, n² + 2n = 288 visited of the
    1024 of the square, for S² + 4S pairs = 256.1 tiles' worth."""
    assert T._live_tiles(16384, (512, 512)) == (288, 1024)
    assert T._live_tiles(256, (128, 128)) == (1 * 1 + 2 * 1, 4)
    assert T._live_tiles(512, (128, 128)) == (2 * 2 + 2 * 2, 16)
    assert T._live_tiles(64, None) == (0, 0)


# -- the noise ----------------------------------------------------------------------------------------------


def test_the_noise_is_a_function_of_the_ids_and_the_seed_and_the_references_bit_for_bit():
    cfg, _, _, sizes = make(seq=4096)
    tokens = jnp.asarray(np.random.default_rng(5).integers(0, 64, (3, 4096)), jnp.int32)
    t, m = T._diffusion_noise(tokens, cfg)
    t2, m2 = jax.jit(lambda x: T._diffusion_noise(x, cfg))(tokens)  # compiled as the step compiles it
    np.testing.assert_array_equal(np.asarray(t), np.asarray(t2))
    np.testing.assert_array_equal(np.asarray(m), np.asarray(m2))
    for i in range(3):  # one sequence at a time, as the reference draws it
        rt, rm = jax.jit(lambda x: ref.noise(x, sizes))(tokens[i])
        np.testing.assert_array_equal(np.asarray(t[i]), np.asarray(rt))
        np.testing.assert_array_equal(np.asarray(m[i]), np.asarray(rm))
    # another sequence, another seed: another draw; the same sequence elsewhere in a batch: the same
    assert not np.array_equal(np.asarray(m[0]), np.asarray(m[1]))
    other = T._diffusion_noise(tokens, dataclasses.replace(cfg, diffusion_seed=8))
    assert not np.array_equal(np.asarray(m), np.asarray(other[1]))
    again = T._diffusion_noise(tokens[::-1], cfg)
    np.testing.assert_array_equal(np.asarray(again[1][::-1]), np.asarray(m))
    # one id changed: the checksum moves and the whole draw with it
    moved = T._diffusion_noise(tokens.at[0, 17].add(1), cfg)
    assert not np.array_equal(np.asarray(moved[1][0]), np.asarray(m[0]))
    # t uniform on [t_min, 1] a block, m Bernoulli(t): the masked share tends to E[t] = (1 + t_min) / 2
    assert float(t.min()) >= 1e-3 and float(t.max()) <= 1.0 + 1e-6 and t.shape == (3, 1024)
    assert abs(float(jnp.mean(t)) - 0.5005) < 0.02 and abs(float(jnp.mean(m)) - 0.5005) < 0.02


def test_the_noised_rows_show_the_mask_id_and_the_clean_rows_the_ids():
    cfg, _, tokens, _ = make()
    _, m = T._diffusion_noise(tokens, cfg)
    rows = np.asarray(T._diffusion_rows(tokens, cfg))
    assert rows.shape == (2, 64) and cfg.mask_id == 63
    np.testing.assert_array_equal(rows[:, 32:], np.asarray(tokens))
    np.testing.assert_array_equal(rows[:, :32], np.where(np.asarray(m), 63, np.asarray(tokens)))
    assert dataclasses.replace(cfg, diffusion_mask_id=5).mask_id == 5


def test_the_statistics_say_the_noise_and_count_both_halves():
    cfg, params, tokens, _ = make(seq=256)
    tokens = jnp.asarray(np.random.default_rng(1).integers(0, 64, (4, 256)), jnp.int32)
    loss, stats = jax.jit(lambda p, t: loss_and_stats(p, t, cfg))(params, tokens)
    t, m = T._diffusion_noise(tokens, cfg)
    assert float(stats["masked_share"]) == pytest.approx(float(jnp.mean(m)))
    assert float(stats["noise_weight_mean"]) == pytest.approx(float(jnp.mean(m / jnp.repeat(t, 4, axis=1))), rel=1e-6)
    assert 0.5 < float(stats["noise_weight_mean"]) < 2.0  # E[m/t] = 1
    assert float(stats["loss_masked_unweighted"]) > 0
    load = np.asarray(stats["tokens_per_expert"])
    assert load.shape == (2, 16) and (load.sum(axis=1) == 4 * 2 * 256 * 4).all()  # 2·S rows a sequence, 4 experts a row


# -- training ----------------------------------------------------------------------------------------------


def test_twenty_fused_steps_on_one_batch_lower_the_loss():
    cfg, _, tokens, _ = make()
    mesh = make_mesh(MeshConfig(), devices=jax.devices()[:1])
    ts = TrainStep(cfg, optax.adamw(1e-2), mesh)
    params = ts.init_params(jax.random.PRNGKey(0))
    opt = ts.init_opt(params)
    losses = []
    for _ in range(20):
        loss, params, opt = ts.step(params, opt, ts.shard_batch(tokens))
        losses.append(float(loss))
    assert losses[-1] < losses[0] - 1.0, losses
    assert {"masked_share", "noise_weight_mean", "loss_masked_unweighted", "rows_held", "window_live_share"} <= set(ts.last_stats)


def test_the_chain_of_grads_runs_a_diffusion_stack():
    """``TrainStep.grads`` as L + 2 programs: the head embeds ``[noised ; clean]``
    and reads the noised half, the tail transposes the same gather; the pieces
    are the one program's gradients."""
    cfg, _, tokens, _ = make()
    assert T.cuts_by_layer(cfg)
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
    assert float(jnp.max(jnp.abs(tree["embed"][cfg.mask_id]))) > 0  # the mask id's row learns


def test_a_dp_x_fsdp_x_tp_mesh_gives_the_unsharded_loss():
    cfg, params, tokens, _ = make(n_kv_heads=2)
    tokens = jnp.concatenate([tokens, tokens[::-1]], axis=0)
    want = float(jax.jit(loss_fn, static_argnums=2)(params, tokens, cfg))
    mesh = make_mesh(MeshConfig(dp=2, fsdp=2, tp=2), devices=jax.devices()[:8])
    ts = TrainStep(cfg, optax.adamw(1e-2), mesh)
    loss, _ = ts.grads(jax.device_put(params, ts._param_shardings), ts.shard_batch(tokens))
    assert float(loss) == pytest.approx(want, rel=1e-5)


# -- what it says and what it refuses ---------------------------------------------------------------------------


def test_the_path_says_the_block_the_tiles_and_the_live_tiles_once(monkeypatch):
    from torchft_tpu import telemetry

    monkeypatch.setattr(T, "_PATHS_SAID", set())
    over = dict(head_dim=128, n_heads=2, n_kv_heads=1, n_layers=1)
    cfg, params, tokens, _ = make(seq=256, attention_impl="flash", **over)
    before = len(telemetry.EVENTS.recent("attention_path"))
    for _ in range(2):
        jax.jit(lambda p: loss_fn(p, tokens, cfg, None))(params)
    (path,) = telemetry.EVENTS.recent("attention_path")[before:]
    assert (path["impl"], path["seq"], path["diffusion_block"]) == ("flash", 512, 4)
    assert (path["block_q"], path["block_k"], path["live_tiles"], path["tiles"]) == (128, 128, 8, 16)
    assert (path["n_heads"], path["n_kv_heads"], path["window"], path["rotary_dim"]) == (2, 1, 0, 128)
    monkeypatch.setattr(T, "_PATHS_SAID", set())
    plain = dataclasses.replace(cfg, attention_impl="auto")
    jax.jit(lambda p: loss_fn(p, tokens, plain, None))(params)
    path = telemetry.EVENTS.recent("attention_path")[-1]
    assert (path["impl"], path["reason"], path["live_tiles"]) == ("plain", "the staircase as a dense mask", 0)


def test_the_ft_loop_says_the_noise_on_loss_sync_and_nothing_of_another_model(monkeypatch):
    """``FTTrainer._record_diffusion_counters``: the three statistics as
    ``tft.diffusion.counters`` and on the span; without them, nothing."""
    import types

    from torchft_tpu.parallel import ft

    said, on_span = [], {}
    monkeypatch.setattr(ft.tracing, "annotate", lambda name, **stats: said.append((name, stats)) or contextlib.nullcontext())
    span = types.SimpleNamespace(set=lambda **kw: on_span.update(kw))
    stats = dict(masked_share=jnp.float32(0.5), noise_weight_mean=jnp.float32(1.02), loss_masked_unweighted=jnp.float32(9.8))
    trainer = types.SimpleNamespace(_ts=types.SimpleNamespace(last_stats=stats, cfg=TransformerConfig(**LAYER)))
    ft.FTTrainer._record_diffusion_counters(trainer, 7, span)
    ((name, counters),) = said
    assert name == "diffusion.counters" and counters == on_span
    assert counters == dict(step=7, block=4, masked_share=0.5, noise_weight_mean=pytest.approx(1.02), loss_masked_unweighted=pytest.approx(9.8))
    trainer._ts.last_stats = {"tokens_per_expert": 0}
    ft.FTTrainer._record_diffusion_counters(trainer, 8, span)
    assert len(said) == 1


def test_the_parts_ops_carry_their_names():
    """The ``op_name`` of the compiled program's ops, what a device trace
    carries: the draw and the concatenation under ``embed/noise``, the layer
    under ``attn/blockdiff`` with ``blockdiff_core`` inside, the rotation by
    positions that repeat there too."""
    cfg, params, tokens, _ = make()
    lowered = jax.jit(jax.grad(lambda p: loss_fn(p, tokens, cfg))).lower(params)
    names = set(re.findall(r'op_name="([^"]+)"', lowered.compile().as_text()))
    under = lambda pattern: [n for n in names if re.search(pattern, n)]
    assert under(r"attn\W.*blockdiff/blockdiff_core/") and under(r"attn\W.*blockdiff/(sin|cos)")
    assert under(r"embed\W.*noise/.*threefry") and under(r"embed\W.*noise/.*select_n") and not under(r"attn\W.*global")


@pytest.mark.parametrize("changes, message", [
    (dict(diffusion_block=3), "a block divides the sequence's tiles"),
    (dict(diffusion_block=256), "a block divides the sequence's tiles"),
    (dict(diffusion_t_min=0.0), "the least noise level lies in"),
    (dict(diffusion_mask_id=64), "a row of the 64 held"),
    (dict(window=8, window_layers=(1,)), r"with \['window'\] layers"),
    (dict(nope_layers=(2,)), r"with \['nope'\] layers"),
    (dict(conv_layers=(1,)), r"with \['conv'\] layers"),
    (dict(n_mtp_modules=1), "what it reads and predicts on a noised row is not defined"),
    (dict(ut_steps=2), "a looped stack whose exits read the noised half alone is missing"),
    (dict(pp=2), "a head on the noised rows with per-position weights inside the manual region is missing"),
    (dict(attention_impl="chunked"), "the staircase's two runs of keys a q block are missing"),
])
def test_what_the_configuration_refuses(changes, message):
    with pytest.raises(ValueError, match=message):
        TransformerConfig(**dict(LAYER, **changes))


def test_what_a_trace_refuses_the_ring_and_a_length_the_block_does_not_divide():
    cfg, params, _, _ = make()
    with pytest.raises(ValueError, match="does not divide the sequence's 30 positions"):
        loss_fn(params, jnp.zeros((1, 30), jnp.int32), cfg)
    mesh = make_mesh(MeshConfig(sp=2), devices=jax.devices()[:2])
    with pytest.raises(ValueError, match=r"a ring over \[noised ; clean\]"):
        T._attention_path(cfg, 64, 1, mesh, diffusion=True)
    q = jnp.zeros((1, 512, 1, 128))
    from torchft_tpu.ops.pallas.flash_attention import flash_attention

    with pytest.raises(ValueError, match="the block is a power of two"):
        flash_attention(q, q, q, block_diffusion=4, block_q=512, block_k=512)  # a tile of 512 over halves of 256
    with pytest.raises(ValueError, match="the staircase has no band"):
        flash_attention(q, q, q, block_diffusion=4, window=8)


def test_without_a_block_nothing_is_drawn_and_the_loss_is_next_token():
    """``diffusion_block`` 0: the other three fields are not read."""
    plain = {k: v for k, v in LAYER.items() if not k.startswith("diffusion")}
    a = TransformerConfig(dtype=jnp.float32, **plain)
    b = dataclasses.replace(a, diffusion_t_min=0.3, diffusion_mask_id=3, diffusion_seed=9)
    params = init_params(jax.random.PRNGKey(0), a)
    tokens = jnp.asarray(np.random.default_rng(0).integers(0, 64, (2, 32)), jnp.int32)
    text = lambda cfg: jax.jit(jax.value_and_grad(lambda p: loss_fn(p, tokens, cfg))).lower(params).as_text()
    assert text(a) == text(b) and "threefry" not in text(a)
    assert set(loss_and_stats(params, tokens, a)[1]) == {"tokens_per_expert", "balance_loss", "rows_held", "window_live_share"}


def test_attention_takes_the_rule_as_a_dense_mask():
    q, k, v = (jax.random.normal(key, (1, 16, 2, 8)) for key in jax.random.split(jax.random.PRNGKey(0), 3))
    got = attention(q, k, v, block_diffusion=4)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(8.0)
    probs = jax.nn.softmax(jnp.where(block_diffusion_mask(8, 4)[None, None], scores, -jnp.inf), axis=-1)
    np.testing.assert_allclose(got, jnp.einsum("bhqk,bkhd->bqhd", probs, v), atol=1e-6)
