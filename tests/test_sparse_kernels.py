"""The kernels of a learned key selection against ``ops.attention``: the flash
kernel's walk under a table of selected pairs (forward, backward, a tile with
no selected pair stepped over), the probabilities of the selected pairs summed
over the query heads, the selector's scores and their gradient a q block at a
time, the exact k-th largest — and the model through all of them (interpreted
here) against the model through dense tables. The model against its reference
is ``tests/test_sparse_attention.py``."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from tests.test_sparse_attention import RTOL, grad_errors, make, system

from torchft_tpu.ops.attention import attention, indexer_scores, select_top, selected_head_probs
from torchft_tpu.ops.pallas.flash_attention import flash_attention, head_probs, scaled_head_major, selected_tiles, tiles_live
from torchft_tpu.ops.pallas.indexer import indexer_scores_bwd_t, indexer_scores_t


def _random_table(key, b, s, keep, dead=None):
    """A causal table [B, S, S] that keeps a pair with probability ``keep``, the diagonal always; ``dead`` (q0, q1,
    k0, k1): a rectangle below the diagonal with no pair in it."""
    seen = (jax.random.uniform(key, (b, s, s)) < keep) | jnp.eye(s, dtype=bool)
    if dead:
        q0, q1, k0, k1 = dead
        seen = seen.at[:, q0:q1, k0:k1].set(False)
    return seen & jnp.tri(s, dtype=bool)


def test_the_kernel_under_a_table_is_plain_attention_under_it_forward_and_backward():
    """Four 32 x 32 tiles a side, groups of 2, one tile below the diagonal with
    no selected pair (q rows 64-95 against keys 0-31): the walk steps over it."""
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    q = jax.random.normal(ks[0], (2, 128, 4, 16))
    k, v = (jax.random.normal(key, (2, 128, 2, 16)) for key in ks[1:3])
    do = jax.random.normal(ks[3], (2, 128, 4, 16))
    seen = _random_table(ks[4], 2, 128, 0.3, dead=(64, 96, 0, 32))
    tiles = selected_tiles(seen, 32)
    live = tiles_live(tiles, 32)
    assert tiles.shape == (4, 2, 128, 32) and live.shape == (4, 2, 4) and not live[2, :, 0].any() and live[3].all()
    (o, lse), vjp = jax.vjp(lambda *a: flash_attention(*a, block_q=32, block_k=32, selected=tiles, live=live), q, k, v)
    want, want_vjp = jax.vjp(lambda *a: attention(*a, selected=seen), q, k, v)
    np.testing.assert_allclose(o, want, atol=2e-5)
    for got, ref_ in zip(vjp((do, jnp.zeros_like(lse))), want_vjp(do)):
        np.testing.assert_allclose(got, ref_, atol=5e-5)
    # the row statistics are each head's logsumexp over the selected keys, and what sums the heads reads them
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, jnp.repeat(k, 2, axis=2)) / 4.0
    np.testing.assert_allclose(lse[:, :, 0], jax.nn.logsumexp(jnp.where(seen[:, None], scores, -jnp.inf), axis=-1), atol=2e-5)
    want_p = selected_head_probs(q, k, seen)
    np.testing.assert_allclose(jnp.sum(want_p, axis=-1), 1.0, atol=1e-5)
    for i in range(4):
        p = head_probs(scaled_head_major(q), k.transpose(0, 2, 1, 3), lse, tiles, live, i, 32)
        np.testing.assert_allclose(p, want_p[:, 32 * i : 32 * (i + 1)].transpose(0, 2, 1), atol=2e-6)
    with pytest.raises(ValueError, match="the table is the whole rule"):
        flash_attention(q, k, v, window=8, selected=tiles)
    with pytest.raises(ValueError, match=r"the table is \[S / block_q, B, S, block_q\]"):
        flash_attention(q, k, v, block_q=64, block_k=32, selected=tiles)


def test_the_indexers_kernels_are_its_plain_scores_and_their_gradient():
    ks = jax.random.split(jax.random.PRNGKey(1), 4)
    q_i = jax.random.normal(ks[0], (2, 96, 3, 8))
    k_i = jax.random.normal(ks[1], (2, 96, 8))
    w = jax.random.normal(ks[2], (2, 96, 3))
    g = jax.random.normal(ks[3], (2, 96, 96)) * jnp.tri(96)
    want, vjp = jax.vjp(indexer_scores, q_i, k_i, w)
    dq, dk, dw = vjp(g)
    q_t, w_t = q_i.transpose(0, 2, 1, 3), w.transpose(0, 2, 1)
    dk_sum = 0.0
    for i in range(3):
        rows = slice(32 * i, 32 * (i + 1))
        got = indexer_scores_t(q_t, k_i, w_t, i, 32, 32)
        reached = jnp.arange(96)[None, :, None] < 32 * (i + 1)
        np.testing.assert_allclose(got, jnp.where(reached, want[:, rows].transpose(0, 2, 1), 0.0), atol=1e-5)
        dq_b, dk_b, dw_b = indexer_scores_bwd_t(q_t, k_i, w_t, g[:, rows].transpose(0, 2, 1), i, 32)
        np.testing.assert_allclose(dq_b, dq[:, rows].transpose(0, 2, 1, 3), atol=2e-5)
        np.testing.assert_allclose(dw_b, dw[:, rows].transpose(0, 2, 1), atol=2e-5)
        dk_sum = dk_sum + dk_b
    np.testing.assert_allclose(dk_sum, dk, atol=5e-5)


@pytest.mark.parametrize("k", [1, 5, 40])
def test_the_threshold_is_the_kth_largest_exactly_and_ties_take_more(k):
    x = jax.random.normal(jax.random.PRNGKey(k), (3, 40, 64)).at[0, :, :6].set(0.25).at[1, 0, :].set(-0.0).at[1, 0, ::2].set(0.0)
    causal = jnp.ones((40, 64), bool).at[:, 50:].set(False)
    seen, tau, over = select_top(x, causal, k, -1)
    want = jnp.sort(jnp.where(causal, x, -jnp.inf), axis=-1)[..., 64 - k]
    np.testing.assert_array_equal(np.asarray(tau), np.asarray(want) + 0.0)  # -0.0 counts as +0.0
    np.testing.assert_array_equal(np.asarray(seen), np.asarray(causal & (x >= want[..., None])))
    assert bool(jnp.all(over == (jnp.sum(seen, axis=-1) > k))) and bool(over[1, 0]) == (k < 50)
    few = select_top(x, causal.at[:, 3:].set(False), 5, -1)  # three causal keys a row: all of them, no threshold
    assert bool(jnp.all(few[0] == causal.at[:, 3:].set(False))) and bool(jnp.all(jnp.isneginf(few[1]))) and not few[2].any()


def test_the_model_through_the_kernels_is_the_model_through_the_dense_table():
    """S 256 at tiles of 128: ``attention_impl`` "flash" runs the selection as
    int8 tiles in the flash kernel's walk, the selector's scores and its target
    a q block at a time (all interpreted here)."""
    cfg, params, tokens, _ = make(seq=256, n_layers=1)
    want, stats, g_want, sets = system(cfg, params, tokens[:1])
    flash = dataclasses.replace(cfg, attention_impl="flash")
    got, said, g_got, tiles = system(flash, params, tokens[:1])
    assert float(got) == pytest.approx(float(want), rel=2e-6) and bool(jnp.all(sets == tiles))
    assert max(jax.tree_util.tree_leaves(grad_errors(g_got, g_want))) < RTOL
    for name in ("indexer_loss", "selected_mean", "selected_over_k", "sparse_tiles_live_share"):
        np.testing.assert_allclose(said[name], stats[name], rtol=1e-5)
    assert float(said["sparse_tiles_live_share"][0]) == 1.0  # three causal tiles, each with a selected pair


# -- the cell's program on the chip's branch ------------------------------------------------------------------------

# digests of the cell's ``pallas_call`` equations, each printed on its own (``tests/test_window_gqa.kernel_equations``):
# pinned at the commit that brought them, beside ``test_window_gqa.CELLS_KERNELS``' rows of the dense walk, which that
# commit left as they were
CELL_KERNELS = {"flash_bwd": ["8db46dbecd2f0e28"], "flash_fwd": ["ddf9a91b9efd2093"], "head_probs": ["611c9c13c6f46bf9"], "indexer_scores": ["0bc5d30f7704e916"], "indexer_scores_bwd": ["b1d7f0d12305ed5d"]}


def test_the_cells_program_runs_each_kernel_as_often_as_the_design_says():
    """``keye-vl-2_0-30b-a3b-1g.fused-s16384`` on the chip's branch, the value and
    gradient of ``loss_fn``, a layer: the core's forward ONCE (its output and row
    statistics are kept across ``remat``) and its backward once; the selector's
    scores four times (the selection and the term, each in the forward and in the
    recomputed forward), their backward once (emitted by the term's recomputed
    forward), the target twice; and the line the program says once."""
    from tests.test_looped_train import cells_program
    from tests.test_window_gqa import kernel_equations

    jaxpr, said = cells_program("keye-vl-2_0-30b-a3b-1g", (1, 16384))
    kernels = kernel_equations(jaxpr.jaxpr)
    counts = {name: len(digests) for name, digests in kernels.items() if name != "None"}
    assert counts == {"flash_fwd": 1, "flash_bwd": 1, "indexer_scores": 4, "indexer_scores_bwd": 1, "head_probs": 2}
    assert {name: sorted(set(kernels[name])) for name in counts} == CELL_KERNELS
    (line,) = [text for text in said if text.startswith("attention_path ")]
    assert line.startswith("attention_path impl=flash block_q=512 block_k=512 batch=1 seq=16384 head_dim=128 value_dim=128 ")
    assert "n_heads=32 n_kv_heads=4 window=0 rotary_dim=128 sparse_topk=2048 indexer=16x64 selection=int8 tiles" in line
