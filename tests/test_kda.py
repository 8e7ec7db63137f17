"""``ops/kda.py`` — gated delta-rule linear attention in chunked form and the
causal short convolution — against the recurrence position by position, and
the flash kernel with keys wider than values, on the CPU (the kernel
interpreted). ``tests/test_hybrid.py`` holds the model that uses them to the
plain reference."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torchft_tpu.ops import kda
from torchft_tpu.ops.attention import attention, chunked_attention


def kda_inputs(b, s, h=2, dk=16, dv=8, seed=0, decay=1.0, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q, k = jax.random.normal(ks[0], (b, s, h, dk)), jax.random.normal(ks[1], (b, s, h, dk))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * dk**-0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (b, s, h, dv))
    g = -jax.nn.softplus(jax.random.normal(ks[3], (b, s, h, dk))) * decay
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, s, h)))
    return q.astype(dtype), k.astype(dtype), v.astype(dtype), g, beta


@pytest.mark.parametrize("batch, seq, decay", [
    (1, 128, 1.0),   # a length the chunk divides
    (2, 100, 1.0),   # one it does not: the tail neither decays nor writes
    (1, 64, 1.0),    # one chunk
    (2, 40, 1.0),    # less than one
    (2, 192, 30.0),  # decays whose inverse overflows float32 inside a chunk: exp(30 x 64)
    (1, 128, 0.01),  # hardly any decay: the delta rule's solve does the work
])
def test_chunked_kda_is_the_recurrence(batch, seq, decay):
    args = kda_inputs(batch, seq, decay=decay)
    o, state = jax.jit(kda.kda_chunked)(*args)
    o_want, state_want = jax.jit(kda.kda_recurrent)(*args)
    assert bool(jnp.all(jnp.isfinite(o)))
    np.testing.assert_allclose(o, o_want, atol=2e-6)  # outputs of order 0.3; float32 sums in another order
    np.testing.assert_allclose(state, state_want, atol=2e-6)

    if seq not in (100, 192):
        return  # the gradients at a length the chunk does not divide, and under the hard decay

    def scalar(fn):
        return lambda *a: jnp.sum(jnp.sin(fn(*a)[0]))

    got = jax.jit(jax.grad(scalar(kda.kda_chunked), argnums=(0, 1, 2, 3, 4)))(*args)
    want = jax.jit(jax.grad(scalar(kda.kda_recurrent), argnums=(0, 1, 2, 3, 4)))(*args)
    for a, b in zip(got, want):
        assert float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b))) < 5e-5


@pytest.mark.parametrize("alike", [0.0, 0.9, 0.99])
def test_chunked_kda_with_keys_alike_and_beta_near_one(alike):
    """What a model is one optimizer step from its initial values: a chunk's
    keys share a direction and beta is near 1, so ``diag(beta) A_kk`` has
    entries near 1 all of one sign. The delta rule's triangular system has to
    be SOLVED: the product form of its inverse cancels powers of 1e9 there
    (it read 2e18 off here, NaN on the chip at the cell's second step)."""
    q, k, v, g, beta = kda_inputs(1, 128, decay=0.01)
    shared = jax.random.normal(jax.random.PRNGKey(7), (1, 1, 2, 16))
    k = shared + (1 - alike) * k
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    beta = jax.nn.sigmoid(4.0 * beta + 1.0)  # 0.73 .. 0.99
    o, state = jax.jit(kda.kda_chunked)(q, k, v, g, beta)
    o_want, state_want = jax.jit(kda.kda_recurrent)(q, k, v, g, beta)
    np.testing.assert_allclose(o, o_want, atol=5e-6)
    np.testing.assert_allclose(state, state_want, atol=5e-6)


def test_chunked_kda_carries_a_state_from_block_to_block():
    args = kda_inputs(2, 192)
    chunked = jax.jit(kda.kda_chunked)
    whole, end = chunked(*args)
    first, mid = chunked(*(a[:, :128] for a in args))
    second, end2 = chunked(*(a[:, 128:] for a in args), initial_state=mid)
    np.testing.assert_allclose(jnp.concatenate([first, second], axis=1), whole, atol=2e-6)
    np.testing.assert_allclose(end2, end, atol=2e-6)


def test_chunked_kda_in_bfloat16():
    """bfloat16 operands (8 mantissa bits: 2^-9 relative an operand), float32
    accumulation, state and decay: outputs of order 0.3 are off the float32
    recurrence by a few 1e-3 (measured 1.8e-3); 1e-2 is the bound."""
    args = kda_inputs(2, 256, dtype=jnp.bfloat16)
    o, _ = jax.jit(kda.kda_chunked)(*args)
    assert o.dtype == jnp.bfloat16
    o_want, _ = jax.jit(kda.kda_recurrent)(*(a.astype(jnp.float32) for a in args))
    assert 1e-5 < float(jnp.max(jnp.abs(o.astype(jnp.float32) - o_want))) < 1e-2


def test_the_short_convolution_is_causal_and_per_channel():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 12, 6))
    w = jax.random.normal(jax.random.PRNGKey(1), (4, 6))
    y = kda.short_conv(x, w)
    want = np.zeros((2, 12, 6), np.float32)
    for t in range(12):
        for i in range(4):
            if t - 3 + i >= 0:
                want[:, t] += np.asarray(w[i]) * np.asarray(x[:, t - 3 + i])
    np.testing.assert_allclose(y, want, atol=1e-6)
    # a later position moves no earlier output; a channel moves no other
    bumped = kda.short_conv(x.at[:, 7, 2].add(1.0), w) - y
    assert float(jnp.max(jnp.abs(bumped[:, :7]))) == 0.0
    assert float(jnp.max(jnp.abs(jnp.delete(bumped, 2, axis=2)))) == 0.0
    # block by block with the taps of history is the whole
    np.testing.assert_allclose(kda.short_conv(x[:, 5:], w, before=x[:, 2:5]), y[:, 5:], atol=1e-6)


def test_the_flash_kernel_pads_keys_to_a_lane_tile_and_reads_heads_in_place():
    """The cell's widths: keys 192, values 128 — values whole lane tiles, keys
    padded with zero columns to 256 (exact). Interpreted on the CPU."""
    from torchft_tpu.ops.pallas.flash_attention import flash_attention

    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q, k = (jax.random.normal(key, (1, 256, 2, 192)) for key in ks[:2])
    v = jax.random.normal(ks[2], (1, 256, 2, 128))

    def scalar(fn):
        return lambda q, k, v: jnp.sum(jnp.sin(fn(q, k, v)))

    with jax.default_matmul_precision("highest"):
        got = flash_attention(q, k, v, block_q=128, block_k=128)
        want = attention(q, k, v)
        g_got = jax.grad(scalar(lambda *a: flash_attention(*a, block_q=128, block_k=128)), argnums=(0, 1, 2))(q, k, v)
        g_want = jax.grad(scalar(attention), argnums=(0, 1, 2))(q, k, v)
    assert got.shape == (1, 256, 2, 128)
    np.testing.assert_allclose(got, want, atol=2e-5)
    for a, b in zip(g_got, g_want):
        assert a.shape == b.shape
        np.testing.assert_allclose(a, b, atol=5e-5)
    np.testing.assert_allclose(chunked_attention(q, k, v, chunk=64), want, atol=2e-5)


