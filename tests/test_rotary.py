"""The interleaved rotation (``ops/layers.rotary_embed`` without a table) as
``x · C + swap(x) · S`` with its gradient written out (PR 53), against the
stride-2 walk over the lanes it replaced — which lives on here as the plain
reference — and against a float64 rotation; and what its traced programs may
no longer hold."""

import hashlib
import itertools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests.test_window_gqa import every_equation
from torchft_tpu.ops import layers
from torchft_tpu.ops.layers import rotary_embed, yarn_inv_freq


def walk(x, positions, theta=10000.0):
    """``rotary_embed`` as it was until PR 53 (5cc5599), letter for letter:
    even and odd lanes sliced apart, turned, stacked and reshaped back."""
    dh = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    angles = positions[:, None].astype(jnp.float32) * freqs[None, :]  # [S, Dh/2]
    cos = jnp.cos(angles)[None, :, None, :]
    sin = jnp.sin(angles)[None, :, None, :]
    x1, x2 = x[..., ::2], x[..., 1::2]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    out1 = xf1 * cos - xf2 * sin
    out2 = xf1 * sin + xf2 * cos
    out = jnp.stack([out1, out2], axis=-1).reshape(x.shape)
    return out.astype(x.dtype)


def turned64(x, positions, theta=10000.0, back=False):
    """The rotation in numpy's float64 (``back``: by the negative angles, which
    is its transpose and so the cotangent's)."""
    x = np.asarray(x.astype(jnp.float32), np.float64)
    dh = x.shape[-1]
    angles = np.asarray(positions, np.float64)[:, None] * theta ** (-np.arange(0, dh, 2, dtype=np.float64) / dh)
    cos, sin = np.cos(angles)[None, :, None, :], np.sin(angles)[None, :, None, :] * (-1 if back else 1)
    out = np.empty_like(x)
    out[..., ::2] = x[..., ::2] * cos - x[..., 1::2] * sin
    out[..., 1::2] = x[..., ::2] * sin + x[..., 1::2] * cos
    return out


def ulps_apart(a, b):
    """|a - b| in units of the last place of the larger of the two, in their dtype."""
    bits = jnp.finfo(a.dtype).nmant
    a, b = (np.asarray(t.astype(jnp.float32), np.float64) for t in (a, b))
    top = np.maximum(np.abs(a), np.abs(b))
    ulp = 2.0 ** (np.floor(np.log2(np.where(top > 0, top, 1.0))) - bits)
    return np.abs(a - b) / ulp


def max_error(dx, exact):
    return np.abs(np.asarray(dx.astype(jnp.float32), np.float64) - exact).max()


def drawn(dtype, shape, offset, seed=0):
    kx, kg = jax.random.split(jax.random.PRNGKey(seed))
    x = (4.0 * jax.random.normal(kx, shape)).astype(dtype)
    g = (4.0 * jax.random.normal(kg, shape)).astype(dtype)
    return x, g, jnp.arange(shape[1]) + offset


SHAPES = [(2, 64, 4, 128), (1, 32, 1, 64), (8, 256, 16, 128)]
CASES = list(itertools.product(["bfloat16", "float32"], SHAPES, [0, 1000]))  # 1000: a sequence shard's positions


@pytest.mark.parametrize("dtype,shape,offset", CASES, ids=lambda v: "x".join(map(str, v)) if isinstance(v, tuple) else str(v))
def test_the_rotation_and_its_gradient_are_the_walks(dtype, shape, offset):
    x, g, positions = drawn(jnp.dtype(dtype), shape, offset)
    new, new_vjp = jax.vjp(lambda x: rotary_embed(x, positions), x)
    old, old_vjp = jax.vjp(lambda x: walk(x, positions), x)
    (new_dx,), (old_dx,) = new_vjp(g), old_vjp(g)
    for ours, theirs in ((new, old), (new_dx, old_dx)):
        assert ours.dtype == theirs.dtype == jnp.dtype(dtype) and ours.shape == shape
        apart = ulps_apart(ours, theirs)
        if dtype == "float32":
            assert apart.max() <= 2
        else:  # the same two float32 products and one sum, rounded once
            assert apart.max() <= 1 and np.mean(apart > 0) < 1e-4
    exact = turned64(g, positions, back=True)
    assert max_error(new_dx, exact) <= max_error(old_dx, exact)
    rtol = 2.0**-8 if dtype == "bfloat16" else 1e-6  # and the float32 angle of position 1255 is 1e-4 off
    np.testing.assert_allclose(np.asarray(new.astype(jnp.float32)), turned64(x, positions), rtol=rtol, atol=4e-3)


def test_autodiff_of_the_forward_would_round_the_gradient_twice():
    """Why the rule is written: the forward's body without it (``custom_vjp.fun``)
    transposes the two ``astype(float32)`` apart, so each product is rounded to
    bfloat16 and the two are added there — whole ulps off, in a large share of
    the elements, where the walk and the written rule round once."""
    x, g, positions = drawn(jnp.bfloat16, (2, 64, 4, 128), 0)
    old_dx = jax.vjp(lambda x: walk(x, positions), x)[1](g)[0]
    cos, sin = layers._pair_tables(positions, 10000.0, x.shape[-1])
    np.testing.assert_array_equal(np.asarray(layers._turn_pairs(x, cos, sin), np.float32), np.asarray(rotary_embed(x, positions), np.float32))
    plain_dx = jax.vjp(lambda x: layers._turn_pairs.fun(x, cos, sin), x)[1](g)[0]
    written_dx = jax.vjp(lambda x: layers._turn_pairs(x, cos, sin), x)[1](g)[0]
    written, plain = ulps_apart(written_dx, old_dx), ulps_apart(plain_dx, old_dx)
    assert written.max() <= 1 and np.mean(written > 0) < 1e-4  # the bound of the test above
    assert plain.max() >= 1 and np.mean(plain > 0) > 0.01
    exact = turned64(g, positions, back=True)
    assert max_error(plain_dx, exact) > 1.25 * max_error(written_dx, exact)


def test_swap_is_exact_and_its_own_inverse():
    for dtype in (jnp.bfloat16, jnp.float32):
        x = (1e3 * jax.random.normal(jax.random.PRNGKey(1), (3, 5, 2, 64))).astype(dtype)
        swapped = layers._swap_pairs(x)
        assert swapped.dtype == x.dtype
        as_np = np.asarray(x.astype(jnp.float32))
        np.testing.assert_array_equal(np.asarray(swapped.astype(jnp.float32)), as_np.reshape(3, 5, 2, 32, 2)[..., ::-1].reshape(as_np.shape))
        np.testing.assert_array_equal(np.asarray(layers._swap_pairs(swapped).astype(jnp.float32)), as_np)


# -- the traced programs -----------------------------------------------------------------------


def walks_a_lane_axis(eqn) -> bool:
    name = eqn.primitive.name
    if name in ("gather", "concatenate") or name.startswith("scatter"):
        return True
    return name == "slice" and any(s != 1 for s in (eqn.params["strides"] or ()))


@pytest.mark.parametrize("dtype,shape", [("bfloat16", (8, 2048, 16, 128)), ("bfloat16", (2, 8192, 1, 64)), ("float32", (2, 64, 4, 128))])
def test_the_rotation_and_its_vjp_walk_no_lanes(dtype, shape):
    x = jax.ShapeDtypeStruct(shape, jnp.dtype(dtype))
    positions = jnp.arange(shape[1])
    forward = jax.make_jaxpr(lambda x: rotary_embed(x, positions))(x)
    both = jax.make_jaxpr(lambda x, g: jax.vjp(lambda x: rotary_embed(x, positions), x)[1](g))(x, x)
    for jaxpr in (forward, both):
        eqns = list(every_equation(jaxpr.jaxpr))
        assert "dot_general" in [eqn.primitive.name for eqn in eqns]  # the swap, and so the search saw inside the rule
        assert not [eqn for eqn in eqns if walks_a_lane_axis(eqn)]
    # and the walk's programs are what the search finds
    old = jax.make_jaxpr(lambda x, g: jax.vjp(lambda x: walk(x, positions), x)[1](g))(x, x)
    found = {eqn.primitive.name for eqn in every_equation(old.jaxpr) if walks_a_lane_axis(eqn)}
    assert {"gather", "concatenate", "scatter-add"} <= found


def test_the_rotation_by_halves_is_the_parents_program():
    """``_rotary_half`` (``rope_pairing`` "half": Laguna, LFM2) is a different
    pairing and was not touched: its forward and vjp trace to the text they
    traced to at 5cc5599."""
    x = jax.ShapeDtypeStruct((2, 64, 4, 128), jnp.bfloat16)
    table = yarn_inv_freq(64, 500000.0, 32.0, 4096)
    f = lambda x: rotary_embed(x, jnp.arange(64) + 1000, inv_freq=table, scale=1.25)
    jaxpr = jax.make_jaxpr(lambda x, g: (f(x), jax.vjp(f, x)[1](g)))(x, x)
    text = re.sub(r"0x[0-9a-f]+", "0x", str(jaxpr))
    assert hashlib.sha256(text.encode()).hexdigest() == "55cf9568625d5c235f14a645bd66450a3c6e9d8b6a531771e1fe87dc1bd56dfa"
