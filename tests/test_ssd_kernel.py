"""The state-space core's Pallas kernel pair (``ops/pallas/kda.ssd_forward`` /
``ssd_backward``, interpreted on the CPU) against the rule one position after
another (``ops/kda.ssd_recurrent``) AND against the ``jax.numpy`` form of
``ssd_chunked`` (``ops/kda._ssd_chunks``): output, final state and the
gradient of q, k, v, g and the initial state, at the smallest shapes that cross
what they test; which calls ``ops/kda.ssd_core`` gives the kernels and which it
refuses; the event that says so. ``tests/test_ssd_hybrid.py`` holds the
``jax.numpy`` form at the CPU rehearsals' widths, the mixer and the program; a
file of its own, so that a worker of the tier-1 run gets half of the compiles.

Tolerance: float32 inputs run every product of the kernels at ``HIGHEST``, so
what differs from the recurrence is the order of the sums — 2e-5 absolute on
outputs of order 1 and 2e-5 of a gradient's largest entry, what
``tests/test_gdn.py`` holds its kernels to (measured here: under 1e-6)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from torchft_tpu.models import transformer as T
from torchft_tpu.models.transformer import TransformerConfig, init_params, loss_and_stats
from torchft_tpu.ops import kda
from torchft_tpu.ops.kda import ssd_chunked, ssd_core, ssd_recurrent

STATE, CHUNK = 128, 128  # the kernels' state width (a lane tile) and their one chunk: the mixer's


def inputs(seq, heads, p, nats, state=STATE, batch=1, seed=0):
    """q, k [B, S, N] (q scaled as a mixer's C·B products are of order 1), v, g <= 0 up to ``nats`` a position, a state."""
    r = np.random.RandomState(seed)
    draw = lambda *shape: jnp.asarray(r.randn(*shape), jnp.float32)  # noqa: E731
    q, k = draw(batch, seq, state) * state**-0.5, draw(batch, seq, state)
    g = -nats * jnp.asarray(r.rand(batch, seq, heads), jnp.float32)
    return q, k, draw(batch, seq, heads, p), g, draw(batch, heads, state, p)


def kernel(q, k, v, g, s0):
    return ssd_chunked(q, k, v, g, chunk=CHUNK, initial_state=s0)


def jax_numpy(q, k, v, g, s0):
    """``ssd_chunked``'s other form at the same call: the tail padded as ``ssd_chunked`` pads it."""
    pad = -q.shape[1] % CHUNK
    q, k, v, g = (jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2)) for x in (q, k, v, g))
    o, end = kda._ssd_chunks(q, k, v, g, s0, CHUNK)
    return o[:, : o.shape[1] - pad], end


def value_and_grads(rule, *args, scalar=None):
    scalar = scalar or (lambda o, end: jnp.sum(o * jnp.cos(o)) + jnp.sum(end * end))

    def of(*a):
        o, end = rule(*a)
        return scalar(o.astype(jnp.float32), end), (o, end)

    (_, out), grads = jax.jit(jax.value_and_grad(of, argnums=(0, 1, 2, 3, 4), has_aux=True))(*args)
    return out, grads


def worst(got, want):
    return max(float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))) / (jnp.max(jnp.abs(b)) + 1e-12)) for a, b in zip(got, want))


def pallas_calls(fn, *args):
    """{a ``pallas_call``'s name: the chains of primitives around its calls} of a function's jaxpr."""
    from tests.test_gdn import paths_to_kernels

    return paths_to_kernels(jax.make_jaxpr(fn)(*args).jaxpr)


# -- the kernel pair against the recurrence and against the jax.numpy form -------------------------------------------


@pytest.mark.parametrize("nats", [0.3, 16.0], ids=["mild", "sixteen_nats"])
@pytest.mark.parametrize(
    "seq, heads, p",
    [(200, 6, 64), (256, 4, 64), (256, 3, 128), (200, 2, 128)],
    ids=["three_head_blocks_of_half_tiles_padded_tail", "two_tiles_a_step", "three_head_blocks_of_whole_tiles", "whole_tiles_padded_tail"],
)
def test_the_kernel_pair_is_the_recurrence_and_the_jax_numpy_form_in_value_and_every_gradient(seq, heads, p, nats):
    """Two chunks (the carried state forward, ``dS`` backward), a tail padded to
    the second (200), heads half a lane tile wide (two a tile) and a whole one,
    one head block of two tiles and three head blocks (q's and k's gradients
    summed over a step's heads inside the step and over the blocks outside), a
    non-zero initial state, and decays up to 16 nats a POSITION: every exponent
    the kernels take is <= 0, so 2 000 nats a chunk leave nothing to overflow."""
    args = inputs(seq, heads, p, nats)
    assert ssd_core(*args[:3], CHUNK) == "ssd_kernel"
    paths = pallas_calls(kernel, *args)
    assert set(paths) == {"ssd_fwd"} and not any("cond" in path for around in paths.values() for path in around)
    with jax.default_matmul_precision("highest"):
        (o, end), grads = value_and_grads(kernel, *args)
        (o_rec, end_rec), grads_rec = value_and_grads(ssd_recurrent, *args)
        (o_np, end_np), grads_np = value_and_grads(jax_numpy, *args)
    assert o.shape == (1, seq, heads, p) and end.shape == (1, heads, STATE, p) and end.dtype == jnp.float32
    for o_want, end_want, grads_want in ((o_rec, end_rec, grads_rec), (o_np, end_np, grads_np)):
        np.testing.assert_allclose(o, o_want, atol=2e-5)
        np.testing.assert_allclose(end, end_want, atol=2e-5)
        assert worst(grads, grads_want) < 2e-5
    assert all(float(jnp.max(jnp.abs(x))) > 1e-3 for x in grads)  # each of the five is a gradient that says something


def test_the_kernels_carry_a_state_from_block_to_block():
    """256 positions at once, and as 128 then 128 with the first block's final
    state handed to the second (the mixer's scan over blocks), two sequences."""
    q, k, v, g, s0 = inputs(256, 2, 64, 2.0, batch=2)
    with jax.default_matmul_precision("highest"):
        whole, end = jax.jit(kernel)(q, k, v, g, s0)
        first, mid = jax.jit(kernel)(*(x[:, :128] for x in (q, k, v, g)), s0)
        second, end2 = jax.jit(kernel)(*(x[:, 128:] for x in (q, k, v, g)), mid)
    np.testing.assert_allclose(jnp.concatenate([first, second], axis=1), whole, atol=2e-6)
    np.testing.assert_allclose(end2, end, atol=2e-6)


def test_the_kernels_decay_sums_are_taken_over_their_own_positions():
    """Thousands of nats ahead of three mild positions: the gradient of THEIR
    decays agrees with the recurrence's to a few float32 steps, where sums
    taken as differences of running sums carry 1e-4 at 2 000 nats into a pair
    that decays by one (``tests/test_gdn.py`` measures 4e-5 for that form)."""
    q, k, v, _, s0 = inputs(128, 2, 64, 1.0)
    line = jnp.concatenate([jnp.full((60,), -70.0), jnp.asarray([-0.25, -0.125, -0.5]), jnp.full((65,), -70.0)])
    g = jnp.broadcast_to(line[None, :, None], (1, 128, 2)).astype(jnp.float32)
    probe = jnp.asarray(np.random.RandomState(9).randn(*v.shape), jnp.float32)
    dg = lambda rule: jax.jit(jax.grad(lambda g: jnp.sum(rule(q, k, v, g, s0)[0] * probe)))(g)  # noqa: E731
    with jax.default_matmul_precision("highest"):
        got, want = dg(kernel), dg(ssd_recurrent)
    assert float(jnp.max(jnp.abs(want[:, 60:63]))) > 1e-2
    assert float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want))) < 3e-6


def test_the_kernels_in_bfloat16_stay_at_bfloat16s_distance_from_the_jax_numpy_form():
    """bfloat16 q, k, v at the cell's widths of a head (64 under a state of
    128): the kernel pair against the ``jax.numpy`` form on the same operands
    and against the float32 recurrence — outputs (of order 1) inside 3e-2, each
    gradient inside 3e-2 of its largest entry (``tests/test_gdn.py``'s band),
    and NOT equal to the recurrence (1e-5: the operands are bfloat16's). The
    pairs are rounded where ``ssd_chunked`` rounds them, the state stays float32."""
    q, k, v, g, s0 = inputs(256, 4, 64, 3.0)
    qb, kb, vb = (x.astype(jnp.bfloat16) for x in (q, k, v))
    assert ssd_core(qb, kb, vb, CHUNK) == "ssd_kernel"
    scalar = lambda o, end: jnp.sum(jnp.sin(o)) + jnp.sum(end * end)  # noqa: E731
    (o, end), got = value_and_grads(kernel, qb, kb, vb, g, s0, scalar=scalar)
    (o_np, end_np), theirs = value_and_grads(jax_numpy, qb, kb, vb, g, s0, scalar=scalar)
    with jax.default_matmul_precision("highest"):
        (o_rec, end_rec), want = value_and_grads(ssd_recurrent, *(x.astype(jnp.float32) for x in (qb, kb, vb)), g, s0, scalar=scalar)
    assert o.dtype == got[0].dtype == got[1].dtype == got[2].dtype == jnp.bfloat16
    assert end.dtype == got[3].dtype == got[4].dtype == jnp.float32
    off = lambda a, b: float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))))  # noqa: E731
    scale = float(jnp.max(jnp.abs(o_rec)))
    assert 1e-5 < off(o, o_rec) < 3e-2 * scale and off(o, o_np) < 3e-2 * scale
    assert off(end, end_rec) < 3e-2 * float(jnp.max(jnp.abs(end_rec))) and off(end, end_np) < 3e-2 * float(jnp.max(jnp.abs(end_rec)))
    for mine, other, ref in zip(got, theirs, want):
        scale = float(jnp.max(jnp.abs(ref)))
        assert off(mine, ref) < 3e-2 * scale and off(mine, other) < 3e-2 * scale


# -- which calls are the kernels' ---------------------------------------------------------------------------


def shapes(heads=2, p=64, state=STATE, dtype=jnp.float32, seq=128, batch=1):
    of = lambda *shape: jax.ShapeDtypeStruct(shape, dtype)  # noqa: E731
    return of(batch, seq, state), of(batch, seq, state), of(batch, seq, heads, p)


@pytest.mark.parametrize(
    "call, chunk",
    [
        (shapes(state=64), CHUNK), (shapes(state=192), CHUNK), (shapes(heads=3), CHUNK), (shapes(heads=4, p=32), CHUNK),
        (shapes(heads=1, p=256), CHUNK), (shapes(), 64), (shapes(), 256), (shapes(heads=8, p=4, state=8), 37),
        (shapes(batch=0), CHUNK),
    ],
    ids=[
        "a_state_of_half_a_tile", "a_state_of_a_tile_and_a_half", "the_heads_lanes_no_whole_tiles", "heads_a_quarter_tile_wide",
        "heads_two_tiles_wide", "a_chunk_of_64", "the_published_chunk_of_256", "a_cpu_rehearsals_widths",
        "a_call_with_no_sequence_in_it",
    ],
)
def test_the_core_answers_jax_numpy_for_each_thing_it_refuses(call, chunk):
    assert ssd_core(*shapes(), CHUNK) == ssd_core(*shapes(heads=1, p=128), CHUNK) == ssd_core(*shapes(state=256), CHUNK) == "ssd_kernel"
    assert ssd_core(*call, chunk) == "jax.numpy"
    q, k, v = call
    g, s0 = jax.ShapeDtypeStruct(v.shape[:3], jnp.float32), jax.ShapeDtypeStruct((v.shape[0], v.shape[2], q.shape[2], v.shape[3]), jnp.float32)
    assert not pallas_calls(lambda *a: ssd_chunked(*a[:4], chunk=chunk, initial_state=a[4]), q, k, v, g, s0)


def test_mixed_dtypes_and_a_mesh_of_several_devices_keep_the_jax_numpy_form():
    q, k, v = shapes()
    bf16 = lambda x: jax.ShapeDtypeStruct(x.shape, jnp.bfloat16)  # noqa: E731
    assert ssd_core(bf16(q), bf16(k), bf16(v), CHUNK) == "ssd_kernel"
    assert ssd_core(bf16(q), bf16(k), v, CHUNK) == ssd_core(q, bf16(k), bf16(v), CHUNK) == "jax.numpy"
    for devices, want in ((1, "ssd_kernel"), (2, "jax.numpy")):
        with jax.set_mesh(Mesh(np.array(jax.devices()[:devices]).reshape(devices, 1), ("fsdp", "tp"))):
            assert ssd_core(q, k, v, CHUNK) == want
            g, s0 = jax.ShapeDtypeStruct((1, 128, 2), jnp.float32), jax.ShapeDtypeStruct((1, 2, STATE, 64), jnp.float32)
            assert bool(pallas_calls(kernel, q, k, v, g, s0)) == (devices == 1)


def test_the_heads_of_a_grid_step_are_whole_lane_tiles():
    from torchft_tpu.ops.pallas import kda as kernels

    # heads a step: the cell's 64 heads of 64 go eight a step in four lane tiles, eight steps a chunk
    assert kernels._ssd_heads(64, 64) == 8 and kernels._ssd_heads(6, 64) == 2 and kernels._ssd_heads(4, 64) == 4
    assert kernels._ssd_heads(3, 128) == 1 and kernels._ssd_heads(8, 128) == 4 and kernels._ssd_heads(2, 128) == 2
    assert kernels.SSD_CHUNK == T._SSD_CHUNK == CHUNK


# -- the event that says which form a mixer's block took -------------------------------------------------------------


def test_a_mixer_at_the_kernels_widths_says_ssd_kernel_and_chunk_128(monkeypatch):
    """One state-space layer with a state of 128 under two heads of 64, traced
    (nothing compiled): the ``ssd_core_path`` event says ``core`` ``ssd_kernel``
    and chunk 128 for a block of 256, once for the shape."""
    from torchft_tpu import telemetry

    monkeypatch.setattr(T, "_PATHS_SAID", set())
    cfg = TransformerConfig(
        dtype=jnp.float32, vocab_size=64, d_model=64, n_layers=1, n_heads=2, head_dim=32, d_ff=64,
        ssd_layers=(1,), ssd_state_dim=STATE, ssd_head_dim=64, ssd_n_heads=2, ssd_expand=2,
    )
    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    tokens = jax.ShapeDtypeStruct((1, 256), jnp.int32)
    before = len(telemetry.EVENTS.recent("ssd_core_path"))
    for _ in range(2):
        jaxpr = jax.make_jaxpr(lambda p, t: loss_and_stats(p, t, cfg)[0])(params, tokens)
    (said,) = telemetry.EVENTS.recent("ssd_core_path")[before:]
    assert {k: said[k] for k in ("core", "chunk", "heads", "head_dim", "state", "block")} == dict(
        core="ssd_kernel", chunk=128, heads=2, head_dim=64, state=STATE, block=256
    )
    from tests.test_gdn import paths_to_kernels

    assert set(paths_to_kernels(jaxpr.jaxpr)) == {"ssd_fwd"}
