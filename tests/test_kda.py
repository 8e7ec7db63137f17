"""``ops/kda.py`` — gated delta-rule linear attention in chunked form and the
causal short convolution — against the recurrence position by position, and
the flash kernel with keys wider than values, and the mixer that calls them
(``transformer._mix_kda``) against the form it had with its input projections
inside the block's checkpoint, on the CPU (the kernels
interpreted). Heads 128 wide take the Pallas kernels of ``ops/pallas/kda.py``
(the ``wide`` cases: one sequence of two heads, so that an interpreted call
takes seconds), every other width the ``jax.numpy`` form. ``tests/test_hybrid.py`` holds the model that uses them to the
plain reference."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torchft_tpu.models import transformer as T
from torchft_tpu.models.transformer import TransformerConfig, init_params
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


WIDE = dict(h=2, dk=128, dv=128)  # what the kernels take
NARROW = dict(h=2, dk=16, dv=8)


def enters_the_kernel(*args, **kw):
    return "pallas_call" in str(jax.make_jaxpr(lambda *a: kda.kda_chunked(*a, **kw))(*args))


def relative(a, b):
    return float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))


@pytest.mark.parametrize("batch, seq, decay, widths", [
    (1, 128, 1.0, NARROW),   # a length the chunk divides
    (2, 100, 1.0, NARROW),   # one it does not: the tail neither decays nor writes
    (1, 64, 1.0, NARROW),    # one chunk
    (2, 40, 1.0, NARROW),    # less than one
    (2, 192, 30.0, NARROW),  # decays whose inverse overflows float32 inside a chunk: exp(30 x 64)
    (1, 128, 0.01, NARROW),  # hardly any decay: the delta rule's solve does the work
    (1, 128, 1.0, WIDE),
    (1, 192, 1.0, WIDE),
    (1, 100, 1.0, WIDE),     # the kernel on a padded tail
    (1, 128, 0.01, WIDE),
])
def test_chunked_kda_is_the_recurrence(batch, seq, decay, widths):
    args = kda_inputs(batch, seq, decay=decay, **widths)
    wide = widths is WIDE
    assert enters_the_kernel(*args) == wide  # a head 16 wide never does
    state = None
    if wide:  # a state to start from, and its gradient
        state = 0.1 * jax.random.normal(jax.random.PRNGKey(11), (batch, 2, 128, 128))
    o, end = jax.jit(kda.kda_chunked)(*args, initial_state=state)
    o_want, end_want = jax.jit(kda.kda_recurrent)(*args, initial_state=state)
    assert bool(jnp.all(jnp.isfinite(o)))
    np.testing.assert_allclose(o, o_want, atol=2e-6)  # outputs of order 0.3; float32 sums in another order
    np.testing.assert_allclose(end, end_want, atol=2e-6)

    if seq not in (100, 192) and not wide:
        return  # the gradients at a length the chunk does not divide, and under the hard decay

    def scalar(fn):
        def of(*a):
            o, end = fn(*a[:5], initial_state=a[5] if wide else None)
            return jnp.sum(jnp.sin(o)) + (jnp.sum(jnp.sin(end)) if wide else 0.0)

        return of

    wrt = (0, 1, 2, 3, 4, 5) if wide else (0, 1, 2, 3, 4)
    got = jax.jit(jax.grad(scalar(kda.kda_chunked), argnums=wrt))(*args, state)
    want = jax.jit(jax.grad(scalar(kda.kda_recurrent), argnums=wrt))(*args, state)
    for a, b in zip(got, want):
        assert relative(a, b) < 5e-5


@pytest.mark.parametrize("widths", [NARROW, WIDE], ids=["narrow", "wide"])
@pytest.mark.parametrize("alike", [0.0, 0.9, 0.99])
def test_chunked_kda_with_keys_alike_and_beta_near_one(alike, widths):
    """What a model is one optimizer step from its initial values: a chunk's
    keys share a direction and beta is near 1, so ``diag(beta) A_kk`` has
    entries near 1 all of one sign. The delta rule's triangular system has to
    be SOLVED: the product form of its inverse cancels powers of 1e9 there
    (it read 2e18 off here, NaN on the chip at the cell's second step)."""
    q, k, v, g, beta = kda_inputs(1, 128, decay=0.01, **widths)
    shared = jax.random.normal(jax.random.PRNGKey(7), (1, 1, 2, widths["dk"]))
    k = shared + (1 - alike) * k
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    beta = jax.nn.sigmoid(4.0 * beta + 1.0)  # 0.73 .. 0.99
    o, state = jax.jit(kda.kda_chunked)(q, k, v, g, beta)
    o_want, state_want = jax.jit(kda.kda_recurrent)(q, k, v, g, beta)
    np.testing.assert_allclose(o, o_want, atol=5e-6)
    np.testing.assert_allclose(state, state_want, atol=5e-6)


@pytest.mark.parametrize("batch, widths", [(2, NARROW), (1, WIDE)], ids=["narrow", "wide"])
def test_chunked_kda_carries_a_state_from_block_to_block(batch, widths):
    args = kda_inputs(batch, 192, **widths)
    chunked = jax.jit(kda.kda_chunked)
    whole, end = chunked(*args)
    first, mid = chunked(*(a[:, :128] for a in args))
    second, end2 = chunked(*(a[:, 128:] for a in args), initial_state=mid)
    np.testing.assert_allclose(jnp.concatenate([first, second], axis=1), whole, atol=2e-6)
    np.testing.assert_allclose(end2, end, atol=2e-6)


@pytest.mark.parametrize("batch, seq, widths", [(2, 256, NARROW), (1, 192, WIDE)], ids=["narrow", "wide"])
def test_chunked_kda_in_bfloat16(batch, seq, widths):
    """bfloat16 operands (8 mantissa bits: 2^-9 relative an operand), float32
    accumulation, state and decay: outputs of order 0.3 are off the float32
    recurrence by a few 1e-3 (measured 1.8e-3; the kernels, whose small float32
    products are three bfloat16 passes there, the same); 1e-2 is the bound."""
    args = kda_inputs(batch, seq, dtype=jnp.bfloat16, **widths)
    o, _ = jax.jit(kda.kda_chunked)(*args)
    assert o.dtype == jnp.bfloat16
    o_want, _ = jax.jit(kda.kda_recurrent)(*(a.astype(jnp.float32) for a in args))
    assert 1e-5 < float(jnp.max(jnp.abs(o.astype(jnp.float32) - o_want))) < 1e-2
    if widths is WIDE:  # and the gradients the kernel writes in bfloat16, against float32's
        scalar = lambda fn: lambda *a: jnp.sum(jnp.sin(fn(*a)[0].astype(jnp.float32)))  # noqa: E731
        got = jax.jit(jax.grad(scalar(kda.kda_chunked), argnums=(0, 1, 2, 3, 4)))(*args)
        want = jax.jit(jax.grad(scalar(kda.kda_recurrent), argnums=(0, 1, 2, 3, 4)))(*(a.astype(jnp.float32) for a in args))
        for a, b in zip(got, want):
            assert relative(a.astype(jnp.float32), b) < 3e-2


def jax_numpy_form(*args, state=None):
    """The ``jax.numpy`` path at any width, for whole chunks."""
    b, _, h, d = args[0].shape
    state = jnp.zeros((b, h, d, args[2].shape[-1]), jnp.float32) if state is None else state
    return kda._chunked(*args, state, 64)


@pytest.mark.parametrize("seq, decay", [(128, 1.0), (192, 0.1), (64, 3.0)])
def test_the_kernels_and_the_jax_numpy_form_agree(seq, decay):
    args = kda_inputs(1, seq, decay=decay, seed=seq, **WIDE)
    state = 0.1 * jax.random.normal(jax.random.PRNGKey(5), (1, 2, 128, 128))
    assert enters_the_kernel(*args, initial_state=state)
    o, end = jax.jit(kda.kda_chunked)(*args, initial_state=state)
    o_want, end_want = jax.jit(jax_numpy_form)(*args, state=state)
    np.testing.assert_allclose(o, o_want, atol=2e-6)
    np.testing.assert_allclose(end, end_want, atol=2e-6)


def test_a_mesh_of_several_devices_keeps_the_jax_numpy_form():
    """The SPMD partitioner refuses a Mosaic call outside a ``shard_map``: a
    program traced under a mesh of two devices never enters the kernels, one
    under a mesh of a single device does (the benchmark's one-chip cell)."""
    from jax.sharding import Mesh

    args = kda_inputs(1, 128, **WIDE)
    for devices, enters in ((1, True), (2, False)):
        with jax.set_mesh(Mesh(np.array(jax.devices()[:devices]).reshape(devices, 1), ("fsdp", "tp"))):
            assert enters_the_kernel(*args) == enters


def test_a_strong_decay_takes_the_exact_path_at_the_kernels_width():
    """30 x the decay: channels lose thousands of nats inside a sub-block, the
    two-factor form the kernels are written in overflows, and the call — its
    ``g`` decides, inside the ``custom_vjp``'s rules — runs the ``jax.numpy``
    form whole: that form's results bit for bit, and its gradients."""
    args = kda_inputs(1, 128, decay=30.0, **WIDE)  # whole pairs of chunks: no padding between the two forms
    assert not bool(kda._kernel_serves(args[3])) and bool(kda._kernel_serves(args[3] / 30.0))
    o, end = jax.jit(kda.kda_chunked)(*args)
    o_want, end_want = jax.jit(jax_numpy_form)(*args)
    assert bool(jnp.all(o == o_want)) and bool(jnp.all(end == end_want))
    o_rec, _ = jax.jit(kda.kda_recurrent)(*args)
    np.testing.assert_allclose(o, o_rec, atol=2e-6)
    scalar = lambda fn: lambda *a: jnp.sum(jnp.sin(fn(*a)[0]))  # noqa: E731
    got = jax.jit(jax.grad(scalar(kda.kda_chunked), argnums=(0, 1, 2, 3, 4)))(*args)
    want = jax.jit(jax.grad(scalar(jax_numpy_form), argnums=(0, 1, 2, 3, 4)))(*args)
    for a, b in zip(got, want):  # the same products pulled back in the backward rule: XLA orders the sums its own way
        assert relative(a, b) < 1e-6


# -- the second residual: the backward kernels read ``M^-1`` where they formed it again (PR 64) ----------------


def kernel_bodies(fn, *args):
    """{a Pallas kernel's name: its body's jaxpr} of the calls ``fn`` traces to, and what ``fn`` returns, as shapes."""
    from tests.test_window_gqa import every_equation

    jaxpr, out = jax.make_jaxpr(fn, return_shape=True)(*args)
    return {str(e.params["name"]): e.params["jaxpr"] for e in every_equation(jaxpr.jaxpr) if e.primitive.name == "pallas_call"}, out


def dots(jaxpr):
    from tests.test_window_gqa import every_equation

    return sum(e.primitive.name == "dot_general" for e in every_equation(jaxpr))


def calls_of_the_inverse(monkeypatch):
    """Counts ``_unit_lower_inverses``' calls from here on: the matrices of each, in the list returned."""
    K = kda._kernels()
    calls, formed = [], K._unit_lower_inverses
    monkeypatch.setattr(K, "_unit_lower_inverses", lambda Ls, exact: calls.append(len(Ls)) or formed(Ls, exact))
    return calls


# a rule's backward kernel, and its chunks formed from the kernel's input refs with NO inverse given
FORMS_IT_AGAIN = {
    "kda": ("_bwd_kernel", lambda K, r, hb, exact: K._chunks(
        *(K._heads(ref, hb) for ref in r[:4]), [K._column(r[4][...], j) for j in range(hb)], r[2].dtype, exact)),
    "gdn": ("_gdn_bwd_kernel", lambda K, r, hb, kb, exact: K._gdn_chunks(*r[:3], r[3][...], r[4][...], hb, kb, exact)[0]),
}


def a_backward_that_forms_the_inverse(monkeypatch, rule):
    """The parent's backward (before PR 64) out of this tree's: the kernel takes
    no notice of the residual it is handed and forms ``M^-1`` from its inputs
    with the forward's code (``_unit_lower_inverses``), cast as the products
    take it. A traced call from here on (``.__wrapped__``: the jit holds the
    other trace) is that backward."""
    K = kda._kernels()
    name, chunks = FORMS_IT_AGAIN[rule]
    given = getattr(K, name)

    def kernel(*refs, **static):
        formed = [x["inv"] for x in chunks(K, refs, **static)]
        given(*refs[:6], formed, *refs[7:], **static)

    monkeypatch.setattr(K, name, kernel)


def kernels_inputs(dtype, seq=256):
    q, k, v, g, beta = kda_inputs(1, seq, dtype=dtype, seed=3, **WIDE)
    keys = jax.random.split(jax.random.PRNGKey(13), 3)
    state, d_end = (0.1 * jax.random.normal(key, (1, 2, 128, 128)) for key in keys[:2])
    do = jax.random.normal(keys[2], v.shape).astype(dtype)
    return tuple(kda._wide(x) for x in (q, k, v, g)) + (beta,), state, kda._wide(do), d_end


def the_backward_given_the_forwards_inverse_is_the_one_that_forms_it_again(monkeypatch, rule, heads, inputs, state, do, d_end):
    """``<rule>_forward`` hands on each pair of chunks' ``M^-1`` a head as its
    products took it (the inputs' dtype), ``<rule>_backward`` reads it: dq, dk,
    dv, dg, dbeta and dS0 are, bit for bit, those of a backward that is handed
    zeros and forms the inverse itself from the same inputs. ``heads``: those
    of the one grid step, so of the one call of ``_unit_lower_inverses``."""
    K = kda._kernels()
    forward, backward = getattr(K, rule + "_forward"), getattr(K, rule + "_backward")
    o, starts, inv, end = forward(*inputs, state)
    pairs = do.shape[1] // K.ROWS
    assert inv.shape == (1, heads, pairs, K.ROWS, K.ROWS) and inv.dtype == do.dtype
    assert starts.shape == (1, heads, 2 * pairs, 128, 128) and starts.dtype == jnp.float32
    i, j = np.indices((K.ROWS, K.ROWS))
    held = np.asarray(inv.astype(jnp.float32))  # unit lower triangular, a chunk a diagonal block
    assert np.all(held[..., i == j] == 1.0) and not np.any(held[..., (j > i) | (i // K.CHUNK != j // K.CHUNK)])
    assert np.any(held[..., (j < i) & (i // K.CHUNK == j // K.CHUNK)])
    got = backward(*inputs, starts, inv, do, d_end)
    calls = calls_of_the_inverse(monkeypatch)
    a_backward_that_forms_the_inverse(monkeypatch, rule)
    want = backward.__wrapped__(*inputs, starts, jnp.zeros_like(inv), do, d_end)
    assert calls == [heads]
    for name, a, b in zip(("dq", "dk", "dv", "dg", "dbeta", "dS0"), got, want):
        assert a.dtype == b.dtype and bool(jnp.any(a != 0)) and bool(jnp.all(a == b)), name


def the_backward_kernels_body_holds_the_inverses_products_no_more(monkeypatch, rule, heads, parents, inputs, state, do, d_end):
    """The chain of ten dependent small products a head (three bfloat16 passes
    each where the inputs are bfloat16) left ``<rule>_bwd``'s body and stayed
    in ``<rule>_fwd``'s — ``parents``: the two bodies' ``dot_general``s at the
    parent (bfd8bba) — and ``_unit_lower_inverses`` is called where the
    forward's body is traced and nowhere else."""
    K = kda._kernels()
    forward, backward = getattr(K, rule + "_forward").__wrapped__, getattr(K, rule + "_backward").__wrapped__
    exact = do.dtype == jnp.float32
    chain = dots(jax.make_jaxpr(lambda L: K._unit_lower_inverses([L], exact))(jnp.zeros((K.ROWS, K.ROWS))).jaxpr)
    assert chain == (10 if exact else 30)
    calls = calls_of_the_inverse(monkeypatch)
    fwd, (_, starts, inv, _) = kernel_bodies(forward, *inputs, state)
    assert calls == [heads]
    bwd, _ = kernel_bodies(backward, *inputs, starts, inv, do, d_end)
    assert calls == [heads]
    assert (dots(fwd[rule + "_fwd"]), dots(bwd[rule + "_bwd"])) == (parents[0], parents[1] - heads * chain)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
def test_the_backward_given_the_forwards_inverse_is_the_one_that_forms_it_again(dtype, monkeypatch):
    the_backward_given_the_forwards_inverse_is_the_one_that_forms_it_again(monkeypatch, "kda", 2, *kernels_inputs(dtype))


@pytest.mark.parametrize("dtype, parents", [(jnp.float32, (48, 92)), (jnp.bfloat16, (108, 188))], ids=["float32", "bfloat16"])
def test_the_backward_kernels_body_holds_the_inverses_products_no_more(dtype, parents, monkeypatch):
    the_backward_kernels_body_holds_the_inverses_products_no_more(monkeypatch, "kda", 2, parents, *kernels_inputs(dtype, seq=128))


def test_the_exact_branch_leaves_zeros_of_the_kernels_residuals_and_reads_none():
    """Under ``_by_decay``'s ``cond`` both branches hand on ``starts`` and the
    inverse: the kernels' where the call's decay is theirs, zeros of the same
    shapes and dtypes where the ``jax.numpy`` form runs — whose gradients are
    its own ``vjp``'s whatever those two residuals hold."""
    args = kda_inputs(1, 128, decay=30.0, **WIDE)
    mild = args[:3] + (args[3] / 30.0,) + args[4:]
    state = jnp.zeros((1, 2, 128, 128), jnp.float32)
    cts = (jax.random.normal(jax.random.PRNGKey(2), args[2].shape), 0.1 * jax.random.normal(jax.random.PRNGKey(3), state.shape))
    _, strong_res = jax.jit(kda._by_decay_fwd)(*args, state)
    _, mild_res = jax.jit(kda._by_decay_fwd)(*mild, state)
    assert not bool(strong_res[0]) and bool(mild_res[0])
    for zeros, kernels in zip(strong_res[-2:], mild_res[-2:]):
        assert (zeros.shape, zeros.dtype) == (kernels.shape, kernels.dtype)
        assert not bool(jnp.any(zeros != 0)) and bool(jnp.any(kernels != 0))
    in_bfloat16 = jax.eval_shape(kda._by_decay_fwd, *kda_inputs(1, 128, dtype=jnp.bfloat16, **WIDE), state)[1]
    assert [r.dtype for r in in_bfloat16[-2:]] == [jnp.float32, jnp.bfloat16]  # the inverse as the products take it
    backward = jax.jit(kda._by_decay_bwd)
    got = backward(strong_res, cts)
    unread = backward(strong_res[:-2] + tuple(jnp.ones_like(r) for r in strong_res[-2:]), cts)
    want = jax.jit(lambda *a: jax.vjp(jax_numpy_form, *a[:5])[1](a[5:]))(*args, *cts)
    for a, b, c in zip(got, unread, want):
        assert bool(jnp.all(a == b)) and relative(a, c) < 1e-6


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


# -- the mixer: what is outside the block's checkpoint ---------------------------------------------

# tiny widths, each product's its own: d 48 -> q, k, v 32 (4 heads of 8), the gates' rank 8, the write strength 4
MIXER = dict(vocab_size=64, d_model=48, n_heads=2, head_dim=16, d_ff=64, norm_eps=1e-5, linear_head_dim=8, linear_n_heads=4, conv_kernel=4)


def mixer_leaves(cfg, key=0):
    """One linear-mixer layer's leaves at the program's initial values, the output norm's weight off 1."""
    lp = jax.tree_util.tree_map(lambda a: a[0, 0], init_params(jax.random.PRNGKey(key), cfg)["layers"])
    return dict(lp, o_norm=lp["o_norm"] + 0.3 * jnp.sin(jnp.arange(lp["o_norm"].size, dtype=jnp.float32)))


def parents_mix_kda(cfg, lp, h):
    """``transformer._mix_kda`` as 080a434 had it: the three projections inside the checkpointed block."""
    b, s, d = h.shape
    heads, hd, taps = cfg.linear_n_heads, cfg.linear_head_dim, cfg.conv_kernel
    ch = heads * hd
    f32 = jnp.float32
    blk = T._KDA_BLOCK if s % T._KDA_BLOCK == 0 else s

    def block(carry, hb):
        state, before = carry
        qkv = jnp.concatenate([hb @ lp["wq"], hb @ lp["wk"], hb @ lp["wv"]], axis=-1)
        filters = jnp.concatenate([lp["conv_q"], lp["conv_k"], lp["conv_v"]], axis=-1)
        mixed = jax.nn.silu(kda.short_conv(qkv, filters, before))
        q, k, v = (mixed[..., i * ch : (i + 1) * ch].reshape(b, blk, heads, hd) for i in range(3))
        raw = jnp.dot(hb @ lp["w_fa"], lp["w_fb"], preferred_element_type=f32)
        g = -jnp.exp(lp["a_log"].astype(f32))[:, None] * jax.nn.softplus(raw + lp["dt_bias"].astype(f32)).reshape(b, blk, heads, hd)
        beta = jax.nn.sigmoid(jnp.dot(hb, lp["w_beta"], preferred_element_type=f32))
        out_gate = jax.nn.sigmoid(jnp.dot(hb @ lp["w_ga"], lp["w_gb"], preferred_element_type=f32)).astype(hb.dtype).reshape(b, blk, heads, hd)
        q = (T._unit_l2(q) * hd**-0.5).astype(v.dtype)
        k = T._unit_l2(k).astype(v.dtype)
        o, state = kda.kda_chunked(q, k, v, g, beta, initial_state=state)
        o = T.rms_norm(o, lp["o_norm"], cfg.norm_eps) * out_gate
        return (state, qkv[:, blk - (taps - 1) :]), o.reshape(b, blk, ch) @ lp["wo"]

    start = (jnp.zeros((b, heads, hd, hd), f32), jnp.zeros((b, taps - 1, 3 * ch), h.dtype))
    blocks = jnp.moveaxis(h.reshape(b, s // blk, blk, d), 1, 0)
    _, out = jax.lax.scan(jax.checkpoint(block), start, blocks)
    return jnp.moveaxis(out, 0, 1).reshape(b, s, d)


def value_and_gradients(mix, lp, h, probe):
    """``mix(lp, h)``'s (output, what it says beside it) and the gradients of
    ``Σ y·probe`` to every leaf and to the input."""

    def f(lp, h):
        y, said = mix(lp, h)
        return jnp.sum(y * probe), (y, said)

    with jax.default_matmul_precision("highest"):
        grads, out = jax.jit(jax.grad(f, argnums=(0, 1), has_aux=True))(lp, h)
    return out, grads


def largest_difference(got, want):
    """Over the leaves of two like trees: max |a - b| over max |b|."""
    return max(jax.tree_util.tree_leaves(jax.tree_util.tree_map(
        lambda a, b: float(jnp.max(jnp.abs(a - b)) / (jnp.max(jnp.abs(b)) + 1e-30)), got, want
    )))


@pytest.mark.parametrize("seq", [32, 40], ids=["two-blocks", "not-a-multiple"])
def test_the_mixer_with_its_projections_ahead_of_the_scan_is_the_parents(seq, monkeypatch):
    """q | k | v taken once over the whole sequence and handed to the blocks as
    ``xs`` are the numbers the parent's block computed for itself: the output
    and the gradient to the input and to every leaf, at two blocks (state and
    taps handed on) and at a length that is one block because it is no
    multiple. What differs is float32 rounding: a product of all the rows
    against a block's, and a weight's gradient summed over positions in one
    contraction against a block's at a time — at one block not a bit, at two
    5e-7 of the output's largest entry and 2e-6 of a gradient leaf's (``wq``,
    behind the L2 norm and the core's inverse), measured here."""
    monkeypatch.setattr(T, "_KDA_BLOCK", 16)
    cfg = TransformerConfig(dtype=jnp.float32, n_layers=1, kda_layers=(1,), **MIXER)
    lp = mixer_leaves(cfg)
    h, probe = (jax.random.normal(jax.random.PRNGKey(i), (2, seq, 48)) for i in (5, 6))
    (y, _), grads = value_and_gradients(lambda lp, h: (T._mix_kda(cfg, lp, h), None), lp, h, probe)
    (y_parent, _), grads_parent = value_and_gradients(lambda lp, h: (parents_mix_kda(cfg, lp, h), None), lp, h, probe)
    assert set(grads[0]) >= {"wq", "wk", "wv", "wo", "w_fa", "w_fb", "w_ga", "w_gb", "w_beta", "conv_q", "conv_k", "conv_v", "a_log", "dt_bias", "o_norm"}
    assert largest_difference(y, y_parent) < 2e-6
    assert largest_difference(grads, grads_parent) < 1e-5


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


