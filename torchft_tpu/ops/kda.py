"""Gated delta-rule linear attention with a per-channel decay (KDA), in
chunked form, and the causal short convolution that feeds it.

Per head, with a float32 state ``S`` [dk, dv], log-decay ``g_t`` [dk] <= 0,
``beta_t`` in (0, 1), ``k_t`` L2-normalised::

    S <- diag(exp(g_t)) S
    S <- S + beta_t k_t (v_t - S^T k_t)^T
    o_t = S^T q_t

Nothing here is sequential over single positions. A sequence is cut into
chunks of ``chunk`` (64) positions; with ``G`` the running sum of ``g``
inside a chunk and ``S0`` the state at its start,

* the pseudo-values ``u_i = beta_i (v_i - S_{i-1}^T (exp(g_i) k_i))`` of a
  chunk solve ``(I + diag(beta) A_kk) u = diag(beta) (v - (k e^G) S0)``, a
  unit lower-triangular system: ``T = (I + diag(beta) A_kk)^-1 diag(beta)``
  is formed for every chunk at once (:func:`_unit_lower_inverse`), so that
  ``u = T v - (T k e^G) S0`` costs the scan over chunks two matrix products;
* ``A[i, j] = sum_c a_ic k_jc exp(G_ic - G_jc)`` (rows ``a`` = k for the
  solve, q for the output; j <= i) is built exactly whatever the decay
  (:func:`_decayed_lower`: matrix products around a row of the chunk near
  the pair, and where a channel decays too much inside 16 positions for
  that, element by element): ``k exp(-G)`` as one factor overflows float32
  within a chunk for decays a trained model really has;
* the scan over chunks carries ``S`` and does, a chunk, ``u = Tv - Wk S``
  and ``S <- diag(e^{G_C}) S + (k e^{G_C - G})^T u``; the outputs
  ``o = (q e^G) S0 + A_qk u`` are taken for all chunks at once after it.

Operands of the large products are in the inputs' dtype (bfloat16 in the
model) with float32 accumulation; the state, the decay, ``A`` and ``T`` are
float32.

Which code runs where. :func:`kda_chunked` has two forms of this mathematics
and picks by what it sees in its input, no field and no switch:

* heads 128 wide in chunks of 64 — the model on a chip — go to the Pallas
  kernels of ``ops/pallas/kda.py``, forward and a hand-written backward
  under one ``custom_vjp`` (:func:`_by_decay`): a chunk's products and a
  head's state stay in VMEM, where this file's ``jax.numpy`` spreads them
  over thousands of small ops through HBM (PERF.md §6, PR 36). On the CPU
  the kernels run interpreted, in the tests only in effect: no model there
  has such heads.
* the kernels build the pairs inside a sub-block of 16 positions as a product
  of two factors around its first row (:func:`_two_factors`), which overflows
  float32 for a channel that decays over 80 nats inside one. A call whose
  ``g`` has such a channel (:func:`_kernel_serves`, a ``lax.cond`` inside
  the ``custom_vjp``'s forward AND backward rules, so that no branch's
  residuals are written for the other) takes the EXACT path whole:
  :func:`_chunked`, the ``jax.numpy`` form, whose :func:`_decayed_lower`
  falls back to pairs element by element and whose backward is autodiff of
  the same products. A trained model can have such channels; a model near
  its initial decays has none.
* every other shape (a head width that is no lane tile, another chunk; the
  CPU rehearsals' heads of 8 and 16) is :func:`_chunked` directly, and so
  is any call traced under a mesh of more than one device, where a Mosaic
  call needs a ``shard_map`` around it that the mixer does not have yet.

:func:`gdn_chunked` — ONE decay a head a position, key heads shared by value
heads (Gated DeltaNet) — picks the same way (:func:`gdn_core` says which):

* heads 128 wide in chunks of 64 on one device go to a kernel pair of their
  own in the same file (``gdn_forward`` / ``gdn_backward``, one ``custom_vjp``,
  :func:`_gdn_kernels`), at EVERY decay: with one decay a row a pair is
  ``(rows · kᵀ) ⊙ exp(the sum of g between the two)``, one product and one
  factor whose exponent is <= 0, so there are no sub-blocks, no two factors to
  overflow, no ``lax.cond`` on the call's ``g`` and no exact path beside the
  kernels. q and k are read in place, a key head once for all its value heads;
  g, beta and their gradients are one float32 a head a position (PERF.md §6,
  PR 55);
* every other call is :func:`_chunked` with the key heads repeated and the
  pairs by :func:`_scalar_lower`, as under a mesh of several devices.

:func:`ssd_chunked` is the third decay rule on the same helpers, the scalar
decay WITHOUT the delta rule's correction (Mamba-2's state-space duality):
``S_t = exp(g_t) S_{t-1} + k_t ⊗ v_t``, ``o_t = S_tᵀ q_t`` with ONE q and ONE k
a position for all the heads. There is no triangular system and no ``M⁻¹``: a
chunk's pairs are :func:`_scalar_lower` of :func:`_sums_between`, ``q·kᵀ``
taken once a chunk for all heads, and the state's two products run over all
heads' lanes at once. It picks as the two above (:func:`ssd_core` says which):

* a state of whole lane tiles under heads 64 or 128 wide, in chunks of 128 on
  one device — the model on a chip — goes to a third kernel pair of the same
  file (``ssd_forward`` / ``ssd_backward``, one ``custom_vjp``,
  :func:`_ssd_kernels`), at every decay: a chunk's pair matrices and a head's
  state stay in VMEM, where the ``jax.numpy`` form writes ``float32[heads,
  chunks, 128, 128]`` to HBM pass after pass, forward and again for autodiff
  (PERF.md §6, PR 70). The state at every chunk's start is the one residual;
* every other call — the CPU rehearsals' widths, another chunk, mixed dtypes,
  a mesh of several devices — is :func:`_ssd_chunks` in ``jax.numpy``, its
  backward autodiff: what the kernels are tested against beside
  :func:`ssd_recurrent`.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

__all__ = [
    "kda_chunked", "gdn_chunked", "gdn_core", "kda_recurrent", "short_conv", "ssd_chunked", "ssd_core", "ssd_recurrent",
]

_HIGHEST = jax.lax.Precision.HIGHEST
# three bfloat16 passes: float32 operands to about 2^-16, half the passes of
# HIGHEST; for the small float32 products whose results are rounded to the
# operands' dtype (bfloat16 in the model) right after
_HIGH = jax.lax.Precision.HIGH


def short_conv(x: jnp.ndarray, w: jnp.ndarray, before: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Causal depthwise convolution over the sequence, one filter a channel.
    x [B, S, C], w [K, C]: ``y_t = sum_i w[i] x_{t-K+1+i}`` (the last tap is
    the current position). ``before`` [B, K-1, C]: the positions ahead of
    ``x`` (a sequence taken block by block); absent: zeros, the sequence's
    start."""
    taps, s = w.shape[0], x.shape[1]
    if before is None:
        xp = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    else:
        xp = jnp.concatenate([before.astype(x.dtype), x], axis=1)
    return sum(xp[:, i : i + s] * w[i] for i in range(taps))


_SUB = 16  # positions of a sub-block: inside one, pairs have no row between them
# the most a channel may decay inside a sub-block (nats) for its pairs to be
# taken as a product of two factors around the sub-block's first row:
# exp(88) is float32's largest
_TWO_FACTOR_NATS = 80.0


def _two_factors(rb, kb, gb):
    """The pairs inside each sub-block [..., n, sub, d] -> [..., n, sub, sub]
    as one product around the sub-block's first row."""
    ref = gb[..., :1, :]
    return jnp.einsum(
        "...ic,...jc->...ij", rb * jnp.exp(gb - ref), kb * jnp.exp(ref - gb), precision=_HIGH
    )


def _by_diagonals(rb, kb, gb):
    """The same pairs with ``exp`` of the difference itself, one diagonal of
    the sub-blocks after another: a diagonal's memory at a time."""
    sub = rb.shape[-2]
    at = jnp.arange(sub)

    def diagonal(inside, off):  # the pairs (i, i - off) of every sub-block
        back = lambda x: jnp.roll(x, off, axis=-2)  # row i holds position i - off
        gap = jnp.where((at >= off)[:, None], gb - back(gb), -jnp.inf)
        line = jnp.sum(rb * back(kb) * jnp.exp(gap), axis=-1)
        return inside + jnp.where(at[:, None] - at[None, :] == off, line[..., None], 0.0), None

    return jax.lax.scan(jax.checkpoint(diagonal), jnp.zeros(rb.shape[:-1] + (sub,), jnp.float32), at)[0]


def _decays_mildly(gb):
    """No channel decays more than :data:`_TWO_FACTOR_NATS` inside a sub-block."""
    return jnp.max(gb[..., :1, :] - gb[..., -1:, :]) < _TWO_FACTOR_NATS


@jax.custom_vjp
def _inside(rb, kb, gb):
    # fresh closures at every call, as ops/layers._grouped_matmul's: with
    # JAX_EXPLAIN_CACHE_MISSES on, jax 0.9.0 raises inside its own explanation
    # when a branch function it has seen is traced at a second shape
    return jax.lax.cond(
        _decays_mildly(gb), lambda *a: _two_factors(*a), lambda *a: _by_diagonals(*a), rb, kb, gb
    )


def _inside_bwd(operands, ct):
    # the branch taken, again from the operands: ``lax.cond``'s own backward
    # carries every branch's residuals out of the forward, zeros for the one
    # not taken (1 GB a call at the model's shapes, written every call)
    pull = lambda branch: lambda operands, ct: jax.vjp(branch, *operands)[1](ct)
    return jax.lax.cond(_decays_mildly(operands[2]), pull(_two_factors), pull(_by_diagonals), operands, ct)


_inside.defvjp(lambda *operands: (_inside(*operands), operands), _inside_bwd)


def _decayed_lower(rows: jnp.ndarray, k: jnp.ndarray, G: jnp.ndarray, diag: bool) -> jnp.ndarray:
    """``A[..., i, j] = sum_c rows[i, c] k[j, c] exp(G[i, c] - G[j, c])`` for
    j < i (and j = i with ``diag``), zero above; rows, k, G [..., C, d] with
    ``G`` non-increasing along C. float32, exact whatever the decay.

    The chunk is cut into sub-blocks of :data:`_SUB` positions. The rows of
    sub-block I against the keys of earlier sub-blocks are a matrix product of
    ``rows_i exp(G_i - G_r)`` and ``k_j exp(G_r - G_j)``, r the first row of
    I: r lies between the pair and both exponents are <= 0. For a key inside
    I the second is >= 0, bounded by what a channel decays inside one
    sub-block: where that is under :data:`_TWO_FACTOR_NATS` for every channel
    of the call — any model near its initial decays — nothing overflows and
    the pairs inside all sub-blocks are one more product of the same factors
    (:func:`_two_factors`). Else (``lax.cond``) they are taken element by
    element (:func:`_by_diagonals`): 16 times the exponentials, exact for
    decays of any size."""
    c, d = rows.shape[-2:]
    sub = min(_SUB, c)
    assert c % sub == 0, f"chunk {c} is not whole sub-blocks of {sub}"
    n = c // sub
    lead = rows.shape[:-2]
    rb, kb, gb = (x.reshape(lead + (n, sub, d)) for x in (rows, k, G))
    inside = _inside(rb, kb, gb)
    out = []
    for b in range(n):
        ref = gb[..., b, :1, :]
        parts = [inside[..., b, :, :]]
        if b:
            parts.insert(0, jnp.einsum(
                "...ic,...jc->...ij",
                rb[..., b, :, :] * jnp.exp(gb[..., b, :, :] - ref),
                k[..., : b * sub, :] * jnp.exp(ref - G[..., : b * sub, :]),
                precision=_HIGH,
            ))
        if c - (b + 1) * sub:
            parts.append(jnp.zeros(lead + (sub, c - (b + 1) * sub), jnp.float32))
        out.append(jnp.concatenate(parts, axis=-1))
    i, j = jnp.arange(c)[:, None], jnp.arange(c)[None, :]
    return jnp.where((j <= i) if diag else (j < i), jnp.concatenate(out, axis=-2), 0.0)


def _sums_between(g: jnp.ndarray) -> jnp.ndarray:
    """``[..., i, j] = sum of g_t over j < t <= i`` (0 where j >= i) for ONE
    log-decay a position, g [..., C, 1]: what position j's write has decayed
    by when position i reads it, summed over the pair's own positions. The
    difference of two running sums is the same number less exactly: where a
    head forgets tens of nats a position the sums reach thousands, their
    float32 steps 1e-4, and a pair that decays little between two such
    stretches would carry that as a relative error."""
    c = g.shape[-2]
    t, j = jnp.arange(c)[:, None], jnp.arange(c)[None, :]
    tril = jnp.tril(jnp.ones((c, c), jnp.float32))
    return jnp.einsum("it,...tj->...ij", tril, jnp.where(t > j, g, 0.0), precision=_HIGHEST)


def _scalar_lower(rows: jnp.ndarray, k: jnp.ndarray, between: jnp.ndarray, diag: bool) -> jnp.ndarray:
    """:func:`_decayed_lower` where a head has ONE decay a position:
    ``A[i, j] = (rows_i · k_j) exp(between[i, j])`` (:func:`_sums_between`),
    one product and one factor whose exponent is <= 0 — nothing overflows
    whatever the decay, and no sub-blocks are needed."""
    c = rows.shape[-2]
    i, j = jnp.arange(c)[:, None], jnp.arange(c)[None, :]
    pairs = jnp.einsum("...ic,...jc->...ij", rows, k, precision=_HIGH) * jnp.exp(between)
    return jnp.where((j <= i) if diag else (j < i), pairs, 0.0)


_BASE = 8  # the largest block whose inverse is taken in product form


def _unit_lower_inverse(L: jnp.ndarray) -> jnp.ndarray:
    """``(I + L)^-1`` for strictly lower-triangular L [..., C, C] — the
    triangular solve of the delta rule — by blocks, in matrix products:
    the diagonal blocks of :data:`_BASE` rows by the product form
    ``(I - D)(I + D^2)(I + D^4)`` (exact, ``D^8 = 0``; its powers stay under
    35 whatever the entries, so nothing cancels), then pairs of blocks merged,
    ``[[A, 0], [C, B]]^-1 = [[A^-1, 0], [-B^-1 C A^-1, B^-1]]``, up to C: block
    forward substitution, as stable as the solve row by row. The product form
    over the whole chunk was what ran here first: its powers of L reach 1e9
    where a chunk's keys are alike and beta is near 1 — any model one optimizer
    step from its initial values — and the sum cancels to nothing; XLA's own
    ``triangular_solve`` is stable and took 22 ms a call on the v5e where this
    takes about one (PERF.md §6, PR 35)."""
    c = L.shape[-1]
    lead = L.shape[:-2]
    s = min(_BASE, c)
    assert c % s == 0 and (c // s) & (c // s - 1) == 0, f"chunk {c} is not a power of two of blocks of {s}"

    def diagonal_blocks(size):  # [..., C/size, size, size]
        n = c // size
        return jnp.einsum("...pipj->...pij", L.reshape(lead + (n, size, n, size)))

    D = diagonal_blocks(s)
    eye = jnp.eye(s, dtype=L.dtype)
    inv, power, reach = eye - D, D, 2
    while reach < s:
        power = jnp.matmul(power, power, precision=_HIGHEST)
        inv = jnp.matmul(inv, eye + power, precision=_HIGHEST)
        reach *= 2
    while s < c:
        below = diagonal_blocks(2 * s)[..., s:, :s]  # C of each pair of blocks
        a, b = inv[..., 0::2, :, :], inv[..., 1::2, :, :]
        corner = -jnp.matmul(jnp.matmul(b, below, precision=_HIGHEST), a, precision=_HIGHEST)
        inv = jnp.concatenate([
            jnp.concatenate([a, jnp.zeros_like(a)], axis=-1),
            jnp.concatenate([corner, b], axis=-1),
        ], axis=-2)
        s *= 2
    return inv[..., 0, :, :]


@jax.custom_vjp
def _unit_lower_inverse_written(L: jnp.ndarray) -> jnp.ndarray:
    """:func:`_unit_lower_inverse` with its gradient WRITTEN: ``X = (I + L)^-1``
    gives ``dL = -Xᵀ dX Xᵀ``, two products of what the forward already holds,
    where autodiff walks back through every 8-row block and every merge — 31
    of the 87 ms a layer that three passes of the scalar-decay rule took at
    b2 x s8192 x 32 x 128 on a v5e (PERF.md §6, PR 54). What falls above the
    diagonal of ``dL`` meets the mask that made ``L``."""
    return _unit_lower_inverse(L)


def _unit_lower_inverse_written_bwd(X, dX):
    Xt = jnp.swapaxes(X, -1, -2)
    return (-jnp.matmul(jnp.matmul(Xt, dX, precision=_HIGHEST), Xt, precision=_HIGHEST),)


_unit_lower_inverse_written.defvjp(lambda L: (_unit_lower_inverse(L),) * 2, _unit_lower_inverse_written_bwd)


def _chunked(q, k, v, g, beta, S0, chunk):
    """The chunked rule in ``jax.numpy``, exact whatever the decay: q, k, v, g
    [B, S, H, d] with S whole chunks (g [B, S, H, 1]: one decay a head,
    :func:`gdn_chunked`), beta [B, S, H], S0 [B, H, dk, dv]
    float32. What :func:`kda_chunked` runs for widths the kernels do not take
    and, at any width, for a call with a channel that decays too fast for them."""
    b, s, h, dk = q.shape
    dv = v.shape[-1]
    dt = v.dtype
    n = s // chunk

    def chunks(x):  # [B, S, H, d] -> [B, H, N, C, d]
        return jnp.moveaxis(x.reshape(b, n, chunk, h, x.shape[-1]), 3, 1)

    f32 = jnp.float32
    qc, kc, vc, gc = chunks(q).astype(f32), chunks(k).astype(f32), chunks(v), chunks(g.astype(f32))
    bc = chunks(beta.astype(f32)[..., None])[..., 0]  # [B, H, N, C]
    tril = jnp.tril(jnp.ones((chunk, chunk), f32))
    G = jnp.einsum("ij,...jc->...ic", tril, gc, precision=_HIGHEST)  # running sum in a chunk
    if g.shape[-1] == 1:
        between = _sums_between(gc)
        pairs, inverse, to_end = _scalar_lower, _unit_lower_inverse_written, between[..., -1, :, None]
    else:
        between, pairs, inverse, to_end = G, _decayed_lower, _unit_lower_inverse, None
    a_kk = pairs(kc, kc, between, diag=False)
    a_qk = pairs(qc, kc, between, diag=True)
    T = inverse(bc[..., None] * a_kk) * bc[..., None, :]  # (I + diag(b) A)^-1 diag(b)
    g_end = G[..., -1:, :]
    k_in = (kc * jnp.exp(G)).astype(dt)  # what a chunk's keys read of S0
    q_in = (qc * jnp.exp(G)).astype(dt)
    k_out = (kc * jnp.exp(g_end - G if to_end is None else to_end)).astype(dt)  # what they leave in the state at its end
    Td = T.astype(dt)
    w_v = jnp.einsum("...ij,...jd->...id", Td, vc, preferred_element_type=f32)
    w_k = jnp.einsum("...ij,...jd->...id", Td, k_in, preferred_element_type=f32).astype(dt)
    decay = jnp.exp(g_end[..., 0, :])  # [B, H, N, dk]

    def step(S, xs):
        w_k_n, w_v_n, k_out_n, decay_n = xs
        u = w_v_n - jnp.einsum("bhck,bhkd->bhcd", w_k_n, S.astype(dt), preferred_element_type=f32)
        S_next = decay_n[..., None] * S + jnp.einsum(
            "bhck,bhcd->bhkd", k_out_n, u.astype(dt), preferred_element_type=f32
        )
        return S_next, (S, u)

    xs = tuple(jnp.moveaxis(x, 2, 0) for x in (w_k, w_v, k_out, decay))
    S_end, (starts, us) = jax.lax.scan(step, S0, xs)
    starts, us = jnp.moveaxis(starts, 0, 2), jnp.moveaxis(us, 0, 2)  # [B, H, N, ...]
    o = jnp.einsum("...ck,...kd->...cd", q_in, starts.astype(dt), preferred_element_type=f32)
    o = o + jnp.einsum("...ij,...jd->...id", a_qk.astype(dt), us.astype(dt), preferred_element_type=f32)
    o = jnp.moveaxis(o, 1, 3).reshape(b, s, h, dv)
    return o.astype(dt), S_end


def _kernel_serves(g):
    """What :func:`_decays_mildly` asks of every sub-block of a call, from
    ``g`` [B, S, H, d] itself: the kernels build the pairs inside a sub-block
    as :func:`_two_factors` does and have no other form."""
    b, s, h, d = g.shape
    subs = g.astype(jnp.float32).reshape(b, s // _SUB, _SUB, h, d)
    # from a sub-block's first row to its last: all of it but the first row
    return jnp.max(subs[:, :, 0] - jnp.sum(subs, axis=2)) < _TWO_FACTOR_NATS


def _wide(x):  # [B, S, H, d] -> the [B, S, H·d] view the kernels read heads from
    return x.reshape(x.shape[:2] + (-1,))


def _kernels():
    # at first use: importing pallas takes most of a second, and a model
    # without a kda layer never needs it
    from torchft_tpu.ops.pallas import kda

    return kda


def _kernel_forward(q, k, v, g, beta, S0):
    with jax.named_scope("kda_kernel"):
        o, starts, inv, S_end = _kernels().kda_forward(
            _wide(q), _wide(k), _wide(v), _wide(g.astype(jnp.float32)), beta.astype(jnp.float32), S0
        )
    return o.reshape(v.shape), S_end, starts, inv


def _kernel_backward(q, k, v, g, beta, S0, starts, inv, do, d_end):
    with jax.named_scope("kda_kernel"):
        dq, dk, dv, dg, dbeta, dS0 = _kernels().kda_backward(
            _wide(q), _wide(k), _wide(v), _wide(g.astype(jnp.float32)), beta.astype(jnp.float32),
            starts, inv, _wide(do), d_end,
        )
    return (
        dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape),
        dg.reshape(g.shape).astype(g.dtype), dbeta.astype(beta.dtype), dS0,
    )


# A ``jax.jit`` of its own, as the kernels' two calls are: a model's layers, and
# a layer's forward, recomputed forward and backward, share one trace of this
# form, one differentiation and one lowering — seconds of a program's set-up
# each — though a model near its initial decays never runs it.
@jax.jit
def _exact(q, k, v, g, beta, S0):
    return _chunked(q, k, v, g, beta, S0, _kernels().CHUNK)


# The path is chosen by the call's ``g``, INSIDE the forward and the backward
# rule of one ``custom_vjp`` (as :func:`_inside`): ``lax.cond``'s own backward
# would carry both branches' residuals out of the forward, zeros for the one
# not taken. Branches are fresh closures at every call (see :func:`_inside`).
@jax.custom_vjp
def _by_decay(q, k, v, g, beta, S0):
    return jax.lax.cond(
        _kernel_serves(g), lambda *a: _kernel_forward(*a)[:2], lambda *a: _exact(*a),
        q, k, v, g, beta, S0,
    )


def _by_decay_fwd(q, k, v, g, beta, S0):
    # the states at the chunks' starts and the pairs of chunks' M^-1 are the
    # kernel path's residuals beside the inputs; the exact path differentiates
    # itself and leaves zeros
    b, s, h, d = q.shape
    chunk, rows = _kernels().CHUNK, _kernels().ROWS
    serves = _kernel_serves(g)
    o, S_end, starts, inv = jax.lax.cond(
        serves,
        lambda *a: _kernel_forward(*a),
        lambda *a: _exact(*a) + (
            jnp.zeros((b, h, s // chunk, d, d), jnp.float32), jnp.zeros((b, h, s // rows, rows, rows), v.dtype),
        ),
        q, k, v, g, beta, S0,
    )
    return (o, S_end), (serves, q, k, v, g, beta, S0, starts, inv)


def _by_decay_bwd(res, cts):
    def exact(q, k, v, g, beta, S0, starts, inv, do, d_end):
        return jax.vjp(lambda *a: _exact(*a), q, k, v, g, beta, S0)[1]((do, d_end))

    return jax.lax.cond(res[0], lambda *a: _kernel_backward(*a), exact, *res[1:], *cts)


_by_decay.defvjp(_by_decay_fwd, _by_decay_bwd)


def kda_chunked(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    g: jnp.ndarray,
    beta: jnp.ndarray,
    chunk: int = 64,
    initial_state: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """q, k [B, S, H, dk] (as they enter the rule: normalised, q scaled);
    v [B, S, H, dv]; g [B, S, H, dk] float32 log-decay (<= 0); beta
    [B, S, H] float32. Returns (o [B, S, H, dv] in v's dtype, the final
    state [B, H, dk, dv] float32). Any S: the tail is padded with positions
    that neither decay nor write (g = 0, beta = 0)."""
    b, s, h, dk = q.shape
    dv = v.shape[-1]
    kernels = _kernels_take(q, k, v, chunk)
    pad = -s % (_kernels().ROWS if kernels else chunk)
    if pad:
        q, k, v, g = (jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0))) for x in (q, k, v, g))
        beta = jnp.pad(beta, ((0, 0), (0, pad), (0, 0)))
    S0 = jnp.zeros((b, h, dk, dv), jnp.float32) if initial_state is None else initial_state.astype(jnp.float32)
    if kernels:
        o, S_end = _by_decay(q, k, v, g, beta, S0)
    else:
        o, S_end = _chunked(q, k, v, g, beta, S0, chunk)
    return o[:, :s], S_end


def _kernels_take(q, k, v, chunk) -> bool:
    """Whether a call is the Pallas kernels': heads of whole lane tiles in the
    kernels' chunk (they take two at a time), one dtype, one device. Under a
    mesh of several devices the SPMD partitioner refuses a Mosaic call outside
    a ``shard_map`` (``transformer._flash_sharded``): such a program keeps the
    ``jax.numpy`` form until its mixer brings one."""
    dk, dv = q.shape[-1], v.shape[-1]
    return (
        dk % 128 == 0  # before pallas is imported at all
        and _kernels().serves(dk, dv, chunk) and q.dtype == k.dtype == v.dtype
        and jax.sharding.get_abstract_mesh().size <= 1
    )


def gdn_core(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, chunk: int = 64) -> str:
    """Which code :func:`gdn_chunked` runs for these inputs, from what it sees
    in them (:func:`_kernels_take`): ``"gdn_kernel"`` — the Pallas kernel pair
    of ``ops/pallas/kda.py`` with one decay a head — or ``"jax.numpy"``
    (:func:`_chunked`)."""
    return "gdn_kernel" if _kernels_take(q, k, v, chunk) else "jax.numpy"


# One ``custom_vjp`` around the kernel pair, and no ``lax.cond`` in it: the
# pairs of one decay a head are ``(q kᵀ) ⊙ exp(sums between)`` with exponents
# <= 0, so the kernels serve every decay and there is no other form to fall to.
@jax.custom_vjp
def _gdn_kernels(q, k, v, g, beta, S0):
    return _gdn_kernels_fwd(q, k, v, g, beta, S0)[0]


def _gdn_kernels_fwd(q, k, v, g, beta, S0):
    with jax.named_scope("gdn_kernel"):
        o, starts, inv, S_end = _kernels().gdn_forward(_wide(q), _wide(k), _wide(v), g, beta, S0)
    # the states at the chunks' starts and the pairs of chunks' M^-1 are the residuals beside the inputs
    return (o.reshape(v.shape), S_end), (q, k, v, g, beta, starts, inv)


def _gdn_kernels_bwd(res, cts):
    q, k, v, g, beta, starts, inv = res
    do, d_end = cts
    with jax.named_scope("gdn_kernel"):
        dq, dk, dv, dg, dbeta, dS0 = _kernels().gdn_backward(
            _wide(q), _wide(k), _wide(v), g, beta, starts, inv, _wide(do), d_end
        )
    return dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape), dg, dbeta, dS0


_gdn_kernels.defvjp(_gdn_kernels_fwd, _gdn_kernels_bwd)


def gdn_chunked(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    g: jnp.ndarray,
    beta: jnp.ndarray,
    chunk: int = 64,
    initial_state: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The same rule with ONE log-decay a head a position and key heads shared
    by value heads (Gated DeltaNet): q, k [B, S, Hk, dk]; v [B, S, Hv, dv], Hv
    a multiple of Hk, value head j reads key head ``j // (Hv / Hk)``; g
    [B, S, Hv] float32 (<= 0) and beta [B, S, Hv]. Returns (o [B, S, Hv, dv],
    the final state [B, Hv, dk, dv] float32); any S, as :func:`kda_chunked`.

    Two forms, picked by what the call's inputs are (:func:`gdn_core`), no field
    and no switch. Heads 128 wide in chunks of 64 on one device — the model on
    a chip — run the Pallas kernel pair ``gdn_forward`` / ``gdn_backward`` under
    one ``custom_vjp``, at EVERY decay: a pair is ``(rows · kᵀ) ⊙ exp(the sum of
    g between them)``, one product and one factor whose exponent is <= 0, so
    there is nothing to overflow, no condition on the call's ``g`` and no exact
    path beside it (the per-channel kernels, handed this decay broadcast over a
    head's lanes, serve under 80 nats in 16 positions only, and the published
    initial values forget ~20 nats a POSITION: PERF.md §6, PR 54). q and k are
    read in place, a key head once for all its value heads; g, beta and their
    gradients travel as one float32 a head a position. Every other call —
    a head width that is no lane tile (the CPU rehearsals), another chunk,
    mixed dtypes, a program traced under a mesh of several devices — is
    :func:`_chunked` in ``jax.numpy`` with the key heads repeated, the pairs of
    a chunk by :func:`_scalar_lower`, its backward autodiff."""
    b, s, hk, dk = q.shape
    hv, dv = v.shape[2:]
    assert hv % hk == 0, f"{hv} value heads do not divide over {hk} key heads"
    kernels = gdn_core(q, k, v, chunk) == "gdn_kernel"
    g = g.astype(jnp.float32)
    pad = -s % (_kernels().ROWS if kernels else chunk)
    if pad:
        q, k, v = (jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0))) for x in (q, k, v))
        g, beta = (jnp.pad(x, ((0, 0), (0, pad), (0, 0))) for x in (g, beta))
    S0 = jnp.zeros((b, hv, dk, dv), jnp.float32) if initial_state is None else initial_state.astype(jnp.float32)
    if kernels:
        o, S_end = _gdn_kernels(q, k, v, g, beta.astype(jnp.float32), S0)
    else:
        q, k = (jnp.repeat(x, hv // hk, axis=2) for x in (q, k))
        o, S_end = _chunked(q, k, v, g[..., None], beta, S0, chunk)
    return o[:, :s], S_end


def kda_recurrent(q, k, v, g, beta, initial_state=None):
    """The rule itself, one position after another (a ``lax.scan`` over S):
    what :func:`kda_chunked` is held to by the tests and by ``chip_smoke.py``.
    Same arguments and results, everything in float32."""
    f32 = jnp.float32
    b, s, h, dk = q.shape
    dv = v.shape[-1]

    def step(S, xs):
        q_t, k_t, v_t, g_t, b_t = xs  # [B, H, d], beta [B, H]
        S = jnp.exp(g_t)[..., None] * S
        pred = jnp.einsum("bhkd,bhk->bhd", S, k_t, precision=_HIGHEST)
        S = S + jnp.einsum("bhk,bhd->bhkd", k_t, b_t[..., None] * (v_t - pred), precision=_HIGHEST)
        return S, jnp.einsum("bhkd,bhk->bhd", S, q_t, precision=_HIGHEST)

    S0 = jnp.zeros((b, h, dk, dv), f32) if initial_state is None else initial_state.astype(f32)
    xs = tuple(jnp.moveaxis(x.astype(f32), 1, 0) for x in (q, k, v, g, beta))
    S_end, o = jax.lax.scan(step, S0, xs)
    return jnp.moveaxis(o, 0, 1).astype(v.dtype), S_end


def ssd_core(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, chunk: int = 64) -> str:
    """Which code :func:`ssd_chunked` runs for these inputs, from what it sees
    in them: ``"ssd_kernel"`` — the Pallas kernel pair of ``ops/pallas/kda.py``
    without a correction — where the state is whole lane tiles, the heads'
    lanes are whole tiles of heads 64 or 128 wide, the chunk is the kernels'
    128, q, k and v are of one dtype and the program is one device's (as
    :func:`_kernels_take`); ``"jax.numpy"`` for every other call — one with no
    sequence in it too, which has no grid to run."""
    n_state, (heads, p) = q.shape[-1], v.shape[2:]
    kernels = (
        n_state % 128 == 0 and heads * p % 128 == 0 and v.size > 0  # before pallas is imported at all
        and _kernels().ssd_serves(n_state, heads, p, chunk) and q.dtype == k.dtype == v.dtype
        and jax.sharding.get_abstract_mesh().size <= 1
    )
    return "ssd_kernel" if kernels else "jax.numpy"


# One ``custom_vjp`` around the kernel pair, as :func:`_gdn_kernels`: every exponent is <= 0, so the kernels serve
# every decay. The state at every chunk's start is the one residual beside the inputs.
@jax.custom_vjp
def _ssd_kernels(q, k, v, g, S0):
    return _ssd_kernels_fwd(q, k, v, g, S0)[0]


def _ssd_kernels_fwd(q, k, v, g, S0):
    with jax.named_scope("ssd_kernel"):
        o, starts, S_end = _kernels().ssd_forward(q, k, _wide(v), g, S0)
    return (o.reshape(v.shape), S_end), (q, k, v, g, starts)


def _ssd_kernels_bwd(res, cts):
    q, k, v, g, starts = res
    do, d_end = cts
    with jax.named_scope("ssd_kernel"):
        dq, dk, dv, dg, dS0 = _kernels().ssd_backward(q, k, _wide(v), g, starts, _wide(do), d_end)
    return dq, dk, dv.reshape(v.shape), dg, dS0


_ssd_kernels.defvjp(_ssd_kernels_fwd, _ssd_kernels_bwd)


def ssd_chunked(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    g: jnp.ndarray,
    chunk: int = 64,
    initial_state: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The scalar-decay rule without a correction (Mamba-2's SSD, one group):
    ``S_t = exp(g_t) S_{t-1} + k_t ⊗ v_t``, ``o_t = S_tᵀ q_t``. q, k [B, S, N]
    — ONE a position, shared by all heads (a state-space layer's C and B); v
    [B, S, H, P] (its ``Δ·x``); g [B, S, H] float32 log-decay (<= 0, its
    ``Δ·A``). Returns (o [B, S, H, P] in v's dtype, the final state
    [B, H, N, P] float32). Any S: the tail is padded with positions that
    neither decay nor write.

    A chunk's pairs are ``(q_i · k_j) exp(sum of g over j < t <= i)``
    (:func:`_scalar_lower` over :func:`_sums_between`: the difference first,
    then ``exp`` of a number <= 0 — no factor ``exp(+Λ)`` exists at any decay),
    the product ``q·kᵀ`` taken ONCE a chunk for all heads. What a chunk's
    positions write is decayed to the chunk's end on the VALUE side (k has no
    head to carry a head's decay) and what they read of the state at the
    chunk's start is decayed on the output side, so both products with the
    state run over all heads' lanes at once, [C, N] x [N, H·P]. Large products
    in the inputs' dtype with float32 accumulation; decays, pairs and the
    state float32.

    Two forms of this, picked by what the call's inputs are (:func:`ssd_core`),
    no field and no switch: a state of whole lane tiles under heads 64 or 128
    wide in chunks of 128 on one device — the model on a chip — runs the Pallas
    kernel pair ``ssd_forward`` / ``ssd_backward`` under one ``custom_vjp``, a
    chunk's pair matrices and a head's state in VMEM; every other call (the
    CPU rehearsals' widths, another chunk, mixed dtypes, a mesh of several
    devices) runs the ``jax.numpy`` below, the scan over chunks elementwise,
    its backward autodiff."""
    b, s, h, p = v.shape
    kernels = ssd_core(q, k, v, chunk) == "ssd_kernel"
    g = g.astype(jnp.float32)
    pad = -s % chunk
    if pad:
        q, k = (jnp.pad(x, ((0, 0), (0, pad), (0, 0))) for x in (q, k))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        g = jnp.pad(g, ((0, 0), (0, pad), (0, 0)))
    S0 = jnp.zeros((b, h, q.shape[-1], p), jnp.float32) if initial_state is None else initial_state.astype(jnp.float32)
    o, S_end = _ssd_kernels(q, k, v, g, S0) if kernels else _ssd_chunks(q, k, v, g, S0, chunk)
    return o[:, :s], S_end


def _ssd_chunks(q, k, v, g, S0, chunk):
    """:func:`ssd_chunked` in ``jax.numpy``: S whole chunks, g and S0 float32.
    What the Pallas kernel pair is tested against beside :func:`ssd_recurrent`."""
    b, s, h, p = v.shape
    n_state, n = q.shape[-1], s // chunk
    dt, f32 = v.dtype, jnp.float32
    qc, kc = (x.reshape(b, n, chunk, n_state) for x in (q, k))
    vc = v.reshape(b, n, chunk, h, p)
    gc = jnp.moveaxis(g.reshape(b, n, chunk, h), 3, 1)[..., None]  # [B, H, n, C, 1]
    between = _sums_between(gc)  # [B, H, n, C, C]
    pairs = _scalar_lower(qc.astype(f32)[:, None], kc.astype(f32)[:, None], between, diag=True)
    to_end = jnp.moveaxis(between[..., -1, :], 1, 3)  # [B, n, C, H]: position j's write, at the chunk's end
    from_start = jnp.moveaxis(between[..., :, 0] + gc[..., :1, 0], 1, 3)  # the chunk's first state, at position i
    o = jnp.einsum("bhnij,bnjhp->bnihp", pairs.astype(dt), vc, preferred_element_type=f32)
    writes = jnp.einsum(
        "bnjk,bnjhp->nbkhp", kc, (vc * jnp.exp(to_end)[..., None]).astype(dt), preferred_element_type=f32
    )
    decay = jnp.moveaxis(jnp.exp(from_start[:, :, -1]), 1, 0)[:, :, None, :, None]  # [n, B, 1, H, 1]: a whole chunk's

    def step(S, xs):  # S [B, N, H, P]
        decay_n, writes_n = xs
        return decay_n * S + writes_n, S

    S_end, starts = jax.lax.scan(step, jnp.moveaxis(S0, 1, 2), (decay, writes))
    read = jnp.einsum("bnik,nbkhp->bnihp", qc, starts.astype(dt), preferred_element_type=f32)
    o = o + read * jnp.exp(from_start)[..., None]
    return o.reshape(b, s, h, p).astype(dt), jnp.moveaxis(S_end, 2, 1)


def ssd_recurrent(q, k, v, g, initial_state=None):
    """:func:`ssd_chunked`'s rule itself, one position after another (a
    ``lax.scan`` over S) in float32: what the tests and ``benchmark/check_granite.py``
    hold the chunked form to. Same arguments and results."""
    f32 = jnp.float32
    b, s, h, p = v.shape

    def step(S, xs):
        q_t, k_t, v_t, g_t = xs  # [B, N], [B, N], [B, H, P], [B, H]
        S = jnp.exp(g_t)[..., None, None] * S + k_t[:, None, :, None] * v_t[:, :, None, :]
        return S, jnp.einsum("bhkp,bk->bhp", S, q_t, precision=_HIGHEST)

    S0 = jnp.zeros((b, h, q.shape[-1], p), f32) if initial_state is None else initial_state.astype(f32)
    xs = tuple(jnp.moveaxis(x.astype(f32), 1, 0) for x in (q, k, v, g))
    S_end, o = jax.lax.scan(step, S0, xs)
    return jnp.moveaxis(o, 0, 1).astype(v.dtype), S_end
