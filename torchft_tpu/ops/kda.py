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

The backward is autodiff of these same products: chunked too. Operands of
the large products are in the inputs' dtype (bfloat16 in the model) with
float32 accumulation; the state, the decay, ``A`` and ``T`` are float32.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

__all__ = ["kda_chunked", "kda_recurrent", "short_conv"]

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
    dt = v.dtype
    pad = -s % chunk
    if pad:
        q, k, v, g = (jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0))) for x in (q, k, v, g))
        beta = jnp.pad(beta, ((0, 0), (0, pad), (0, 0)))
    n = (s + pad) // chunk

    def chunks(x):  # [B, S, H, d] -> [B, H, N, C, d]
        return jnp.moveaxis(x.reshape(b, n, chunk, h, x.shape[-1]), 3, 1)

    f32 = jnp.float32
    qc, kc, vc, gc = chunks(q).astype(f32), chunks(k).astype(f32), chunks(v), chunks(g.astype(f32))
    bc = chunks(beta.astype(f32)[..., None])[..., 0]  # [B, H, N, C]
    tril = jnp.tril(jnp.ones((chunk, chunk), f32))
    G = jnp.einsum("ij,...jc->...ic", tril, gc, precision=_HIGHEST)  # running sum in a chunk
    a_kk = _decayed_lower(kc, kc, G, diag=False)
    a_qk = _decayed_lower(qc, kc, G, diag=True)
    T = _unit_lower_inverse(bc[..., None] * a_kk) * bc[..., None, :]  # (I + diag(b) A)^-1 diag(b)
    g_end = G[..., -1:, :]
    k_in = (kc * jnp.exp(G)).astype(dt)  # what a chunk's keys read of S0
    q_in = (qc * jnp.exp(G)).astype(dt)
    k_out = (kc * jnp.exp(g_end - G)).astype(dt)  # what they leave in the state at its end
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

    S0 = jnp.zeros((b, h, dk, dv), f32) if initial_state is None else initial_state.astype(f32)
    xs = tuple(jnp.moveaxis(x, 2, 0) for x in (w_k, w_v, k_out, decay))
    S_end, (starts, us) = jax.lax.scan(step, S0, xs)
    starts, us = jnp.moveaxis(starts, 0, 2), jnp.moveaxis(us, 0, 2)  # [B, H, N, ...]
    o = jnp.einsum("...ck,...kd->...cd", q_in, starts.astype(dt), preferred_element_type=f32)
    o = o + jnp.einsum("...ij,...jd->...id", a_qk.astype(dt), us.astype(dt), preferred_element_type=f32)
    o = jnp.moveaxis(o, 1, 3).reshape(b, s + pad, h, dv)[:, :s]
    return o.astype(dt), S_end


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
