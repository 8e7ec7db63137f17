"""A key selector's scores as pallas TPU kernels: one q block against every
key tile up to the diagonal, forward and backward.

The selector (a "lightning indexer": ``Hi`` heads of ``Di`` lanes over ONE key
head) scores a pair ``I[t, s] = Σ_j w[t, j]·ReLU(q_j[t]·k[s])``. In
``jax.numpy`` the ``[Hi, bq, S]`` products go through HBM in float32 — 0.5 GB a
block of 512 queries at 16 heads and 16 384 keys, written and read — where
here a tile's ``Hi`` products live in VMEM and only the ``[bkc, bq]`` sum
leaves. Both kernels work on TRANSPOSED tiles ``[keys, queries]``, as
``flash_attention``'s do: a query's weight ``w[:, j]`` is then a lane row that
broadcasts along sublanes, and what selects over a row's keys
(``ops.attention.kth_largest``) reduces along sublanes.

Grid (batch, key tile) for the q block ``i`` (a traced index, scalar-prefetched:
the block specs pick the block, so nothing is sliced outside): a tile past the
diagonal is zeros, its keys not fetched. The backward takes the cotangent of
the scores' tile and gives ``dq`` and ``dw`` (summed over the key tiles in
their resident output blocks) and the q block's part of ``dk``; nothing here is
a ``custom_vjp`` — the one caller (``models/transformer._indexer_kl``) emits
the selector's closed-form gradient in its forward pass and calls both.

Precision: operands in their storage dtype into the MXU, float32 accumulation;
ReLU, weights and sums in float32; the backward's ``w·dI`` on the live lanes is
cast to the storage dtype before its two products, as the flash backward casts
dS. On a CPU the kernels run under ``interpret=True``."""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from torchft_tpu.ops.pallas.flash_attention import _NN, _NT, _TN, _dot, _should_interpret

__all__ = ["indexer_scores_t", "indexer_scores_bwd_t"]


def _reaches(i_ref, c, bq, bkc):
    return c * bkc < i_ref[0] * bq + bq  # the key tile starts at or before the q block's last position


def _scores_kernel(i_ref, q_ref, k_ref, w_ref, o_ref, *, heads, bq, bkc):
    c = pl.program_id(1)

    @pl.when(_reaches(i_ref, c, bq, bkc))
    def _sum():
        k = k_ref[...]
        acc = jnp.zeros((bkc, bq), jnp.float32)
        for j in range(heads):
            acc += w_ref[j : j + 1, :] * jnp.maximum(_dot(k, q_ref[j], _NT), 0.0)
        o_ref[...] = acc

    @pl.when(jnp.logical_not(_reaches(i_ref, c, bq, bkc)))
    def _none():
        o_ref[...] = jnp.zeros_like(o_ref)


def _scores_bwd_kernel(i_ref, q_ref, k_ref, w_ref, g_ref, dq_ref, dk_ref, dw_ref, *, heads, bq, bkc):
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _init():
        dq_ref[...] = jnp.zeros_like(dq_ref)
        dw_ref[...] = jnp.zeros_like(dw_ref)

    @pl.when(_reaches(i_ref, c, bq, bkc))
    def _sum():
        k, g = k_ref[...], g_ref[...]
        dk = jnp.zeros(dk_ref.shape, jnp.float32)
        for j in range(heads):
            q = q_ref[j]
            x = _dot(k, q, _NT)
            dw_ref[j : j + 1, :] += jnp.sum(g * jnp.maximum(x, 0.0), axis=0, keepdims=True)
            gx = jnp.where(x > 0.0, g * w_ref[j : j + 1, :], 0.0).astype(q.dtype)
            dq_ref[j] += _dot(gx, k, _TN)
            dk += _dot(gx, q, _NN)
        dk_ref[...] = dk

    @pl.when(jnp.logical_not(_reaches(i_ref, c, bq, bkc)))
    def _none():
        dk_ref[...] = jnp.zeros_like(dk_ref)


def _specs(heads, di, bq, bkc):
    last = lambda i_ref: (i_ref[0] * bq + bq - 1) // bkc  # the last key tile the q block reaches
    return [
        pl.BlockSpec((None, heads, bq, di), lambda b, c, i_ref: (b, 0, i_ref[0], 0)),
        pl.BlockSpec((None, bkc, di), lambda b, c, i_ref: (b, jnp.minimum(c, last(i_ref)), 0)),
        pl.BlockSpec((None, heads, bq), lambda b, c, i_ref: (b, 0, i_ref[0])),
    ]


def _params(bq, bkc):
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"), vmem_limit_bytes=min(112 << 20, (24 << 20) + 48 * bq * bkc)
    )


def indexer_scores_t(
    q_i: jnp.ndarray, k_i: jnp.ndarray, w: jnp.ndarray, i, block_q: int = 128, block_k: int = 128,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """``I[b, s, t] = Σ_j w[b, j, i·bq + t]·ReLU(q_i[b, j, i·bq + t]·k_i[b, s])`` float32 ``[B, S, bq]``: the scores
    of q block ``i`` (traced) against every key, transposed; zeros in the tiles past the block's last position (the
    caller's causal mask covers them). ``q_i`` [B, Hi, S, Di] head-major, ``k_i`` [B, S, Di], ``w`` [B, Hi, S]
    float32 with any constant on it."""
    b, heads, s, di = q_i.shape
    bq, bkc = min(block_q, s), min(block_k, s)
    return pl.pallas_call(
        functools.partial(_scores_kernel, heads=heads, bq=bq, bkc=bkc),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(b, s // bkc), in_specs=_specs(heads, di, bq, bkc),
            out_specs=pl.BlockSpec((None, bkc, bq), lambda b, c, i_ref: (b, c, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((b, s, bq), jnp.float32),
        compiler_params=_params(bq, bkc),
        interpret=_should_interpret() if interpret is None else interpret,
        name="indexer_scores",
    )(jnp.asarray(i, jnp.int32).reshape(1), q_i, k_i, w)


def indexer_scores_bwd_t(
    q_i: jnp.ndarray, k_i: jnp.ndarray, w: jnp.ndarray, g: jnp.ndarray, i, block_k: int = 128,
    interpret: Optional[bool] = None,
):
    """(dq [B, Hi, bq, Di], dk [B, S, Di], dw [B, Hi, bq]), float32: the cotangents of q block ``i``'s rows of ``q_i``
    and ``w`` and the block's part of ``k_i``'s, from the cotangent ``g`` [B, S, bq] of :func:`indexer_scores_t`'s
    output (zero past the diagonal)."""
    b, heads, s, di = q_i.shape
    bq, bkc = g.shape[-1], min(block_k, s)
    return pl.pallas_call(
        functools.partial(_scores_bwd_kernel, heads=heads, bq=bq, bkc=bkc),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(b, s // bkc),
            in_specs=[
                *_specs(heads, di, bq, bkc),
                pl.BlockSpec((None, bkc, bq), lambda b, c, i_ref: (b, jnp.minimum(c, (i_ref[0] * bq + bq - 1) // bkc), 0)),
            ],
            out_specs=[
                pl.BlockSpec((None, heads, bq, di), lambda b, c, i_ref: (b, 0, 0, 0)),
                pl.BlockSpec((None, bkc, di), lambda b, c, i_ref: (b, c, 0)),
                pl.BlockSpec((None, heads, bq), lambda b, c, i_ref: (b, 0, 0)),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b, heads, bq, di), jnp.float32),
            jax.ShapeDtypeStruct((b, s, di), jnp.float32),
            jax.ShapeDtypeStruct((b, heads, bq), jnp.float32),
        ],
        compiler_params=_params(bq, bkc),
        interpret=_should_interpret() if interpret is None else interpret,
        name="indexer_scores_bwd",
    )(jnp.asarray(i, jnp.int32).reshape(1), q_i, k_i, w, g)
