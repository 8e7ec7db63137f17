"""Flash attention (causal) as pallas TPU kernels, fwd + bwd.

FlashAttention-2 style: the [Sq, Sk] score matrix never materializes in
HBM; probabilities are recomputed blockwise in the backward from a saved
logsumexp. The K/V (resp. Q/dO) block axis is the innermost *grid*
dimension — pallas double-buffers each block's HBM→VMEM DMA against the
previous block's compute — with the running accumulators (acc/m/l, dq,
dk/dv) living in VMEM scratch that persists across the inner grid
sweep (TPU grids execute sequentially per core).

Causal scheduling masks the diagonal blocks and skips compute above the
diagonal via ``pl.when``.

Matmuls keep their storage dtype (bf16) into the MXU and request
``preferred_element_type=float32`` (f32 accumulate). On CPU the kernels
run under ``interpret=True`` so unit tests check numerics against
``ops.attention``.

Role: this kernel is the MEMORY-CEILING path — it makes sequences whose
[S,S] scores can't fit HBM trainable at all (32k tokens on one v5e chip).
It is not the speed path: at d=64 each 128×128 block is ~2 microscopic
matmuls, so the grid is DMA/sequencing-latency-bound and XLA's fused
attention was an order of magnitude faster wherever it fits (19x fwd at
s=8192 on a v5e, measured before this round). The (1,128,128) blocks are
a choice of an earlier toolchain; larger blocks and several heads per
grid step have not been tried on the directly attached chip (ROADMAP
D3). The crossover is handled in policy: models/transformer.py
``_use_flash`` engages this kernel only above the scores-memory
threshold.

On the chip the kernels compile as written (libtpu 0.0.34, jax 0.9.0):
``chip_smoke.py`` phase 3 checks for the Mosaic ``tpu_custom_call`` in
the lowered text and for agreement of forward and backward with
``ops.attention`` at head_dim 64 and 128, which is what guards
:func:`_should_interpret`'s choice from the backend.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention"]

_NEG_INF = -1e30
_LANES = 128  # m/l scratch padded to a full lane tile


def _should_interpret() -> bool:
    return jax.default_backend() == "cpu"


def _iota(n: int) -> jnp.ndarray:
    # 1D iota is unsupported on TPU; build 2D and squeeze
    return jax.lax.broadcasted_iota(jnp.int32, (n, 1), 0)[:, 0]


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, (dims, ((), ())),
                               preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# forward: grid (bh, nq, nk) — nk innermost, acc/m/l in scratch
# ---------------------------------------------------------------------------


def _fwd_kernel(
    q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref,
    *, bq, bk, scale, causal,
):
    i, j = pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # blocks strictly above the diagonal contribute nothing
    run = (j * bk <= i * bq + bq - 1) if causal else True

    @pl.when(run)
    def _compute():
        # inputs keep their storage dtype (bf16): the MXU takes bf16
        # operands at full rate and accumulates f32 via
        # preferred_element_type — upcasting first costs an extra VPU pass
        s = _dot(q_ref[0], k_ref[0], ((1,), (1,))) * scale
        if causal:
            q_pos = i * bq + _iota(bq)
            k_pos = j * bk + _iota(bk)
            s = jnp.where(k_pos[None, :] <= q_pos[:, None], s, _NEG_INF)
        m_prev = m_ref[:, 0]
        blk_max = jnp.max(s, axis=1)
        m_new = jnp.maximum(m_prev, blk_max)
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        acc_ref[...] = acc_ref[...] * corr[:, None] + _dot(
            p.astype(v_ref.dtype), v_ref[0], ((1,), (0,))
        )
        l_ref[:, 0] = l_ref[:, 0] * corr + jnp.sum(p, axis=1)
        m_ref[:, 0] = m_new

    @pl.when(j == nk - 1)
    def _finish():
        l = jnp.maximum(l_ref[:, 0], 1e-30)
        o_ref[0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)
        lse = m_ref[:, 0] + jnp.log(l)
        lse_ref[0] = jnp.broadcast_to(lse[None, :], (8, bq))


def _fwd(q, k, v, bq, bk, scale, causal, interpret):
    bh, s, d = q.shape
    grid = (bh, s // bq, s // bk)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, bq=bq, bk=bk, scale=scale, causal=causal),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 8, bq), lambda b, i, j: (b, 0, i), memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, d), q.dtype),
            jax.ShapeDtypeStruct((bh, 8, s), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq, _LANES), jnp.float32),
            pltpu.VMEM((bq, _LANES), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------


def _dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, acc_ref,
    *, bq, bk, scale, causal,
):
    i, j = pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    run = (j * bk <= i * bq + bq - 1) if causal else True

    @pl.when(run)
    def _compute():
        lse = lse_ref[0, 0, :]
        delta = delta_ref[0, 0, :]
        s = _dot(q_ref[0], k_ref[0], ((1,), (1,))) * scale
        if causal:
            q_pos = i * bq + _iota(bq)
            k_pos = j * bk + _iota(bk)
            s = jnp.where(k_pos[None, :] <= q_pos[:, None], s, _NEG_INF)
        p = jnp.exp(s - lse[:, None])
        dp = _dot(do_ref[0], v_ref[0], ((1,), (1,)))
        ds = p * (dp - delta[:, None]) * scale
        acc_ref[...] += _dot(ds.astype(k_ref.dtype), k_ref[0], ((1,), (0,)))

    @pl.when(j == nk - 1)
    def _finish():
        dq_ref[0] = acc_ref[...].astype(dq_ref.dtype)


def _dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
    dk_acc, dv_acc,
    *, bq, bk, scale, causal,
):
    j, i = pl.program_id(1), pl.program_id(2)
    nq = pl.num_programs(2)

    @pl.when(i == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    run = (i * bq + bq - 1 >= j * bk) if causal else True

    @pl.when(run)
    def _compute():
        lse = lse_ref[0, 0, :]
        delta = delta_ref[0, 0, :]
        s = _dot(q_ref[0], k_ref[0], ((1,), (1,))) * scale
        if causal:
            q_pos = i * bq + _iota(bq)
            k_pos = j * bk + _iota(bk)
            s = jnp.where(k_pos[None, :] <= q_pos[:, None], s, _NEG_INF)
        p = jnp.exp(s - lse[:, None])
        dv_acc[...] += _dot(p.astype(do_ref.dtype), do_ref[0], ((0,), (0,)))
        dp = _dot(do_ref[0], v_ref[0], ((1,), (1,)))
        ds = p * (dp - delta[:, None]) * scale
        dk_acc[...] += _dot(ds.astype(q_ref.dtype), q_ref[0], ((0,), (0,)))

    @pl.when(i == nq - 1)
    def _finish():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _bwd(bq, bk, scale, causal, interpret, res, do):
    q, k, v, o, lse = res
    bh, s, d = q.shape
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    delta = jnp.broadcast_to(delta[:, None, :], (bh, 8, s))

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, bq=bq, bk=bk, scale=scale, causal=causal),
        grid=(bh, s // bq, s // bk),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 8, bq), lambda b, i, j: (b, 0, i), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 8, bq), lambda b, i, j: (b, 0, i), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec(
            (1, bq, d), lambda b, i, j: (b, i, 0), memory_space=pltpu.VMEM
        ),
        out_shape=jax.ShapeDtypeStruct((bh, s, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        interpret=interpret,
    )(q, k, v, do, lse, delta)

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, bq=bq, bk=bk, scale=scale, causal=causal),
        grid=(bh, s // bk, s // bq),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, j, i: (b, i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bk, d), lambda b, j, i: (b, j, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bk, d), lambda b, j, i: (b, j, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bq, d), lambda b, j, i: (b, i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 8, bq), lambda b, j, i: (b, 0, i), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 8, bq), lambda b, j, i: (b, 0, i), memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, bk, d), lambda b, j, i: (b, j, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bk, d), lambda b, j, i: (b, j, 0), memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, d), k.dtype),
            jax.ShapeDtypeStruct((bh, s, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, d), jnp.float32),
            pltpu.VMEM((bk, d), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# public op
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q, k, v, bq, bk, causal, interpret):
    scale = q.shape[-1] ** -0.5
    o, _ = _fwd(q, k, v, bq, bk, scale, causal, interpret)
    return o


def _flash_fwd(q, k, v, bq, bk, causal, interpret):
    scale = q.shape[-1] ** -0.5
    o, lse = _fwd(q, k, v, bq, bk, scale, causal, interpret)
    return o, (q, k, v, o, lse)


def _flash_bwd(bq, bk, causal, interpret, res, do):
    scale = res[0].shape[-1] ** -0.5
    return _bwd(bq, bk, scale, causal, interpret, res, do)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    causal: bool = True,
    block_q: int = 128,
    block_k: int = 128,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Causal flash attention. q/k/v: [B, S, H, Dh] -> [B, S, H, Dh].

    Requires S % block == 0 (pick smaller blocks for short sequences).
    Differentiable (custom FlashAttention-2 backward)."""
    b, s, h, d = q.shape
    bq = min(block_q, s)
    bk = min(block_k, s)
    if s % bq or s % bk:
        raise ValueError(f"seq len {s} must be a multiple of block sizes ({bq},{bk})")
    if interpret is None:
        interpret = _should_interpret()

    def pack(x):
        return x.transpose(0, 2, 1, 3).reshape(b * h, s, d)

    o = _flash(pack(q), pack(k), pack(v), bq, bk, causal, interpret)
    return o.reshape(b, h, s, d).transpose(0, 2, 1, 3)
