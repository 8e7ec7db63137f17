"""Flash attention (causal) as pallas TPU kernels, fwd + bwd.

FlashAttention-2 style: the [Sq, Sk] score matrix never materializes in
HBM; probabilities are recomputed tile by tile in the backward from a
saved logsumexp. Two kernels: the forward, and ONE backward that computes
dQ, dK and dV from a single pass over the scores (five matmuls and one exp
a tile, where separate dQ and dK/dV kernels pay seven and two).

Tiling: the grid is (batch, head, outer block, inner block). A grid step
holds one block of ``block_q`` query rows and up to ``_RESIDENT_KEYS`` rows
of K and V in VMEM, and loops inside the step over tiles of ``block_k``
keys — pallas double-buffers the next step's blocks against this one's
compute; the running accumulators (acc/m/l; dk/dv) live in VMEM scratch
that persists across the inner grid sweep (TPU grids execute sequentially
per core). With the whole sequence resident (S <= ``_RESIDENT_KEYS`` at 256-lane heads,
twice that at 128 lanes and under: ``_resident_tiles``) the forward is one
step a q block and the backward's dQ is complete within its step; longer
sequences write dQ in one part per K block, summed outside.

Causal scheduling: the loop over key tiles stops at the diagonal — a tile
above it is never computed, a K/V block above it never fetched (the index
maps clamp to the last block the diagonal needs, and a step whose block
index did not change re-uses the resident block) — and only the tiles the
diagonal crosses build the mask. Under a band (``window``: position i sees
the keys i - window < j <= i) the loop starts at the tile that holds the
first row's first key, masks the tiles the band's lower edge crosses as well,
and a K/V block wholly below the band is not fetched either: at 512 x 512
tiles and a window of 512 a query tile visits two key tiles, whatever S.

Block diffusion (``block_diffusion`` = D): the rows are a sequence twice,
``[noised ; clean]``, and the mask is a staircase over the two halves
(:func:`_over_staircase_tiles`): a q tile of the noised half visits the
noised tile that holds its own positions and the clean tiles up to its own,
one of the clean half the clean tiles up to its own — n² + 2n of the 4n² tiles
at n tiles a half, 288 of 1 024 at 512 x 512 and S 8192 for S² + 4S pairs =
256.1 tiles' worth at D 4 — and builds the mask only in the tiles the
staircase or a block's edge crosses. The whole sequence is one resident K
block there (a longer one is refused by name).

A selection (``selected``): the pairs a query sees arrive as a table of int8
tiles, transposed as the scores are — ``selected[i, b, s, t]`` says whether
query ``i·block_q + t`` of sequence b sees key s; the table holds causality,
so no tile builds a mask of its own — with, a tile, whether any of its pairs is
seen (``live``, in SMEM): the walk stops at the diagonal and steps over a tile
in which none is (:func:`_over_selected_tiles`). The whole sequence is one
resident K block there, and the grids keep the query heads of a key/value
group on consecutive steps, so the group reads a q block's tiles of the table
once. :func:`head_probs` walks the same tiles for the probabilities of the
selected pairs summed over the query heads, from the forward's row statistics.

Grouped queries: k and v may have fewer heads than q. In the forward the
grid runs over query heads and a group's heads read the one K/V block of
head ``h // group`` in place (consecutive steps, so it stays resident); the
backward's grid runs over KEY/VALUE heads and its innermost axis sweeps the
q blocks of every query head of the group, so dK and dV are summed over the
group in the scratch accumulators and written once.

Layout: with ``head_dim`` a multiple of 128 (one lane tile) a head's
columns are read in place from the ``[B, S, H·Dh]`` view, no transpose
around the call; other head sizes go through ``[B, H, S, Dh]`` — at 64
lanes (``lfm2-8b-a1b-1g``: b2 x s8192 x 32 heads over 8) the transposes
are 0.6 ms of the call's 28.5 where padding the lanes to 128 to read heads
in place cost 2.7 more (my chip run, PR 52). Both
kernels work on TRANSPOSED scores ``K·Qᵀ`` ``[block_k, block_q]``: the
per-query statistics (running max and sum, logsumexp, delta) are then
lane rows that broadcast along sublanes, where as ``[block_q, 1]`` columns
each of their updates costs a pass over a ``[block_q, 128]`` array.

Precision: matmul operands keep their storage dtype (bf16) into the MXU
with f32 accumulation (``preferred_element_type``); scores, running max /
sum and exp are f32 (the v5e has no bf16 VPU); probabilities and dS are
cast to the storage dtype before their second matmul; 1/sqrt(d) goes onto
the q tile once (in f32, rounded to the storage dtype). On CPU the kernels
run under ``interpret=True`` so unit tests check numerics against
``ops.attention``.

Remat: the backward kernel's residuals are (q, k, v, o, lse). q, k and v
are projections a ``jax.checkpoint`` around the layer computes again; o and
lse only the forward kernel can give, and both lie in HBM once it has run.
The forward rule names them (:data:`CORE_OUT`, :data:`CORE_LSE`,
``jax.ad_checkpoint.checkpoint_name``) and hands the NAMED arrays on as the
output and as the residuals, so a checkpoint whose policy saves the two names
(``models/transformer._remat``, policies "all" and "dots" alike) keeps
[B, S, H·Dv] in the storage dtype and [B, H, 8, S] float32 a call, and its
recomputation holds no forward call: the kernel's forward runs once a layer
a step, not twice (PERF.md §6, PR 47). Without such a checkpoint a name is
the identity.

Role: at the tiles ``models/transformer._flash_blocks`` picks it is the
speed path of ``attention_impl`` "auto" on a TPU, at whole lane tiles and
at 64 lanes (the policy and its measured tables: ``_attention_path`` there
and PERF.md §6, PRs 31 and 52), and at
any tile the memory-ceiling path for sequences whose [S, S] scores cannot
fit HBM.

On the chip the kernels compile as written (libtpu 0.0.34, jax 0.9.0):
``chip_smoke.py`` phase 3 checks for the Mosaic ``tpu_custom_call`` in
the lowered text and for agreement of forward and backward with
``ops.attention`` at head_dim 64 and 128, at the benchmark cells'
sequence and heads and at s8192 with four 64-wide query heads a key/value
head, which is what guards :func:`_should_interpret`'s
choice from the backend.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention", "head_probs", "selected_tiles", "tiles_live", "CORE_OUT", "CORE_LSE"]

# ``checkpoint_name``s of what only the forward kernel can give the backward: its output and its rows' logsumexp
CORE_OUT = "attn_core_out"
CORE_LSE = "attn_core_lse"

_NEG_INF = -1e30
_LANES = 128  # one lane tile: a head of that many columns is read in place
_ROWS = 8  # sublanes a row of per-position statistics is stored over


def _should_interpret() -> bool:
    return jax.default_backend() == "cpu"


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, (dims, ((), ())),
                               preferred_element_type=jnp.float32)


_NT = ((1,), (1,))  # a · bᵀ
_NN = ((1,), (0,))  # a · b
_TN = ((0,), (0,))  # aᵀ · b


def _causal(key0, n_keys, q0, n_q, window=None):
    """[n_keys, n_q] True where the key may be attended (k_pos <= q_pos, and
    under a band q_pos - window < k_pos)."""
    k = key0 + jax.lax.broadcasted_iota(jnp.int32, (n_keys, n_q), 0)
    q = q0 + jax.lax.broadcasted_iota(jnp.int32, (n_keys, n_q), 1)
    if window is None:
        return k <= q
    return (k <= q) & (k > q - window)


def _over_key_tiles(row0, bq, col0, bk, bkc, causal, step, window=None) -> None:
    """Run ``step(c, masked)`` for the tiles c of ``bkc`` keys, of the
    ``bk`` resident from ``col0``, that the q rows ``[row0, row0 + bq)``
    may attend: first those wholly below the diagonal, without a mask,
    then those it crosses; a tile above it is not computed. Under a band the
    loop starts at the tile that holds the first row's first key
    (``row0 - window + 1``) and masks the tiles the band's lower edge crosses
    too: those before the last row's first key."""
    nc = bk // bkc
    if not causal:
        jax.lax.fori_loop(0, nc, lambda c, _: step(c, False), None)
        return
    below = jnp.clip(jnp.maximum(row0 - col0 + 1, 0) // bkc, 0, nc)
    reached = jnp.clip(jnp.maximum(row0 + bq - 1 - col0 + bkc, 0) // bkc, 0, nc)
    if window is None:
        jax.lax.fori_loop(0, below, lambda c, _: step(c, False), None)
        jax.lax.fori_loop(below, reached, lambda c, _: step(c, True), None)
        return
    first = jnp.clip(jnp.maximum(row0 - window + 1 - col0, 0) // bkc, 0, nc)
    whole = jnp.clip((jnp.maximum(row0 + bq - window - col0, 0) + bkc - 1) // bkc, first, nc)
    below = jnp.clip(below, first, nc)
    edge = jnp.minimum(whole, below)
    jax.lax.fori_loop(first, edge, lambda c, _: step(c, True), None)
    jax.lax.fori_loop(edge, below, lambda c, _: step(c, False), None)
    jax.lax.fori_loop(below, reached, lambda c, _: step(c, True), None)


def _staircase(key0, n_keys, q0, n_q, block, lo, hi):
    """[n_keys, n_q] True where the key at position ``key0 + r`` lies in
    ``[f + lo, f + hi)``, f the first position of the block (of ``block``
    positions) that holds the query's ``q0 + c``: ``lo`` None — every key
    before ``f + hi``. The block's start is worked out on the queries' lane
    row alone; the [n_keys, n_q] work is an iota and the comparisons."""
    k = key0 + jax.lax.broadcasted_iota(jnp.int32, (n_keys, n_q), 0)
    q = q0 + jax.lax.broadcasted_iota(jnp.int32, (1, n_q), 1)
    first = q - (q & (block - 1))  # ``block`` is a power of two
    seen = k < first + hi
    return seen if lo is None else seen & (k >= first + lo)


def _over_staircase_tiles(row0, bq, nc, bkc, half, block, step) -> None:
    """Run ``step(c, seen)`` for the tiles c of ``bkc`` keys, of the ``nc``
    resident (the whole ``[noised ; clean]`` sequence, ``half`` rows each),
    that the q rows ``[row0, row0 + bq)`` may attend under block diffusion;
    ``seen``: None where every pair of the tile is, else what builds its mask.
    Row r has position ``r mod half`` and block ``position // block``. A
    NOISED row sees the noised keys of its own block — the tiles that hold its
    own positions, masked, and visited FIRST, so that every row has a key
    before any tile in which it has none — and the clean keys of the blocks
    before it; a CLEAN row the clean keys of its block and those before; no
    row sees a noised key of another block. Of the clean keys the tiles that
    end at or before the rows' first position run without a mask, those up to
    the rows' last position build it, and the others are not computed:
    ``block`` divides the tiles, so a tile's rows lie in one half."""
    clean = row0 >= half
    q0 = row0 - jnp.where(clean, half, 0)  # the rows' first position
    own = lambda c: functools.partial(_staircase, c * bkc, bkc, q0, bq, block, 0, block)
    # clean keys: tile c holds the positions from c * bkc - half; a noised row stops before its block, a clean one after
    upto = jnp.where(clean, block, 0)
    before = lambda c: functools.partial(_staircase, c * bkc - half, bkc, q0, bq, block, None, upto)
    diag = q0 // bkc
    jax.lax.fori_loop(diag, jnp.where(clean, diag, (q0 + bq + bkc - 1) // bkc), lambda c, _: step(c, own(c)), None)
    whole = (half + q0) // bkc
    jax.lax.fori_loop(half // bkc, whole, lambda c, _: step(c, None), None)
    jax.lax.fori_loop(whole, jnp.minimum((half + q0 + bq + bkc - 1) // bkc, nc), lambda c, _: step(c, before(c)), None)


def _over_selected_tiles(row0, bq, nc, bkc, sel_ref, live, step) -> None:
    """Run ``step(c, seen)`` for the tiles c of ``bkc`` keys, of the ``nc``
    resident, that hold a pair the q rows ``[row0, row0 + bq)`` see under a
    selection: those up to the diagonal (the table holds causality: none is
    seen past it) for which ``live(c)`` is not zero; ``seen`` reads the tile of
    the table, which is every tile's mask. A row whose keys all lie in later
    tiles meets a tile of its own with nothing seen: its running maximum stays
    at the floor, and the first key it does see wipes what it summed there."""
    reached = jnp.clip((row0 + bq - 1 + bkc) // bkc, 0, nc)

    def visit(c, _):
        @pl.when(live(c) != 0)
        def _seen():
            step(c, lambda: sel_ref[pl.ds(pl.multiple_of(c * bkc, bkc), bkc), :].astype(jnp.int32) != 0)

    jax.lax.fori_loop(0, reached, visit, None)


def _walk(i, j, bq, bk, bkc, causal, window, diffusion, step, sel=None) -> None:
    """``step(c, seen)`` over the key tiles of grid step (q block i, k block j)
    under the call's rule: the staircase of block diffusion, the tiles a
    selection (``sel``: the table's block and ``live``) leaves live, or the
    causal / banded / full walk of :func:`_over_key_tiles`."""
    if sel is not None:
        _over_selected_tiles(i * bq, bq, bk // bkc, bkc, *sel, step)
        return
    if diffusion:
        _over_staircase_tiles(i * bq, bq, bk // bkc, bkc, *diffusion, step)
        return
    # the mask and the scalars it starts from are traced where ``step`` asks for them, after its first product
    seen = lambda c: lambda: _causal(j * bk + c * bkc, bkc, i * bq, bq, window)
    _over_key_tiles(i * bq, bq, j * bk, bk, bkc, causal, lambda c, masked: step(c, seen(c) if masked else None), window)


def _scaled(q_ref, scale):
    # 1/sqrt(d) once on the [bq, d] tile, not on every [bq, bkc] of scores
    return (q_ref[...].astype(jnp.float32) * scale).astype(q_ref.dtype)


# ---------------------------------------------------------------------------
# forward: grid (b, h, nq, nk) — nk innermost, acc/m/l in scratch
# ---------------------------------------------------------------------------


def _sel_of(sel_ref, live_ref, i):
    # the walk's view of a selection: the q block's tiles of the table and, a key tile, whether it holds a seen pair
    b = pl.program_id(0)  # read here: the interpreter knows a grid index at the kernel's top level alone
    return sel_ref, lambda c: live_ref[i, b, c]


def _at(selected: bool, cond):
    """``pl.when(cond())``, or under a selection — one K block, so every grid
    step starts and ends its rows — the call itself."""
    return (lambda fn: fn()) if selected else pl.when(cond())


def _fwd_kernel(
    q_ref, k_ref, v_ref, *rest,
    bq, bk, bkc, scale, causal, window=None, diffusion=None, selected=False,
):
    # transposed scores [bkc, bq]: the running max and sum are lane rows
    # [1, bq] — as a [bq, 1] column each of their updates costs a pass over a
    # [bq, 128] array, more than the scores' own at 512 keys — and the output
    # accumulates transposed, [d, bq], turned once per q block
    sel = None
    if selected:  # grid (b, key/value head, nq, the group's heads)
        sel_ref, live_ref, *rest = rest
    o_ref, lse_ref, acc_ref, m_ref, l_ref = rest
    i, j = pl.program_id(2), pl.program_id(3)
    if selected:
        j, sel = 0, _sel_of(sel_ref, live_ref, i)

    @_at(selected, lambda: j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = _scaled(q_ref, scale)

    def step(c, seen):
        keys = pl.ds(pl.multiple_of(c * bkc, bkc), bkc)
        st = _dot(k_ref[keys, :], q, _NT)
        if seen is not None:
            st = jnp.where(seen(), st, _NEG_INF)
        m_prev = m_ref[:1, :]
        m_new = jnp.maximum(m_prev, jnp.max(st, axis=0, keepdims=True))
        pt = jnp.exp(st - m_new)
        corr = jnp.exp(m_prev - m_new)
        acc_ref[...] = acc_ref[...] * corr + _dot(
            v_ref[keys, :], pt.astype(v_ref.dtype), _TN
        )
        l_new = l_ref[:1, :] * corr + jnp.sum(pt, axis=0, keepdims=True)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)

    _walk(i, j, bq, bk, bkc, causal, window, diffusion, step, sel)

    @_at(selected, lambda: j == pl.num_programs(3) - 1)
    def _finish():
        l = jnp.maximum(l_ref[:1, :], 1e-30)
        o_ref[...] = (acc_ref[...] / l).T.astype(o_ref.dtype)
        lse_ref[...] = jnp.broadcast_to(m_ref[:1, :] + jnp.log(l), lse_ref.shape)


# ---------------------------------------------------------------------------
# backward: grid (b, key/value head, nk, group x nq) — the q blocks of every
# query head of the group innermost, dk/dv in scratch over all of them (a
# group of one: (b, h, nk, nq)); dq of this (k block, q block) is complete
# within the step
# ---------------------------------------------------------------------------


def _bwd_kernel(
    q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref, *rest,
    bq, bk, bkc, scale, causal, window=None, nq=None, diffusion=None, selected=0,
):
    sel = None
    if selected:
        sel_ref, live_ref, *rest = rest
    dq_ref, dk_ref, dv_ref, dk_acc, dv_acc, dq_acc = rest
    j, y = pl.program_id(2), pl.program_id(3)
    if selected:  # ``selected`` heads a group: a q block's heads follow one another, so its tiles of the table are read once
        i = y // selected
        sel = _sel_of(sel_ref, live_ref, i)
    else:
        i = y if nq is None else y % nq  # nq: q blocks a head, where a group's heads share the sweep

    @pl.when(y == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    dq_acc[...] = jnp.zeros_like(dq_acc)
    q = _scaled(q_ref, scale)
    do = do_ref[...]
    lse = lse_ref[:1, :]
    # delta = rowsum(dO * O), wanted as a lane row like lse: a matmul with
    # ones puts it there, where a reduction would leave a column
    delta = jax.lax.dot_general(
        jnp.ones((_ROWS, do.shape[1]), jnp.float32),
        do.astype(jnp.float32) * o_ref[...].astype(jnp.float32),
        (_NT, ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )[:1, :]

    def step(c, seen):
        keys = pl.ds(pl.multiple_of(c * bkc, bkc), bkc)
        k, v = k_ref[keys, :], v_ref[keys, :]
        # transposed scores [bkc, bq]: the statistics' lane rows broadcast
        # along sublanes as they are
        st = _dot(k, q, _NT)
        if seen is not None:
            st = jnp.where(seen(), st, _NEG_INF)
        pt = jnp.exp(st - lse)
        dv_acc[keys, :] += _dot(pt.astype(do.dtype), do, _NN)
        dst = (pt * (_dot(v, do, _NT) - delta)).astype(q.dtype)
        dk_acc[keys, :] += _dot(dst, q, _NN)  # q carries the scale
        dq_acc[...] += _dot(dst, k, _TN)

    _walk(i, j, bq, bk, bkc, causal, window, diffusion, step, sel)

    dq_ref[...] = (dq_acc[...] * scale).astype(dq_ref.dtype)

    @pl.when(y == pl.num_programs(3) - 1)
    def _finish():
        dk_ref[...] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)


# ---------------------------------------------------------------------------
# block specs and the two calls
# ---------------------------------------------------------------------------


def _same_head(h, x, y):
    return h


def _tile(lanes: bool, blk: int, d: int, idx, lead: bool = False, head=_same_head) -> pl.BlockSpec:
    """One [blk, d] tile of a q/k/v-shaped array; ``idx(x, y)`` gives its
    block along S from the grid's last two indices. ``lead``: the array has
    one more axis in front, indexed by x (dq's part of each k block).
    ``head(h, x, y)``: the array's head where it is not the grid's (grouped
    queries: a key/value head in the forward's grid over query heads, a
    query head in the backward's over key/value heads)."""
    if lanes:  # [B, S, H·Dh]: head h is lane tile h
        at = lambda b, h, x, y: (b, idx(x, y), head(h, x, y))
        shape = (None, blk, d)
    else:  # [B, H, S, Dh]
        at = lambda b, h, x, y: (b, head(h, x, y), idx(x, y), 0)
        shape = (None, None, blk, d)
    if lead:
        return pl.BlockSpec(
            (None,) + shape, lambda b, h, x, y: (x,) + at(b, h, x, y),
            memory_space=pltpu.VMEM,
        )
    return pl.BlockSpec(shape, at, memory_space=pltpu.VMEM)


def _row(blk: int, idx, head=_same_head) -> pl.BlockSpec:
    """One [8, blk] tile of per-position statistics, [B, H, 8, S]."""
    return pl.BlockSpec(
        (None, None, _ROWS, blk), lambda b, h, x, y: (b, head(h, x, y), 0, idx(x, y)),
        memory_space=pltpu.VMEM,
    )


def _params(bq: int, bkc: int, resident: int = 0) -> pltpu.CompilerParams:
    # the f32 [bq, bkc] temporaries (scores, p, dp, ds, the mask) outgrow
    # the default scoped limit from 512 x 512 on: raise the limit rather
    # than shrink the tiles (a v5e core has 128 MiB). ``resident``: bytes of
    # the K and V blocks a step keeps (double-buffered in and, as dK and dV,
    # out, with their f32 accumulators); the 8 MiB of 2048 keys of two lane
    # tiles are inside the 24
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=min(112 << 20, (24 << 20) + 40 * bq * bkc + max(0, resident - (8 << 20))),
    )


def _resident_bytes(bk: int, d: int, dv: int, itemsize: int = 2) -> int:
    # K and V in and dK and dV out, double-buffered in the storage dtype, and the two f32 accumulators: 12 bytes an
    # element in bf16, 20 in float32 (a reference check's float32 program: at 64 lanes and 8192 resident keys its
    # backward needs 42.5 MiB where bf16's count allowed 38.25: the compiler refused the whole program, PR 52)
    return (4 * itemsize + 4) * bk * (d + dv)


def _sel_specs(bq: int, bk: int, q_blk):
    """The blocks of a selection's two arrays: the q block's tiles of the table
    ``[nq, B, S, bq]`` int8 — ``q_blk(x, y)`` the q block of a grid step — in
    VMEM, and ``live`` ``[nq, B, S // bkc]`` int32 whole in SMEM."""
    return [
        pl.BlockSpec((None, None, bk, bq), lambda b, h, x, y: (q_blk(x, y), b, 0, 0), memory_space=pltpu.VMEM),
        pl.BlockSpec(memory_space=pltpu.SMEM),
    ]


def _fwd_selected(q, k, v, sel, shape, blocks, interpret):
    """:func:`_fwd` under a selection: the grid is (b, key/value head, q block,
    the group's heads), so that the group's heads of one q block follow one
    another and its tiles of the table stay resident."""
    b, s, h, d, dv, scale, group, _, _ = shape
    bq, bk, bkc = blocks
    lanes = q.ndim == 3
    q_at, k_at = (lambda i, g: i), (lambda i, g: 0)
    q_head = lambda hk, i, g: hk * group + g
    return pl.pallas_call(
        functools.partial(_fwd_kernel, bq=bq, bk=bk, bkc=bkc, scale=scale, causal=True, selected=True),
        grid=(b, h // group, s // bq, group),
        in_specs=[
            _tile(lanes, bq, d, q_at, head=q_head),
            _tile(lanes, bk, d, k_at),
            _tile(lanes, bk, dv, k_at),
            *_sel_specs(bq, bk, q_at),
        ],
        out_specs=[_tile(lanes, bq, dv, q_at, head=q_head), _row(bq, q_at, head=q_head)],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape[:-1] + (h * dv if lanes else dv,), q.dtype),
            jax.ShapeDtypeStruct((b, h, _ROWS, s), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((dv, bq), jnp.float32),
            pltpu.VMEM((_ROWS, bq), jnp.float32),
            pltpu.VMEM((_ROWS, bq), jnp.float32),
        ],
        compiler_params=_params(bq, bkc, _resident_bytes(bk, d, dv, q.dtype.itemsize) + 2 * bk * bq),
        interpret=interpret,
        name="flash_fwd",
    )(q, k, v, *sel)


def _fwd(q, k, v, shape, blocks, causal, interpret):
    b, s, h, d, dv, scale, group, window, diffusion = shape
    bq, bk, bkc = blocks
    lanes = q.ndim == 3
    q_at = lambda i, j: i
    if causal and window is not None:  # and a block below the band: the first one needed
        k_at = lambda i, j: jnp.clip(j, jnp.maximum(i * bq - window + 1, 0) // bk, (i * bq + bq - 1) // bk)
    elif causal:  # a block above the diagonal: keep the last one needed resident
        k_at = lambda i, j: jnp.minimum(j, (i * bq + bq - 1) // bk)
    else:
        k_at = lambda i, j: j
    # a group's query heads follow one another in the grid, so their one K/V block stays resident
    kv_head = _same_head if group == 1 else (lambda h, x, y: h // group)
    return pl.pallas_call(
        functools.partial(
            _fwd_kernel, bq=bq, bk=bk, bkc=bkc, scale=scale, causal=causal, window=window, diffusion=diffusion
        ),
        grid=(b, h, s // bq, s // bk),
        in_specs=[
            _tile(lanes, bq, d, q_at),
            _tile(lanes, bk, d, k_at, head=kv_head),
            _tile(lanes, bk, dv, k_at, head=kv_head),
        ],
        out_specs=[_tile(lanes, bq, dv, q_at), _row(bq, q_at)],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape[:-1] + (h * dv if lanes else dv,), q.dtype),
            jax.ShapeDtypeStruct((b, h, _ROWS, s), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((dv, bq), jnp.float32),
            pltpu.VMEM((_ROWS, bq), jnp.float32),
            pltpu.VMEM((_ROWS, bq), jnp.float32),
        ],
        compiler_params=_params(bq, bkc, _resident_bytes(bk, d, dv, q.dtype.itemsize)),
        interpret=interpret,
        name="flash_fwd",
    )(q, k, v)


def _bwd(shape, blocks, causal, interpret, res, do, sel=None):
    q, k, v, o, lse = res
    b, s, h, d, dv, scale, group, window, diffusion = shape
    bq, bk, bkc = blocks
    lanes = q.ndim == 3
    nk, nq = s // bk, s // bq
    k_at = lambda j, i: j
    more, more_specs, more_bytes = {}, [], 0
    if sel is not None:  # a q block's heads follow one another: its tiles of the table are read once a key/value head
        q_head = lambda h, j, y: h * group + y % group
        q_blk = lambda j, y: y // group
        more, more_specs, more_bytes = dict(selected=group), _sel_specs(bq, bk, q_blk), 2 * bk * bq
    elif group == 1:
        q_head, q_blk = _same_head, (lambda j, i: i)
    else:  # the grid's head is a key/value head; its last axis sweeps the group's heads, nq blocks each
        q_head = lambda h, j, y: h * group + y // nq
        q_blk = lambda j, y: y % nq
    if causal:  # a q block above the diagonal: fetch the first one below
        q_at = lambda j, y: jnp.maximum(q_blk(j, y), (j * bk) // bq)
    else:
        q_at = q_blk
    dq, dk, dv = pl.pallas_call(
        functools.partial(
            _bwd_kernel, bq=bq, bk=bk, bkc=bkc, scale=scale, causal=causal, window=window,
            nq=None if group == 1 else nq, diffusion=diffusion, **more,
        ),
        grid=(b, h // group, nk, group * nq),
        in_specs=[
            _tile(lanes, bq, d, q_at, head=q_head),
            _tile(lanes, bk, d, k_at),
            _tile(lanes, bk, dv, k_at),
            _tile(lanes, bq, dv, q_at, head=q_head),
            _tile(lanes, bq, dv, q_at, head=q_head),
            _row(bq, q_at, head=q_head),
            *more_specs,
        ],
        out_specs=[
            # dq's part of EVERY (k block, q block), zeros above the diagonal
            _tile(lanes, bq, d, q_blk, lead=True, head=q_head),
            _tile(lanes, bk, d, k_at),
            _tile(lanes, bk, dv, k_at),
        ],
        out_shape=[
            # one k block (the whole sequence resident): the part is dq
            jax.ShapeDtypeStruct((nk,) + q.shape, q.dtype if nk == 1 else jnp.float32),
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, d), jnp.float32),
            pltpu.VMEM((bk, dv), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        compiler_params=_params(bq, bkc, _resident_bytes(bk, d, dv, q.dtype.itemsize) + more_bytes),
        interpret=interpret,
        name="flash_bwd",
    )(q, k, v, o, do, lse, *(sel or ()))
    dq = dq[0] if nk == 1 else jnp.sum(dq, axis=0).astype(q.dtype)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# public op
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q, k, v, shape, blocks, causal, interpret):
    return _fwd(q, k, v, shape, blocks, causal, interpret)[0]


def _flash_fwd(q, k, v, shape, blocks, causal, interpret):
    o, lse = _fwd(q, k, v, shape, blocks, causal, interpret)
    # the named arrays are the output AND the residuals: under a ``jax.checkpoint`` whose policy saves the two names
    # nothing reads the un-named ones, so the recomputation holds no forward call (the module's docstring, "Remat")
    o, lse = checkpoint_name(o, CORE_OUT), checkpoint_name(lse, CORE_LSE)
    return o, (q, k, v, o, lse)


_flash.defvjp(_flash_fwd, _bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _flash_selected(q, k, v, selected, live, shape, blocks, interpret):
    """(output, row statistics) under a selection; the statistics carry no
    gradient (what reads them — the selector's target — is detached)."""
    return _fwd_selected(q, k, v, (selected, live), shape, blocks, interpret)


def _flash_selected_fwd(q, k, v, selected, live, shape, blocks, interpret):
    o, lse = _fwd_selected(q, k, v, (selected, live), shape, blocks, interpret)
    o, lse = checkpoint_name(o, CORE_OUT), checkpoint_name(lse, CORE_LSE)
    return (o, lse), (q, k, v, o, lse, selected, live)


def _flash_selected_bwd(shape, blocks, interpret, res, cts):
    *res, selected, live = res
    return (*_bwd(shape, blocks, True, interpret, tuple(res), cts[0], (selected, live)), None, None)


_flash_selected.defvjp(_flash_selected_fwd, _flash_selected_bwd)

# ---------------------------------------------------------------------------
# the selected pairs' probabilities, summed over the query heads: grid (b, key tile) for ONE q block
# ---------------------------------------------------------------------------


def _probs_kernel(i_ref, live_ref, q_ref, k_ref, lse_ref, sel_ref, p_ref, acc_ref, *, heads, group, bq, bkc):
    b, c = pl.program_id(0), pl.program_id(1)
    i = i_ref[0]
    seen = (c * bkc < i * bq + bq) & (live_ref[i, b, c] != 0)  # the walk's tiles: up to the diagonal, and live

    @pl.when(seen)
    def _sum():
        acc_ref[...] = jnp.zeros_like(acc_ref)

        def head(a, _):
            st = _dot(k_ref[a // group], q_ref[a], _NT)  # q carries the scale, as the core's does
            acc_ref[...] += jnp.exp(st - lse_ref[a, :1, :])

        jax.lax.fori_loop(0, heads, head, None)
        p_ref[...] = jnp.where(sel_ref[...].astype(jnp.int32) != 0, acc_ref[...] * (1.0 / heads), 0.0)

    @pl.when(jnp.logical_not(seen))
    def _none():
        p_ref[...] = jnp.zeros_like(p_ref)


def scaled_head_major(q: jnp.ndarray) -> jnp.ndarray:
    """[B, S, H, D] -> [B, H, S, D] with 1/sqrt(D) on it, rounded as the core's kernels round it (:func:`_scaled`)."""
    return (q.astype(jnp.float32) * q.shape[-1] ** -0.5).astype(q.dtype).transpose(0, 2, 1, 3)


def head_probs(
    q: jnp.ndarray, k: jnp.ndarray, lse: jnp.ndarray, selected: jnp.ndarray, live: jnp.ndarray, i,
    block_k: int = 128, interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """``p[b, s, t] = (1/H)·Σ_a exp(q_a[i·bq + t]·k_{a // group}[s] - lse_a[i·bq + t])`` on the pairs q block ``i``
    selects and 0 elsewhere, float32 ``[B, S, bq]``: the mean over the query heads of each head's softmax over the
    selected keys, which sums to one over a row's keys — the selector's target. ``q`` [B, H, S, D] is
    :func:`scaled_head_major`'s, ``k`` [B, Hkv, S, D] head-major as well, ``lse`` [B, H, 8, S] the forward kernel's
    row statistics, ``selected`` / ``live`` the core's own (:func:`selected_tiles`, :func:`tiles_live`), ``i`` a
    traced index. The walk is the core's: a tile past the diagonal or without a selected pair is zeros, and its keys
    are not fetched. No gradient: the target is detached."""
    b, h, s, d = q.shape
    nq, _, _, bq = selected.shape
    bkc = min(block_k, s)
    group = h // k.shape[1]
    if interpret is None:
        interpret = _should_interpret()
    last = lambda i_ref: (i_ref[0] * bq + bq - 1) // bkc  # the last key tile the q block reaches
    return pl.pallas_call(
        functools.partial(_probs_kernel, heads=h, group=group, bq=bq, bkc=bkc),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, s // bkc),
            in_specs=[
                pl.BlockSpec((None, h, bq, d), lambda b, c, i_ref, live: (b, 0, i_ref[0], 0)),
                pl.BlockSpec((None, h // group, bkc, d), lambda b, c, i_ref, live: (b, 0, jnp.minimum(c, last(i_ref)), 0)),
                pl.BlockSpec((None, h, _ROWS, bq), lambda b, c, i_ref, live: (b, 0, 0, i_ref[0])),
                pl.BlockSpec((None, None, bkc, bq), lambda b, c, i_ref, live: (i_ref[0], b, jnp.minimum(c, last(i_ref)), 0)),
            ],
            out_specs=pl.BlockSpec((None, bkc, bq), lambda b, c, i_ref, live: (b, c, 0)),
            scratch_shapes=[pltpu.VMEM((bkc, bq), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((b, s, bq), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"), vmem_limit_bytes=min(112 << 20, (32 << 20) + 40 * bq * bkc),
        ),
        interpret=interpret,
        name="head_probs",
    )(jnp.asarray(i, jnp.int32).reshape(1), live, q, k, lse, selected)


def selected_tiles(seen: jnp.ndarray, block_q: int) -> jnp.ndarray:
    """A table of pairs ``seen`` [B, Sq, Sk] (True where the query sees the
    key; causality is the table's to hold) as the kernels read it: int8
    ``[Sq // bq, B, Sk, bq]``, a q block's tiles transposed as the scores are."""
    b, sq, sk = seen.shape
    bq = min(block_q, sq)
    return seen.reshape(b, sq // bq, bq, sk).transpose(1, 0, 3, 2).astype(jnp.int8)


def tiles_live(selected: jnp.ndarray, block_k: int) -> jnp.ndarray:
    """int32 ``[nq, B, S // bkc]``: whether a tile of ``selected`` holds a seen pair."""
    nq, b, s, bq = selected.shape
    bkc = min(block_k, s)
    return jnp.any(selected.reshape(nq, b, s // bkc, bkc * bq) != 0, axis=-1).astype(jnp.int32)


# Most rows of K and V a grid step keeps in VMEM, for heads of TWO lane tiles
# (256 lanes: ``qwen3-next-80b-a3b-1g``'s 8 192 keys, which compile and load, PR
# 54); heads of one lane tile keep twice as many rows in the same bytes (16 384
# keys of 128 lanes: ``smallthinker-21b-a3b-1g`` at its full context). A
# sequence up to that long is one K block: the backward's dQ is then complete
# within its step, where a longer one writes a float32 part per K block
# ([4, B, S, H·D] = 2.1 GB at s8192 under the 2048 this was until PR 35; [2, B,
# S, H·D] = 0.94 GB a layer at b2 x s16384 x 28 heads of 128 under a limit in
# rows alone) and sums them outside.
_RESIDENT_KEYS = 8192


def _resident_tiles(s: int, bkc: int, lanes: int = 2 * _LANES) -> int:
    # K and V arrive in the largest whole number of tiles that divides S and
    # stays under the resident rows of heads ``lanes`` wide (the wider of keys
    # and values, in whole lane tiles): fewer grid steps and DMAs than a tile
    # a step, and up to there the backward's dq needs no second pass
    rows = _RESIDENT_KEYS * max(1, 2 * _LANES // (-(-lanes // _LANES) * _LANES))
    return max(m for m in range(1, max(rows // bkc, 1) + 1) if (s // bkc) % m == 0)


def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    causal: bool = True,
    block_q: int = 128,
    block_k: int = 128,
    interpret: Optional[bool] = None,
    window: Optional[int] = None,
    block_diffusion: int = 0,
    selected: Optional[jnp.ndarray] = None,
    live: Optional[jnp.ndarray] = None,
):
    """Causal flash attention. q/k/v: [B, S, H, Dh] -> [B, S, H, Dh].

    ``selected`` (in place of ``causal``, ``window`` and ``block_diffusion``):
    the pairs seen, as :func:`selected_tiles` lays them out at ``block_q``
    (int8 ``[S // block_q, B, S, block_q]``; the table holds causality), with
    ``live`` (:func:`tiles_live` at ``block_k``; worked out here if absent).
    Returns ``(output, row statistics [B, H, 8, S] float32)`` then: the
    logsumexp of each head's selected scores, which :func:`head_probs` reads.
    The table carries no gradient. The whole sequence is one resident K block.

    ``block_diffusion`` = D > 0 (in place of ``causal`` and ``window``): the
    rows are a sequence twice, ``[noised ; clean]``, S/2 rows each; row r has
    position ``r mod S/2`` and block ``position // D``. A noised row sees the
    noised keys of its own block (in both directions) and the clean keys of
    the blocks before it, a clean row the clean keys of its own block and of
    those before, no row a noised key of another block
    (``ops.attention.block_diffusion_mask`` is the rule as a table). A tile in
    which no pair is seen is not computed (:func:`_over_staircase_tiles`: of
    n tiles a half, n² + 2n of the 4n² are visited); the mask is built in the
    tiles the staircase or a block's edge crosses. D is a power of two that
    divides the tiles, the tiles divide S/2, and all S rows of K and V are
    resident (``_resident_tiles``: 16 384 rows of 128 lanes).

    Grouped queries: k and v may have fewer heads than q, a whole number of
    query heads a key/value head (query head a reads head a // group); the
    group's one K/V block is read in place and dK, dV are summed over the
    group inside the backward kernel. ``window`` (causal only): position i
    attends to the keys j with i - window < j <= i, and a tile wholly outside
    that band is not computed.

    ``block_q`` x ``block_k`` is the tile of scores computed at a time.
    Requires S % block == 0 (pick smaller blocks for short sequences).
    Differentiable (custom FlashAttention-2 backward).

    Values may be narrower or wider than keys (q, k [B, S, H, Dk], v
    [B, S, H, Dv] -> [B, S, H, Dv]; scores scaled by Dk^-1/2). Where the
    values are whole lane tiles and the keys are not (a latent attention's
    128 + 64 against 128), q and k are padded with zero columns to the next
    lane tile, which changes no score, so that heads are still read in place."""
    b, s, h, d = q.shape
    dv = v.shape[-1]
    scale = d**-0.5
    group = h // k.shape[2]
    if h % k.shape[2] or k.shape[2] != v.shape[2]:
        raise ValueError(f"{h} query heads over {k.shape[2]} key and {v.shape[2]} value heads: groups are whole")
    if window is not None and not (causal and window >= 1):
        raise ValueError(f"window={window}: a band is causal and at least one key wide")
    if block_diffusion and window is not None:
        raise ValueError(f"block_diffusion={block_diffusion} with window={window}: the staircase has no band")
    if selected is not None and (window is not None or block_diffusion):
        raise ValueError(f"selected with window={window}, block_diffusion={block_diffusion}: the table is the whole rule")
    if d % _LANES and dv % _LANES == 0:
        widen = ((0, 0), (0, 0), (0, 0), (0, -d % _LANES))
        q, k = jnp.pad(q, widen), jnp.pad(k, widen)
        d += -d % _LANES
    bq = min(block_q, s)
    bkc = min(block_k, s)
    if s % bq or s % bkc:
        raise ValueError(f"seq len {s} must be a multiple of block sizes ({bq},{bkc})")
    if interpret is None:
        interpret = _should_interpret()
    blocks = (bq, bkc * _resident_tiles(s, bkc, max(d, dv)), bkc)
    diffusion = None
    if block_diffusion:
        half = s // 2
        if block_diffusion & (block_diffusion - 1) or s % 2 or half % bq or half % bkc or bq % block_diffusion or bkc % block_diffusion:
            raise ValueError(
                f"block_diffusion={block_diffusion} over {s} rows at tiles {bq} x {bkc}: the block is a power of two "
                "that divides both tiles, and the tiles divide each half of the rows"
            )
        if blocks[1] != s:
            raise ValueError(
                f"block_diffusion over {s} rows of {max(d, dv)} lanes: K and V arrive in blocks of {blocks[1]} rows; the "
                "index maps that fetch a noised row's own block and then the clean blocks before it (and the backward's "
                "q blocks of one K block) are missing — the staircase runs with the whole sequence resident"
            )
        causal, diffusion = False, (half, block_diffusion)

    if d % _LANES == 0 and dv % _LANES == 0:
        # a head's columns are whole lane tiles of the [B, S, H·Dh] view
        def pack(x):
            return x.reshape(b, s, x.shape[2] * x.shape[3])

        def unpack(x):
            return x.reshape(b, s, h, dv)
    else:
        def pack(x):
            return x.transpose(0, 2, 1, 3)

        unpack = pack

    shape = (b, s, h, d, dv, scale, group, window, diffusion)
    if selected is not None:
        if blocks[1] != s or selected.shape != (s // bq, b, s, bq):
            raise ValueError(
                f"selected {selected.shape} over {s} rows of {max(d, dv)} lanes at tiles {bq} x {bkc}: the table is "
                f"[S / block_q, B, S, block_q] and all {s} rows of K and V are one resident block (here {blocks[1]}); a "
                "selection over several K blocks (the table's blocks a K block, dq's parts) is missing"
            )
        if live is None:
            live = tiles_live(selected, bkc)
        o, lse = _flash_selected(pack(q), pack(k), pack(v), selected, live, shape, blocks, interpret)
        return unpack(o), lse
    o = _flash(pack(q), pack(k), pack(v), shape, blocks, causal, interpret)
    return unpack(o)
