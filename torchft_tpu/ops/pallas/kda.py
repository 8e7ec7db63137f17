"""The chunked gated delta rule (``ops/kda.py``: KDA's per-channel decay, and
Gated DeltaNet's one decay a head) and the scalar decay without its correction
(Mamba-2's state-space duality) as pallas TPU kernels, fwd + bwd: a head's
state and a chunk's products never leave VMEM.

``ops/kda.kda_chunked`` in ``jax.numpy`` writes every intermediate of a chunk
— the running decay sums, three ``exp`` factors, the decayed products, the
triangular inverse's ten small products, the scan's carries — to HBM, forward
and again for autodiff: thousands of ops of a few milliseconds (PERF.md §6,
PR 36). Here a grid step holds TWO chunks of 64 positions of ``hb`` heads and
does all of it on the chip. Two kernels:

* the forward: grid ``(batch, head blocks, pairs of chunks)``, the last axis
  sequential, the heads' states in float32 scratch across it. It writes the
  outputs, the two residuals beside the inputs — the state at every chunk's
  START and every pair of chunks' ``M^-1`` as the products take it (bfloat16
  where the inputs are: 32 KB a head a pair, 16.8 MB a block of 1024
  positions of 2 x 32 heads) — and the final state.
* the backward: the same grid walked from the last pair to the first,
  carrying ``dS``. A step recomputes its chunks' ``G``, ``A`` and ``u`` from
  the inputs and the saved start states and READS ``M^-1``: forming it is 30
  of a forward step's ~41 bfloat16 passes a head, and the forward of the
  block's checkpoint has just done so (PERF.md §6, PR 64). Then the
  gradients of q, k, v, g, beta, and at the first pair the initial state's.

Two chunks a step because everything of a chunk that does not read the state
— the running sums, the decayed products, the inverse, ``M^-1 [beta v | beta
k e^G]`` — is a product of 64 x 64 matrices, half an MXU tile a side: the two
chunks' matrices are the diagonal blocks of one [128, 128] matrix and every
such product serves both (the inverse's merges stop at blocks of 64). Only
``u``, the outputs' read of the state and the state's update go chunk by
chunk, three products each.

The mathematics is ``ops/kda.py``'s, product for product (that docstring's
notation): the decayed products around a sub-block's first row
(``_two_factors`` for pairs inside a sub-block too: the caller sends here only
calls whose decay that form holds), ``M^-1 = (I + diag(beta) A_kk)^-1`` by
block forward substitution in matrix products (8-row diagonal blocks in
product form, merged pairwise up to 64), ``u = M^-1 (beta v) - M^-1 (beta k
e^G) S``, ``o = (q e^G) S + A_qk u``, ``S <- e^{G_end} S + (k e^{G_end - G})^T
u``. The backward differentiates these by hand; through the inverse as ``dM =
-(M^-T dw) (M^-1 r)^T``, and never through the sub-blocks' reference rows,
whose two factors cancel.

Layout: q, k, v, g, o are read and written in place from the ``[B, S, H·128]``
views (``flash_attention.py``'s rule), a block ``(128, 128·hb)`` a step; beta
and its gradient go as ``[B, H/hb, S, hb]`` (a step's heads the lanes of a
small block). The state is ``[dk, dv]`` outside and ``[dv, dk]`` in here,
turned at a sequence's two ends, so that a chunk's decay — a lane row like
``G`` — broadcasts over it.

Precision is the inputs': bfloat16 q, k, v give bfloat16 operands with
float32 accumulation to the 128-wide products and keep ``G``, ``A``,
``M^-1`` and the state float32, their small float32 products taken as three
products of operands split into a bfloat16 head and tail (Mosaic's ``dot``
has ``DEFAULT`` and ``HIGHEST`` only); float32 inputs — the CPU tests, which
hold the kernel to the recurrence at 2e-6 — run every product at ``HIGHEST``.
Several heads a step, written stage by stage — every head's loads, then a
step of every head's inverse before the next step of any, every store last:
one head's chain of nine small dependent products is latency, and the other
heads' fill it (one head a step took 2.57 ms a block of 1024 positions
forward where four take 1.64 and eight 1.57, host dispatch included; PERF.md
§6, PR 36).

ONE decay a head a position (Gated DeltaNet, ``ops/kda.gdn_chunked``) has a
kernel pair of its own at the end of this file, ``gdn_forward`` /
``gdn_backward``: the same frame — the grid, two chunks a step, the heads
stage by stage, the block inverse, ``u``, ``o``, the state's update, the start
states and the inverse as the two residuals, the precision — with what the
scalar decay changes:

* the pairs are ``A[i, j] = (rows_i · k_j) exp(sum of g over j < t <= i)``:
  one [2·128, 128] x [128, 128] product a KEY head (``[q; k] kᵀ``, shared by
  its value heads) and one ``exp`` of a [128, 128] block a value head, every
  exponent <= 0 — no sub-blocks, no reference rows, no clamp, and so no
  condition on a call's decay: the kernels serve 56 nats a position as they
  serve 0.1;
* every sum of g is taken over the positions it spans, as a product with a 0/1
  mask on the MXU in three exact bfloat16 pieces: between a pair (``tril ·
  where(t > j, g_t, 0)``: two chunks' sums the diagonal blocks of one
  [128, 128] matrix), from a chunk's start to a row (``G``) and from a row to
  its chunk's end (what a key leaves in the state there; the same terms as the
  pair matrix's last row, taken as a product of their own because the kernel
  needs them down a column) — never the difference of two running sums, whose
  float32 step at thousands of nats would be a pair's relative error;
* g, beta and their gradients travel as ``[B, Hv/hb, S, hb]`` float32, 4 bytes
  a head a position; a decay scales a row, so ``(q e^G) S`` is ``e^G (q S)``
  and q enters its products as it was read;
* q and k blocks are the key heads of a step's value heads (the index map
  sends value head j to key head ``j // (Hv/Hk)``); ``dq`` and ``dk`` are
  summed over a key head's value heads inside the step — the pairs' part as
  four products a KEY head of the summed ``dA ⊙ e^between`` — and, where a
  group spans several steps, once more outside.

The scalar decay WITHOUT a correction (Mamba-2's state-space duality,
``ops/kda.ssd_chunked``: ``S_t = exp(g_t) S_{t-1} + k_t ⊗ v_t``, ``o_t = S_tᵀ
q_t``) is the third pair, ``ssd_forward`` / ``ssd_backward``, after that: the
grid, the sequential last axis, the state in float32 scratch, the sums of g as
0/1-mask products, the precision and the layout of ``g`` are the frame's;
what the rule drops from it:

* there is no ``beta``, no ``k·kᵀ``, no ``M^-1`` and so no ``u``, ``w_v``,
  ``w_k`` and no second residual: a head's work in a chunk is one ``exp`` of a
  [128, 128] block, the mask, one apply. With no inverse whose merges stop at
  64 there are no "two chunks as diagonal blocks" either: a grid step is ONE
  chunk of 128 (:data:`SSD_CHUNK`), a head's pairs one whole MXU tile;
* q and k are ONE a position for all heads (``[B, S, N]``): ``q·kᵀ`` is taken
  once a step, and both products with the state run over the step's heads'
  lanes at once — the state is held ``[N, hb·P]``, every head's lanes side by
  side, the read ``[128, N] x [N, hb·P]`` decayed on the OUTPUT side, the
  write ``kᵀ (v e^T)`` decayed to the chunk's end on the VALUE side. The
  residual, the states at the chunks' starts, is kept in that layout
  (``[B, S/128, N, H·P]`` float32: nobody else reads it);
* a value head is 64 lanes (or 128): the heads of a step are taken from the
  ``[B, S, H·P]`` view in whole lane tiles, two heads a tile, and a head's
  products are the tile's with the other head's lanes zero
  (:func:`_tile_heads`) — half a tile of an operand costs the MXU a whole one
  either way;
* ``dq`` and ``dk`` are all the heads': the pairs' part as two products a step
  of the summed ``dA ⊙ e^between``, the state's part by the contraction over
  the step's heads' lanes, and the head blocks' once more outside;
* g's gradient through the pairs takes no product a head: position t is in
  the sums of the pairs j < t <= i, so it gets the sum from t on of ``dA ⊙ A``'s
  row sums less its column sums — a lane and a sublane reduction a head, and
  one 0/1-mask product a step shared with ``G``'s gradient. (A difference in
  a GRADIENT, whose terms are of the gradient's own size; the sums of g in the
  exponents are never one.)

On the chip: ``chip_smoke.py`` ``kda_cells``, ``gdn_cells`` and ``ssd_cells``
hold the kernels, forward and gradients, to the recurrence and fail without
their Mosaic call in the lowered text; ``tests/test_flash_attention.py``
compiles them for a described v5e at the cells' shapes; ``tests/test_kda.py``,
``tests/test_gdn.py`` and ``tests/test_ssd_kernel.py`` run them interpreted.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = [
    "kda_forward", "kda_backward", "gdn_forward", "gdn_backward", "ssd_forward", "ssd_backward",
    "serves", "ssd_serves", "CHUNK", "ROWS", "SSD_CHUNK",
]

CHUNK = 64  # positions of a chunk: the only one the kernels are written for
ROWS = 2 * CHUNK  # positions a grid step takes: two chunks, the diagonal blocks of its [128, 128] matrices
_SUB = 16  # ops/kda._SUB
_BASE = 8  # ops/kda._BASE
_LANES = 128
_HEADS = (4, 2, 1)  # heads a grid step, the most that divides H

_HIGHEST = jax.lax.Precision.HIGHEST
_NT = ((1,), (1,))  # a · bᵀ
_NN = ((1,), (0,))  # a · b
_TN = ((0,), (0,))  # aᵀ · b
_F32, _BF16 = jnp.float32, jnp.bfloat16


def serves(dk: int, dv: int, chunk: int) -> bool:
    """Whether the kernels take these widths: heads read in place as lane
    tiles, one chunk size."""
    return dk == _LANES and dv == _LANES and chunk == CHUNK


def _should_interpret() -> bool:
    return jax.default_backend() == "cpu"


def _dot(a, b, dims, exact):
    return jax.lax.dot_general(
        a, b, (dims, ((), ())), precision=_HIGHEST if exact else None, preferred_element_type=_F32,
    )


def _split(x, exact):
    """A float32 operand as a small product takes it: itself for ``HIGHEST``,
    else its bfloat16 head and tail (16 of its 24 bits)."""
    if exact:
        return (x,)
    head = x.astype(_BF16)
    return head, (x - head.astype(_F32)).astype(_BF16)


def _dot_f32(a, b, dims, exact):
    """A product of two :func:`_split` operands to about 2^-16 of them: at
    ``HIGHEST``, or as three bfloat16 passes."""
    if exact:
        return _dot(a[0], b[0], dims, True)
    return _dot(a[0], b[0], dims, False) + (_dot(a[0], b[1], dims, False) + _dot(a[1], b[0], dims, False))


def _iota(shape, axis):
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis)


def _square():
    """Row and column of a [ROWS, ROWS] matrix, and whether the two are of one chunk."""
    i, j = _iota((ROWS, ROWS), 0), _iota((ROWS, ROWS), 1)
    return i, j, i // CHUNK == j // CHUNK


def _exact_sum(mask, x, exact, dims=_NN):
    """``mask · x`` (``maskᵀ · x`` under ``_TN``) for a float32 x, exactly:
    ones are exact in bfloat16, and three bfloat16 pieces are all of a float32."""
    if exact:
        return _dot(mask.astype(_F32), x, dims, True)
    ones = mask.astype(_BF16)
    head, tail = _split(x, False)
    rest = (x - head.astype(_F32) - tail.astype(_F32)).astype(_BF16)
    return _dot(ones, head, dims, False) + (_dot(ones, tail, dims, False) + _dot(ones, rest, dims, False))


def _running_sum(g, exact, transposed=False):
    """``G = tril · g`` over each chunk's rows (``trilᵀ · dG`` for the
    gradient), exactly — the exponents of everything after."""
    i, j, same = _square()
    return _exact_sum(same & (j <= i), g, exact, _TN if transposed else _NN)


def _column(block, j):
    """Lane j of a small [ROWS, hb] block as a [ROWS, 1] column."""
    return jnp.sum(jnp.where(_iota(block.shape, 1) == j, block, 0.0), axis=1, keepdims=True)


def _by_chunk(first, second):
    """A [1, 128] row of each chunk, over the rows of its chunk."""
    return jnp.where(_iota((ROWS, _LANES), 0) < CHUNK, first, second)


def _sub_rows(b):
    """Where sub-block b of each chunk lies in a grid step's rows."""
    return slice(b * _SUB, (b + 1) * _SUB), slice(CHUNK + b * _SUB, CHUNK + (b + 1) * _SUB)


def _sub_blocks(x, b):
    """Sub-block b of both chunks, [sub, 128] each."""
    first, second = _sub_rows(b)
    return x[first, :], x[second, :]


def _part(product, c, part):
    """Of a sub-block's product [4·sub, …] the rows of chunk c: q's (part 0) or k's (1)."""
    return product[(2 * c + part) * _SUB : (2 * c + part + 1) * _SUB]


def _pairs(q, k, G, exact):
    """``A_qk`` (j <= i) and ``A_kk`` (j < i) of two chunks, the diagonal
    blocks of [ROWS, ROWS] float32 matrices, and the factors they are products
    of, which the backward needs again. Sub-block b of BOTH chunks is one
    product: rows ``[q; k] e^{G - ref}`` of the first chunk's, then of the
    second's, against the keys ``k e^{ref - G}`` of both — ref the running sum
    at each sub-block's first row — whose other chunk's columns are dropped."""
    row = _iota((ROWS, _LANES), 0) % CHUNK
    left_exp, right_exp, lefts, rights, products = [], [], [], [], []
    for b in range(CHUNK // _SUB):
        g0, g1 = _sub_blocks(G, b)
        e0, e1 = jnp.exp(g0 - g0[:1, :]), jnp.exp(g1 - g1[:1, :])  # from the sub-block's first row on: <= 1
        # keys up to the end of sub-block b, seen from its first row: <= 1 for
        # the earlier sub-blocks, under e^80 inside it (the caller's condition)
        e_r = jnp.where(row < (b + 1) * _SUB, jnp.exp(jnp.minimum(_by_chunk(g0[:1, :], g1[:1, :]) - G, 85.0)), 0.0)
        (q0, q1), (k0, k1) = _sub_blocks(q, b), _sub_blocks(k, b)
        left = jnp.concatenate([q0 * e0, k0 * e0, q1 * e1, k1 * e1], axis=0)  # [4·sub, 128]
        right = k * e_r
        left_exp.append((e0, e1)), right_exp.append(e_r)
        lefts.append(_split(left, exact)), rights.append(_split(right, exact))
        products.append(_dot_f32(lefts[-1], rights[-1], _NT, exact))  # [4·sub, ROWS]
    i, j, same = _square()
    rows = lambda part: jnp.concatenate([_part(p, c, part) for c in (0, 1) for p in products], axis=0)  # noqa: E731
    a_qk = jnp.where(same & (j <= i), rows(0), 0.0)
    a_kk = jnp.where(same & (j < i), rows(1), 0.0)
    return a_qk, a_kk, dict(left_exp=left_exp, right_exp=right_exp, lefts=lefts, rights=rights)


def _unit_lower_inverses(Ls, exact):
    """``(I + L)^-1`` of each L [ROWS, ROWS] (strictly lower, two diagonal
    blocks of CHUNK): ``ops/kda._unit_lower_inverse`` on whole matrices — a
    matrix of diagonal blocks times one of diagonal blocks is the blocks'
    products — up to blocks of CHUNK. One step of every matrix before the
    next step of any: each is a chain of ten dependent small products, and
    the chains of a grid step's heads fill each other's waits."""
    i, j, _ = _square()
    eye = (i == j).astype(_F32)
    base = i // _BASE == j // _BASE
    powers = [_split(jnp.where(base, L, 0.0), exact) for L in Ls]
    invs = [eye - jnp.where(base, L, 0.0) for L in Ls]
    reach = 2
    while reach < _BASE:
        squares = [_dot_f32(p, p, _NN, exact) for p in powers]
        invs = [_dot_f32(_split(inv, exact), _split(eye + sq, exact), _NN, exact) for inv, sq in zip(invs, squares)]
        reach *= 2
        if reach < _BASE:
            powers = [_split(sq, exact) for sq in squares]
    s = _BASE
    while s < CHUNK:
        corner = (i // (2 * s) == j // (2 * s)) & (i // s != j // s)
        halves = [_split(inv, exact) for inv in invs]
        across = [_dot_f32(h, _split(jnp.where(corner, L, 0.0), exact), _NN, exact) for h, L in zip(halves, Ls)]
        invs = [inv - _dot_f32(_split(a, exact), h, _NN, exact) for inv, a, h in zip(invs, across, halves)]
        s *= 2
    return invs


def _chunks(qs, ks, vs, gs, betas, dt, exact, invs=None):
    """What two chunks' forward and backward share, from their inputs alone
    (no state), for every head of a grid step, stage by stage: q, k, v
    [ROWS, 128] float32, g [ROWS, 128], beta [ROWS, 1] a head. ``invs``: the
    heads' ``M^-1`` as the products take them, in ``dt`` — what the forward
    saved, in the backward; formed here where there is none, in the forward."""
    Gs = [_running_sum(g, exact) for g in gs]
    pairs = [_pairs(q, k, G, exact) for q, k, G in zip(qs, ks, Gs)]
    if invs is None:
        Ls = [beta * a_kk for beta, (_, a_kk, _) in zip(betas, pairs)]
        invs = [inv.astype(dt) for inv in _unit_lower_inverses(Ls, exact)]
    out = []
    for q, k, v, G, beta, (a_qk, a_kk, factors), inv_d in zip(qs, ks, vs, Gs, betas, pairs, invs):
        ends = G[CHUNK - 1 : CHUNK, :], G[ROWS - 1 : ROWS, :]
        e_in, e_out = jnp.exp(G), jnp.exp(_by_chunk(*ends) - G)
        k_in = k * e_in
        out.append(dict(
            factors, a_qk=a_qk, a_kk=a_kk, inv=inv_d, e_in=e_in, e_out=e_out,
            decay=[jnp.exp(end) for end in ends], q_in=q * e_in, k_in=k_in, k_out=k * e_out,
            # [w_v | w_k] = M^-1 [beta v | beta k e^G]
            w_v=_dot(inv_d, (beta * v).astype(dt), _NN, exact),
            w_k=_dot(inv_d, (beta * k_in).astype(dt), _NN, exact),
        ))
    return out


_HALVES = (slice(0, CHUNK), slice(CHUNK, ROWS))  # a grid step's two chunks


def _heads(ref, hb, dtype=_F32):
    """The heads' lane tiles of a [ROWS, 128·hb] block."""
    return [ref[:, j * _LANES : (j + 1) * _LANES].astype(dtype) for j in range(hb)]


# ---------------------------------------------------------------------------
# forward: grid (b, head block, pair of chunks) — in turn, the states in scratch
# ---------------------------------------------------------------------------


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, s0_ref, o_ref, starts_ref, inv_ref, end_ref, state, *, hb, exact):
    n = pl.program_id(2)
    dt = v_ref.dtype

    @pl.when(n == 0)
    def _init():
        for j in range(hb):
            state[j] = s0_ref[j].T

    # every load first and every store last, the heads side by side in between
    betas = [_column(beta_ref[...], j) for j in range(hb)]
    vs = _heads(v_ref, hb)
    xs = _chunks(_heads(q_ref, hb), _heads(k_ref, hb), vs, _heads(g_ref, hb), betas, dt, exact)
    states = [state[j] for j in range(hb)]
    starts, us, reads = [], [], []
    for at, half in enumerate(_HALVES):
        starts.append(states)
        sd = [st.astype(dt) for st in states]
        us.append([x["w_v"][half] - _dot(x["w_k"][half].astype(dt), s, _NT, exact) for x, s in zip(xs, sd)])
        reads.append([_dot(x["q_in"][half].astype(dt), s, _NT, exact) for x, s in zip(xs, sd)])
        states = [
            st * x["decay"][at] + _dot(u.astype(dt), x["k_out"][half].astype(dt), _TN, exact)
            for st, x, u in zip(states, xs, us[-1])
        ]
    for j, x in enumerate(xs):
        u = jnp.concatenate([us[0][j], us[1][j]], axis=0).astype(dt)
        o = jnp.concatenate([reads[0][j], reads[1][j]], axis=0) + _dot(x["a_qk"].astype(dt), u, _NN, exact)
        o_ref[:, j * _LANES : (j + 1) * _LANES] = o.astype(o_ref.dtype)
        starts_ref[j, 0], starts_ref[j, 1] = starts[0][j], starts[1][j]
        inv_ref[j] = x["inv"]
        state[j] = states[j]

    @pl.when(n == pl.num_programs(2) - 1)
    def _finish():
        for j in range(hb):
            end_ref[j] = state[j].T


# ---------------------------------------------------------------------------
# backward: the same grid from the last pair to the first, dS in scratch
# ---------------------------------------------------------------------------


def _bwd_kernel(
    q_ref, k_ref, v_ref, g_ref, beta_ref, starts_ref, inv_ref, do_ref, dend_ref,
    dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref, ds0_ref, dstate, *, hb, exact,
):
    n = pl.program_id(2)
    dt = v_ref.dtype

    @pl.when(n == 0)
    def _init():
        for j in range(hb):
            dstate[j] = dend_ref[j].T

    heads = range(hb)
    beta_block = beta_ref[...]
    betas = [_column(beta_block, j) for j in heads]
    qs, ks, vs = _heads(q_ref, hb), _heads(k_ref, hb), _heads(v_ref, hb)
    dos = _heads(do_ref, hb, dt)
    xs = _chunks(qs, ks, vs, _heads(g_ref, hb), betas, dt, exact, [inv_ref[j] for j in heads])
    sts = [[starts_ref[j, at].astype(dt) for at in (0, 1)] for j in heads]  # [dv, dk], as the products take them
    i, jj, same = _square()

    # u of both chunks from their saved start states; A_qkᵀ do of both at once
    us = [
        jnp.concatenate([x["w_v"][half] - _dot(x["w_k"][half].astype(dt), st[at], _NT, exact)
                         for at, half in enumerate(_HALVES)], axis=0)
        for x, st in zip(xs, sts)
    ]
    from_o = [_dot(x["a_qk"].astype(dt), do, _TN, exact) for x, do in zip(xs, dos)]
    # the second chunk, then the first: o = q_in S + A_qk u;  S' = decay S + k_outᵀ u;  u = w_v - w_k S
    ds = [dstate[j] for j in heads]
    dus, dk_outs, dg_ends = [[None, None] for _ in heads], [[None, None] for _ in heads], [[None, None] for _ in heads]
    for at in (1, 0):
        half = _HALVES[at]
        for j, x in enumerate(xs):
            dsd = ds[j].astype(dt)
            du = from_o[j][half] + _dot(x["k_out"][half].astype(dt), dsd, _NT, exact)
            dus[j][at] = du
            dk_outs[j][at] = _dot(us[j][half].astype(dt), dsd, _NN, exact)
            dg_ends[j][at] = jnp.sum(ds[j] * starts_ref[j, at], axis=0, keepdims=True) * x["decay"][at]
            ds[j] = (
                _dot(dos[j][half], x["q_in"][half].astype(dt), _TN, exact) + ds[j] * x["decay"][at]
                - _dot(du.astype(dt), x["w_k"][half].astype(dt), _TN, exact)
            )
    dbetas = jnp.zeros(beta_block.shape, _F32)
    for j, x in enumerate(xs):
        q, k, v, do, beta = qs[j], ks[j], vs[j], dos[j], betas[j]
        k_out, k_in, q_in = x["k_out"], x["k_in"], x["q_in"]
        du = jnp.concatenate(dus[j], axis=0).astype(dt)
        dk_out = jnp.concatenate(dk_outs[j], axis=0)
        da_qk = jnp.where(same & (jj <= i), _dot(do, us[j].astype(dt), _NT, exact), 0.0)
        dq_in = jnp.concatenate([_dot(do[half], sts[j][at], _NN, exact) for at, half in enumerate(_HALVES)], axis=0)
        dw_k = -jnp.concatenate([_dot(du[half], sts[j][at], _NN, exact) for at, half in enumerate(_HALVES)], axis=0)
        dr_v = _dot(x["inv"], du, _TN, exact)
        dr_k = _dot(x["inv"], dw_k.astype(dt), _TN, exact)
        # M = I + diag(beta) A_kk:  dM = -M^-T dM^-1 M^-T = -(dr_v w_vᵀ + dr_k w_kᵀ)
        dm = -(
            _dot(dr_v.astype(dt), x["w_v"].astype(dt), _NT, exact)
            + _dot(dr_k.astype(dt), x["w_k"].astype(dt), _NT, exact)
        )
        da_kk = jnp.where(same & (jj < i), beta * dm, 0.0)
        dbeta = (
            jnp.sum(dm * x["a_kk"], axis=1, keepdims=True)
            + jnp.sum(dr_v * v, axis=1, keepdims=True)
            + jnp.sum(dr_k * k_in, axis=1, keepdims=True)
        )
        dk_in = beta * dr_k

        # the decayed products, a sub-block of both chunks at a time: rows
        # [q; k] e^{G - ref} against keys k e^{ref - G}; ref is a constant (its
        # two factors cancel)
        dq_rows, dk_rows, dG_rows = [[], []], [[], []], [[], []]
        dk_keys = jnp.zeros((ROWS, _LANES), _F32)
        dG_keys = jnp.zeros((ROWS, _LANES), _F32)
        for b in range(CHUNK // _SUB):
            dp = _split(jnp.concatenate([m[at] for at in _sub_rows(b) for m in (da_qk, da_kk)], axis=0), exact)
            d_left = _dot_f32(dp, x["rights"][b], _NN, exact)  # [4·sub, 128]
            d_right = _dot_f32(dp, x["lefts"][b], _TN, exact)  # [ROWS, 128]
            for c, at in enumerate(_sub_rows(b)):
                e = x["left_exp"][b][c]
                dq_b, dk_b = _part(d_left, c, 0), _part(d_left, c, 1)
                dq_rows[c].append(dq_b * e), dk_rows[c].append(dk_b * e)
                dG_rows[c].append((dq_b * q[at] + dk_b * k[at]) * e)
            right = x["right_exp"][b]
            dk_keys = dk_keys + d_right * right
            dG_keys = dG_keys + d_right * (k * right)
        whole = lambda rows: jnp.concatenate(rows[0] + rows[1], axis=0)  # noqa: E731
        dq = whole(dq_rows) + dq_in * x["e_in"]
        dk = whole(dk_rows) + dk_keys + dk_in * x["e_in"] + dk_out * x["e_out"]
        dG = whole(dG_rows) - dG_keys + dk_in * k_in + dq_in * q_in - dk_out * k_out
        row = _iota((ROWS, _LANES), 0)
        for at, half in enumerate(_HALVES):  # the chunk's last row also sets its decay
            dg_end = dg_ends[j][at] + jnp.sum((dk_out * k_out)[half], axis=0, keepdims=True)
            dG = dG + jnp.where(row == half.stop - 1, dg_end, 0.0)
        lanes = slice(j * _LANES, (j + 1) * _LANES)
        dq_ref[:, lanes] = dq.astype(dq_ref.dtype)
        dk_ref[:, lanes] = dk.astype(dk_ref.dtype)
        dv_ref[:, lanes] = (beta * dr_v).astype(dv_ref.dtype)
        dg_ref[:, lanes] = _running_sum(dG, exact, transposed=True)
        dbetas = dbetas + jnp.where(_iota(beta_block.shape, 1) == j, dbeta, 0.0)
        dstate[j] = ds[j]
    dbeta_ref[...] = dbetas

    @pl.when(n == pl.num_programs(2) - 1)
    def _finish():
        for j in heads:
            ds0_ref[j] = dstate[j].T


# ---------------------------------------------------------------------------
# block specs and the two calls
# ---------------------------------------------------------------------------


def _heads_a_step(h: int) -> int:
    return next(m for m in _HEADS if h % m == 0)


def _specs(hb, at):
    """Block specs by role; ``at(n)`` gives the pair of chunks a grid step works on."""
    vm = pltpu.VMEM
    wide = pl.BlockSpec((None, ROWS, _LANES * hb), lambda b, h, n: (b, at(n), h), memory_space=vm)
    beta = pl.BlockSpec((None, None, ROWS, hb), lambda b, h, n: (b, h, at(n), 0), memory_space=vm)
    state = pl.BlockSpec((None, hb, _LANES, _LANES), lambda b, h, n: (b, h, 0, 0), memory_space=vm)
    starts = pl.BlockSpec(
        (None, hb, 2, _LANES, _LANES), lambda b, h, n: (b, h, at(n), 0, 0), memory_space=vm
    )
    inv = pl.BlockSpec((None, hb, None, ROWS, ROWS), lambda b, h, n: (b, h, at(n), 0, 0), memory_space=vm)
    return wide, beta, state, starts, inv


_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"), vmem_limit_bytes=64 << 20,
)


def _beta_blocks(beta, hb):  # [B, S, H] -> [B, H/hb, S, hb]
    b, s, h = beta.shape
    return jnp.moveaxis(beta.reshape(b, s, h // hb, hb), 2, 1)


# Both calls are ``jax.jit``s of their own: a model's layers, and a layer's
# forward, recomputed forward and backward, then share ONE trace of a kernel's
# body and one lowering of it to Mosaic — seconds each, of a program's set-up —
# where a plain function is traced and lowered at every call site.
@functools.partial(jax.jit, static_argnames="interpret")
def kda_forward(q, k, v, g, beta, initial_state, interpret=None):
    """q, k, v [B, S, H·128] in one dtype, g [B, S, H·128] float32, beta
    [B, S, H] float32, the state [B, H, 128, 128] float32; S whole pairs of
    chunks (:data:`ROWS`). Returns (o [B, S, H·128], the state at each chunk's
    start [B, H, S/64, 128, 128] as the kernels hold it ([dv, dk]), each pair
    of chunks' ``M^-1`` [B, H, S/128, 128, 128] in q's dtype, the final
    state)."""
    b, s, _ = q.shape
    h = beta.shape[-1]
    hb = _heads_a_step(h)
    wide, beta_spec, state, starts, inv = _specs(hb, lambda n: n)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, hb=hb, exact=q.dtype == jnp.float32),
        grid=(b, h // hb, s // ROWS),
        in_specs=[wide, wide, wide, wide, beta_spec, state],
        out_specs=[wide, starts, inv, state],
        out_shape=[
            jax.ShapeDtypeStruct(v.shape, v.dtype),
            jax.ShapeDtypeStruct((b, h, s // CHUNK, _LANES, _LANES), jnp.float32),
            jax.ShapeDtypeStruct((b, h, s // ROWS, ROWS, ROWS), v.dtype),
            jax.ShapeDtypeStruct((b, h, _LANES, _LANES), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((hb, _LANES, _LANES), jnp.float32)],
        compiler_params=_PARAMS,
        interpret=_should_interpret() if interpret is None else interpret,
        name="kda_fwd",
    )(q, k, v, g, _beta_blocks(beta, hb), initial_state)


@functools.partial(jax.jit, static_argnames="interpret")
def kda_backward(q, k, v, g, beta, starts, inv, do, d_end, interpret=None):
    """The gradients of q, k, v, g, beta and the initial state, from the
    forward's inputs, its ``starts`` and ``M^-1``, and the cotangents of o and
    of the final state."""
    b, s, _ = q.shape
    h = beta.shape[-1]
    hb, n = _heads_a_step(h), s // ROWS
    wide, beta_spec, state, starts_spec, inv_spec = _specs(hb, lambda i: n - 1 - i)
    dq, dk, dv, dg, dbeta, ds0 = pl.pallas_call(
        functools.partial(_bwd_kernel, hb=hb, exact=q.dtype == jnp.float32),
        grid=(b, h // hb, n),
        in_specs=[wide, wide, wide, wide, beta_spec, starts_spec, inv_spec, wide, state],
        out_specs=[wide, wide, wide, wide, beta_spec, state],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
            jax.ShapeDtypeStruct(g.shape, jnp.float32),
            jax.ShapeDtypeStruct((b, h // hb, s, hb), jnp.float32),
            jax.ShapeDtypeStruct((b, h, _LANES, _LANES), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((hb, _LANES, _LANES), jnp.float32)],
        compiler_params=_PARAMS,
        interpret=_should_interpret() if interpret is None else interpret,
        name="kda_bwd",
    )(q, k, v, g, _beta_blocks(beta, hb), starts, inv, do, d_end)
    return dq, dk, dv, dg, jnp.moveaxis(dbeta, 1, 2).reshape(b, s, h), ds0


# ---------------------------------------------------------------------------
# ONE log-decay a head a position (Gated DeltaNet, ``ops/kda.gdn_chunked``):
# the same frame, the pairs as (rows · kᵀ) ⊙ exp(sums between)
# ---------------------------------------------------------------------------


def _lanes(columns):
    """[ROWS, 1] columns side by side, head j's in lane j of a [ROWS, 128] matrix."""
    lane = _iota((ROWS, _LANES), 1)
    out = jnp.zeros((ROWS, _LANES), _F32)
    for j, column in enumerate(columns):
        out = out + jnp.where(lane == j, column, 0.0)
    return out


def _total(x):
    """The sum of a matrix, [1, 1]."""
    return jnp.sum(jnp.sum(x, axis=0, keepdims=True), axis=1, keepdims=True)


def _rows_sum(x):
    return jnp.sum(x, axis=1, keepdims=True)


def _masks():
    """Of a [ROWS, ROWS] matrix of two chunks: row, and column j of row i's
    chunk with j <= i, with j < i, with j > i."""
    i, j, same = _square()
    return _iota((ROWS, 1), 0), same & (j <= i), same & (j < i), same & (j > i)


def _dot_pieces(pieces, b, dims, exact):
    """A :func:`_split` float32 operand against one that is exact as it
    stands (the inputs' q and k): to 2^-16, a product a piece."""
    out = _dot(pieces[0], b, dims, exact)
    for piece in pieces[1:]:
        out = out + _dot(piece, b, dims, exact)
    return out


def _gdn_chunks(q_ref, k_ref, v_ref, g_block, beta_block, hb, kb, exact, invs=None):
    """What two chunks' forward and backward share, from their inputs alone,
    for the ``hb`` value heads of a grid step over its ``kb`` key heads, stage
    by stage. g, beta [ROWS, hb]: ONE log-decay and one write strength a head
    a position. Every sum of g is taken over the positions it spans — the
    pair's own (``between``), a chunk's start to a position (``G``), a
    position to its chunk's end (``T``) — as a masked triangular product,
    never as a difference of two running sums. ``invs`` as :func:`_chunks` takes them."""
    dt = v_ref.dtype
    _, lower, strict, later = _masks()
    gs = [_column(g_block, h) for h in range(hb)]
    betas = [_column(beta_block, h) for h in range(hb)]
    g_lanes = _lanes(gs)
    G_all = _exact_sum(lower, g_lanes, exact)  # every head's sums in two products
    T_all = _exact_sum(later, g_lanes, exact)
    qs, ks, vs = _heads(q_ref, kb, dt), _heads(k_ref, kb, dt), _heads(v_ref, hb)
    # q kᵀ and k kᵀ before the decay: one product a KEY head for all its value heads
    products = [_dot(jnp.concatenate([q, k], axis=0), k, _NT, exact) for q, k in zip(qs, ks)]  # [2·ROWS, ROWS]
    group = hb // kb
    betweens = [_exact_sum(lower, jnp.where(strict, g, 0.0), exact) for g in gs]
    decayed = [jnp.exp(between) for between in betweens]  # exponents <= 0: nothing overflows whatever the decay
    a_qk = [jnp.where(lower, products[h // group][:ROWS] * e, 0.0) for h, e in enumerate(decayed)]
    a_kk = [jnp.where(strict, products[h // group][ROWS:] * e, 0.0) for h, e in enumerate(decayed)]
    if invs is None:
        invs = [inv.astype(dt) for inv in _unit_lower_inverses([beta * a for beta, a in zip(betas, a_kk)], exact)]
    out = []
    for h in range(hb):
        k = ks[h // group].astype(_F32)
        G, T = _column(G_all, h), _column(T_all, h)
        e_in, e_out = jnp.exp(G), jnp.exp(T)
        k_in, inv_d = k * e_in, invs[h]
        out.append(dict(
            beta=betas[h], v=vs[h], a_qk=a_qk[h], a_kk=a_kk[h], decayed=decayed[h], inv=inv_d, e_in=e_in, e_out=e_out,
            decay=[jnp.exp(G[CHUNK - 1 : CHUNK, :]), jnp.exp(G[ROWS - 1 : ROWS, :])], k_in=k_in, k_out=k * e_out,
            # [w_v | w_k] = M^-1 [beta v | beta k e^G]
            w_v=_dot(inv_d, (betas[h] * vs[h]).astype(dt), _NN, exact),
            w_k=_dot(inv_d, (betas[h] * k_in).astype(dt), _NN, exact).astype(dt),
        ))
    return out, qs, ks


def _gdn_fwd_kernel(
    q_ref, k_ref, v_ref, g_ref, beta_ref, s0_ref, o_ref, starts_ref, inv_ref, end_ref, state, *, hb, kb, exact,
):
    n = pl.program_id(2)
    dt = v_ref.dtype

    @pl.when(n == 0)
    def _init():
        for h in range(hb):
            state[h] = s0_ref[h].T

    xs, qs, _ = _gdn_chunks(q_ref, k_ref, v_ref, g_ref[...], beta_ref[...], hb, kb, exact)
    group = hb // kb
    states = [state[h] for h in range(hb)]
    starts, us, reads = [], [], []
    for at, half in enumerate(_HALVES):
        starts.append(states)
        sd = [st.astype(dt) for st in states]
        us.append([x["w_v"][half] - _dot(x["w_k"][half], s, _NT, exact) for x, s in zip(xs, sd)])
        reads.append([_dot(qs[h // group][half], s, _NT, exact) for h, s in enumerate(sd)])
        states = [
            st * x["decay"][at] + _dot(u.astype(dt), x["k_out"][half].astype(dt), _TN, exact)
            for st, x, u in zip(states, xs, us[-1])
        ]
    for h, x in enumerate(xs):
        u = jnp.concatenate([us[0][h], us[1][h]], axis=0).astype(dt)
        # (q e^G) S = e^G (q S): one decay a row scales the product, not its operand
        o = x["e_in"] * jnp.concatenate([reads[0][h], reads[1][h]], axis=0) + _dot(x["a_qk"].astype(dt), u, _NN, exact)
        o_ref[:, h * _LANES : (h + 1) * _LANES] = o.astype(o_ref.dtype)
        starts_ref[h, 0], starts_ref[h, 1] = starts[0][h], starts[1][h]
        inv_ref[h] = x["inv"]
        state[h] = states[h]

    @pl.when(n == pl.num_programs(2) - 1)
    def _finish():
        for h in range(hb):
            end_ref[h] = state[h].T


def _gdn_bwd_kernel(
    q_ref, k_ref, v_ref, g_ref, beta_ref, starts_ref, inv_ref, do_ref, dend_ref,
    dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref, ds0_ref, dstate, *, hb, kb, exact,
):
    n = pl.program_id(2)
    dt = v_ref.dtype

    @pl.when(n == 0)
    def _init():
        for h in range(hb):
            dstate[h] = dend_ref[h].T

    heads, group = range(hb), hb // kb
    xs, qs, ks = _gdn_chunks(
        q_ref, k_ref, v_ref, g_ref[...], beta_ref[...], hb, kb, exact, [inv_ref[h] for h in heads]
    )
    row, lower, strict, later = _masks()
    dos = _heads(do_ref, hb)
    dods = [do.astype(dt) for do in dos]
    do_ins = [(x["e_in"] * do).astype(dt) for x, do in zip(xs, dos)]  # o = e^G (q S) + A_qk u
    sts = [[starts_ref[h, at].astype(dt) for at in (0, 1)] for h in heads]  # [dv, dk], as the products take them

    # u of both chunks from their saved start states; A_qkᵀ do of both at once
    us = [
        jnp.concatenate([x["w_v"][half] - _dot(x["w_k"][half], st[at], _NT, exact)
                         for at, half in enumerate(_HALVES)], axis=0)
        for x, st in zip(xs, sts)
    ]
    from_o = [_dot(x["a_qk"].astype(dt), do, _TN, exact) for x, do in zip(xs, dods)]
    # the second chunk, then the first: S' = decay S + k_outᵀ u;  u = w_v - w_k S
    ds = [dstate[h] for h in heads]
    dus, dk_outs, dg_ends = [[None, None] for _ in heads], [[None, None] for _ in heads], [[None, None] for _ in heads]
    for at in (1, 0):
        half = _HALVES[at]
        for h, x in enumerate(xs):
            dsd = ds[h].astype(dt)
            du = from_o[h][half] + _dot(x["k_out"][half].astype(dt), dsd, _NT, exact)
            dus[h][at] = du
            dk_outs[h][at] = _dot(us[h][half].astype(dt), dsd, _NN, exact)
            dg_ends[h][at] = _total(ds[h] * starts_ref[h, at]) * x["decay"][at]
            ds[h] = (
                _dot(do_ins[h][half], qs[h // group][half], _TN, exact) + ds[h] * x["decay"][at]
                - _dot(du.astype(dt), x["w_k"][half], _TN, exact)
            )
    zeros = lambda: [jnp.zeros((ROWS, _LANES), _F32) for _ in range(kb)]  # noqa: E731
    dq_sums, dk_sums, d_qk, d_kk = zeros(), zeros(), zeros(), zeros()  # a key head's, over its value heads
    dGs, dTs, d_betweens, dbetas = [], [], [], []
    for h, x in enumerate(xs):
        key = h // group
        beta, k_in, k_out = x["beta"], x["k_in"], x["k_out"]
        du = jnp.concatenate(dus[h], axis=0).astype(dt)
        dk_out = jnp.concatenate(dk_outs[h], axis=0)
        da_qk = jnp.where(lower, _dot(dods[h], us[h].astype(dt), _NT, exact), 0.0)
        # e^G (do S): q's gradient through the state's read, as it is
        dq_in = jnp.concatenate([_dot(do_ins[h][half], sts[h][at], _NN, exact) for at, half in enumerate(_HALVES)], axis=0)
        dw_k = -jnp.concatenate([_dot(du[half], sts[h][at], _NN, exact) for at, half in enumerate(_HALVES)], axis=0)
        dr_v = _dot(x["inv"], du, _TN, exact)
        dr_k = _dot(x["inv"], dw_k.astype(dt), _TN, exact)
        # M = I + diag(beta) A_kk:  dM = -M^-T dM^-1 M^-T = -(dr_v w_vᵀ + dr_k w_kᵀ)
        dm = -(
            _dot(dr_v.astype(dt), x["w_v"].astype(dt), _NT, exact)
            + _dot(dr_k.astype(dt), x["w_k"], _NT, exact)
        )
        da_kk = jnp.where(strict, beta * dm, 0.0)
        dk_in = beta * dr_k
        dbetas.append(_rows_sum(dm * x["a_kk"]) + _rows_sum(dr_v * x["v"] + dr_k * k_in))
        # A = P ⊙ e^between: the pair's sum gets dA ⊙ A, the product dA ⊙ e^between
        between = _exact_sum(lower, da_qk * x["a_qk"] + da_kk * x["a_kk"], exact, _TN)
        d_betweens.append(_rows_sum(jnp.where(strict, between, 0.0)))
        dG = _rows_sum(dq_in * qs[key].astype(_F32) + dk_in * k_in)
        for at, half in enumerate(_HALVES):  # the chunk's last row also sets its decay
            dG = dG + jnp.where(row == half.stop - 1, dg_ends[h][at], 0.0)
        dGs.append(dG)
        dTs.append(_rows_sum(dk_out * k_out))
        d_qk[key] = d_qk[key] + da_qk * x["decayed"]
        d_kk[key] = d_kk[key] + da_kk * x["decayed"]
        dq_sums[key] = dq_sums[key] + dq_in
        dk_sums[key] = dk_sums[key] + dk_in * x["e_in"] + dk_out * x["e_out"]
        dv_ref[:, h * _LANES : (h + 1) * _LANES] = (beta * dr_v).astype(dv_ref.dtype)
        dstate[h] = ds[h]
    dg = _lanes(d_betweens) + _exact_sum(lower, _lanes(dGs), exact, _TN) + _exact_sum(later, _lanes(dTs), exact, _TN)
    dg_ref[...] = dg[:, :hb]
    dbeta_ref[...] = _lanes(dbetas)[:, :hb]
    for key in range(kb):  # P = [q; k] kᵀ, one product a key head: so its gradient's four
        q, k = qs[key], ks[key]
        d_q, d_k = _split(d_qk[key], exact), _split(d_kk[key], exact)
        lanes = slice(key * _LANES, (key + 1) * _LANES)
        dq_ref[:, lanes] = (dq_sums[key] + _dot_pieces(d_q, k, _NN, exact)).astype(dq_ref.dtype)
        dk_ref[:, lanes] = (
            dk_sums[key] + _dot_pieces(d_q, q, _TN, exact) + (_dot_pieces(d_k, k, _NN, exact) + _dot_pieces(d_k, k, _TN, exact))
        ).astype(dk_ref.dtype)

    @pl.when(n == pl.num_programs(2) - 1)
    def _finish():
        for h in heads:
            ds0_ref[h] = dstate[h].T


def _gdn_heads(hv: int, hk: int):
    """(value heads, key heads) a grid step holds: the most value heads that
    are whole groups of a key head, or a part of one group."""
    group = hv // hk
    hb = next(m for m in _HEADS if hv % m == 0 and (m % group == 0 or group % m == 0))
    return hb, max(1, hb // group)


def _gdn_specs(hv, hk, at):
    hb, kb = _gdn_heads(hv, hk)
    wide, small, state, starts, inv = _specs(hb, at)
    # value head j reads key head j // (hv / hk): a step's first value head names its key heads' block
    keys = pl.BlockSpec(
        (None, ROWS, _LANES * kb), lambda b, h, n: (b, at(n), h * hb * hk // hv // kb), memory_space=pltpu.VMEM
    )
    return hb, kb, keys, wide, small, state, starts, inv


@functools.partial(jax.jit, static_argnames="interpret")
def gdn_forward(q, k, v, g, beta, initial_state, interpret=None):
    """q, k [B, S, Hk·128] and v [B, S, Hv·128] in one dtype, g and beta
    [B, S, Hv] float32, the state [B, Hv, 128, 128] float32; S whole pairs of
    chunks (:data:`ROWS`), Hv a multiple of Hk. Returns what
    :func:`kda_forward` does, a state a VALUE head."""
    b, s, _ = v.shape
    hv, hk = beta.shape[-1], q.shape[-1] // _LANES
    hb, kb, keys, wide, small, state, starts, inv = _gdn_specs(hv, hk, lambda n: n)
    return pl.pallas_call(
        functools.partial(_gdn_fwd_kernel, hb=hb, kb=kb, exact=q.dtype == jnp.float32),
        grid=(b, hv // hb, s // ROWS),
        in_specs=[keys, keys, wide, small, small, state],
        out_specs=[wide, starts, inv, state],
        out_shape=[
            jax.ShapeDtypeStruct(v.shape, v.dtype),
            jax.ShapeDtypeStruct((b, hv, s // CHUNK, _LANES, _LANES), jnp.float32),
            jax.ShapeDtypeStruct((b, hv, s // ROWS, ROWS, ROWS), v.dtype),
            jax.ShapeDtypeStruct((b, hv, _LANES, _LANES), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((hb, _LANES, _LANES), jnp.float32)],
        compiler_params=_PARAMS,
        interpret=_should_interpret() if interpret is None else interpret,
        name="gdn_fwd",
    )(q, k, v, _beta_blocks(g, hb), _beta_blocks(beta, hb), initial_state)


@functools.partial(jax.jit, static_argnames="interpret")
def gdn_backward(q, k, v, g, beta, starts, inv, do, d_end, interpret=None):
    """The gradients of q, k ([B, S, Hk·128]: summed over a key head's value
    heads), v, g, beta ([B, S, Hv] float32) and the initial state, from what
    :func:`gdn_forward` took and its ``starts`` and ``M^-1``."""
    b, s, _ = v.shape
    hv, hk = beta.shape[-1], q.shape[-1] // _LANES
    n = s // ROWS
    hb, kb, keys, wide, small, state, starts_spec, inv_spec = _gdn_specs(hv, hk, lambda i: n - 1 - i)
    # a step writes its key heads' gradients over ITS value heads; where a key
    # head's group spans several steps they are summed after, in float32
    parts = hv // hb * kb // hk
    per_step = jax.ShapeDtypeStruct((b, s, hv // hb * kb * _LANES), q.dtype if parts == 1 else jnp.float32)
    dq, dk, dv, dg, dbeta, ds0 = pl.pallas_call(
        functools.partial(_gdn_bwd_kernel, hb=hb, kb=kb, exact=q.dtype == jnp.float32),
        grid=(b, hv // hb, n),
        in_specs=[keys, keys, wide, small, small, starts_spec, inv_spec, wide, state],
        out_specs=[
            pl.BlockSpec((None, ROWS, _LANES * kb), lambda b, h, i: (b, n - 1 - i, h), memory_space=pltpu.VMEM),
        ] * 2 + [wide, small, small, state],
        out_shape=[
            per_step, per_step,
            jax.ShapeDtypeStruct(v.shape, v.dtype),
            jax.ShapeDtypeStruct((b, hv // hb, s, hb), jnp.float32),
            jax.ShapeDtypeStruct((b, hv // hb, s, hb), jnp.float32),
            jax.ShapeDtypeStruct((b, hv, _LANES, _LANES), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((hb, _LANES, _LANES), jnp.float32)],
        compiler_params=_PARAMS,
        interpret=_should_interpret() if interpret is None else interpret,
        name="gdn_bwd",
    )(q, k, v, _beta_blocks(g, hb), _beta_blocks(beta, hb), starts, inv, do, d_end)
    if parts > 1:
        dq, dk = (x.reshape(b, s, hk, parts, _LANES).sum(axis=3).reshape(q.shape).astype(q.dtype) for x in (dq, dk))
    unblock = lambda x: jnp.moveaxis(x, 1, 2).reshape(b, s, hv)  # noqa: E731
    return dq, dk, dv, unblock(dg), unblock(dbeta), ds0


# ---------------------------------------------------------------------------
# The scalar decay WITHOUT a correction (Mamba-2's state-space duality,
# ``ops/kda.ssd_chunked``): the frame without its inverse — ONE chunk of 128 a
# grid step, one q and one k a position for all heads
# ---------------------------------------------------------------------------

SSD_CHUNK = ROWS  # positions of a chunk, all a grid step takes: one whole [128, 128] tile a head


def ssd_serves(n_state: int, heads: int, p: int, chunk: int) -> bool:
    """Whether the state-space kernels take these widths: the state whole lane
    tiles, the heads' lanes whole tiles read in place (a value head half a
    tile or one), one chunk size."""
    return n_state % _LANES == 0 and p in (_LANES // 2, _LANES) and heads * p % _LANES == 0 and chunk == SSD_CHUNK


def _ssd_heads(heads: int, p: int) -> int:
    """The heads a grid step holds, in whole lane tiles, chosen as
    :func:`_gdn_heads` chooses: the most tiles of :data:`_HEADS` that divide the
    heads' lanes. Sixteen tiles a step run 1 % of the cell's step faster than
    four (what a step does once for all its heads is a third of a step of four
    tiles) and cost 12 s of its set-up: the kernels' bodies are written out a
    head, and lowering them to Mosaic took 16 s where four tiles take 4.5
    (PERF.md §6, PR 70)."""
    tiles = heads * p // _LANES
    return next(m for m in _HEADS if tiles % m == 0) * _LANES // p


def _ssd_masks():
    """Of ONE chunk's [ROWS, ROWS] matrix: row, column j <= row i, j < i, j > i."""
    i, j = _iota((ROWS, ROWS), 0), _iota((ROWS, ROWS), 1)
    return _iota((ROWS, 1), 0), j <= i, j < i, j > i


def _tile_heads(x, p):
    """A [rows, 128] lane tile once a head of it, the other heads' lanes zero:
    a head ``p`` lanes wide is half a tile or a whole one, and a product with
    the masked tile is the product with the head."""
    if p == _LANES:
        return [x]
    lane = _iota(x.shape, 1)
    return [jnp.where((lane >= i * p) & (lane < (i + 1) * p), x, jnp.zeros_like(x)) for i in range(_LANES // p)]


def _over_tile(values, p, rows=ROWS):
    """A value a row ([rows, 1] or [1, 1]) of each head of a lane tile, over that head's lanes: [rows, 128]."""
    out = jnp.broadcast_to(values[-1], (rows, _LANES))
    if len(values) == 1:
        return out
    lane = _iota((rows, _LANES), 1)
    for i, value in enumerate(values[:-1]):
        out = jnp.where((lane >= i * p) & (lane < (i + 1) * p), value, out)
    return out


def _rows_as_lanes(rows):
    """[1, ROWS] rows, head j's as the column in lane j of a [ROWS, 128] matrix (:func:`_lanes` for rows): the
    heads' rows one under another, turned once."""
    held = -(-len(rows) // 8) * 8
    at = _iota((held, ROWS), 0)
    out = jnp.zeros((held, ROWS), _F32)
    for j, r in enumerate(rows):
        out = jnp.where(at == j, r, out)
    if held < _LANES:
        out = jnp.concatenate([out, jnp.zeros((_LANES - held, ROWS), _F32)], axis=0)
    return out.T


def _wide_tiles(tiles):
    return tiles[0] if len(tiles) == 1 else jnp.concatenate(tiles, axis=1)


def _ssd_chunk(q_ref, k_ref, g_block, hb, p, exact):
    """What a chunk's forward and backward share, from q, k and g alone, for
    the ``hb`` heads of a grid step: ``q·kᵀ`` ONCE for all of them, and a head's
    three sums of g — between a pair, from the chunk's start through a row
    (``G``: what the state at the start has decayed by when the row reads it),
    from a row to the chunk's end (``T``: what the row's write has decayed by
    there) — each over the positions it spans (:func:`_gdn_chunks`'s rule)."""
    _, lower, strict, later = _ssd_masks()
    gs = [_column(g_block, h) for h in range(hb)]
    g_lanes = _lanes(gs)
    G_all, T_all = _exact_sum(lower, g_lanes, exact), _exact_sum(later, g_lanes, exact)  # every head's in two products
    q, k = q_ref[...], k_ref[...]
    products = _dot(q, k, _NT, exact)  # [ROWS, ROWS]
    out = []
    for h, g in enumerate(gs):
        decayed = jnp.exp(_exact_sum(lower, jnp.where(strict, g, 0.0), exact))  # exponents <= 0 at every decay
        G = _column(G_all, h)
        out.append(dict(
            decayed=decayed, a=jnp.where(lower, products * decayed, 0.0),
            e_in=jnp.exp(G), e_out=jnp.exp(_column(T_all, h)), decay=jnp.exp(G[ROWS - 1 : ROWS, :]),
        ))
    return out, q, k


def _ssd_fwd_kernel(q_ref, k_ref, v_ref, g_ref, s0_ref, o_ref, starts_ref, end_ref, state, *, hb, p, exact):
    n = pl.program_id(2)
    dt = v_ref.dtype

    @pl.when(n == 0)
    def _init():
        state[...] = s0_ref[...]

    xs, q, k = _ssd_chunk(q_ref, k_ref, g_ref[...], hb, p, exact)
    S = state[...]  # [N, hb·P]: the state as every head's lanes side by side
    starts_ref[...] = S
    read = _dot(q, S.astype(dt), _NN, exact)  # every head's read of the state at the chunk's start, at once
    per = _LANES // p
    written, decays = [], []
    for t in range(hb // per):
        lanes, mine = slice(t * _LANES, (t + 1) * _LANES), xs[t * per : (t + 1) * per]
        v = v_ref[:, lanes]
        o = read[:, lanes] * _over_tile([x["e_in"] for x in mine], p)  # decayed on the OUTPUT side
        for x, v_head in zip(mine, _tile_heads(v, p)):
            o = o + _dot(x["a"].astype(dt), v_head, _NN, exact)
        o_ref[:, lanes] = o.astype(o_ref.dtype)
        # what the chunk's positions write, decayed to its end on the VALUE side: k has no head
        written.append((v.astype(_F32) * _over_tile([x["e_out"] for x in mine], p)).astype(dt))
        decays.append(_over_tile([x["decay"] for x in mine], p, rows=1))
    state[...] = S * _wide_tiles(decays) + _dot(k, _wide_tiles(written), _TN, exact)

    @pl.when(n == pl.num_programs(2) - 1)
    def _finish():
        end_ref[...] = state[...]


def _ssd_bwd_kernel(
    q_ref, k_ref, v_ref, g_ref, starts_ref, do_ref, dend_ref, dq_ref, dk_ref, dv_ref, dg_ref, ds0_ref, dstate,
    *, hb, p, exact,
):
    n = pl.program_id(2)
    dt = v_ref.dtype

    @pl.when(n == 0)
    def _init():
        dstate[...] = dend_ref[...]

    xs, q, k = _ssd_chunk(q_ref, k_ref, g_ref[...], hb, p, exact)
    row, lower, strict, later = _ssd_masks()
    S, d_end = starts_ref[...], dstate[...]  # the state at the chunk's start; the cotangent of the one at its end
    Sd, d_end_d = S.astype(dt), d_end.astype(dt)
    # o = e^G (q S) + A v;  S' = decay S + kᵀ (v e^T)
    read = _dot(q, Sd, _NN, exact)
    d_written = _dot(k, d_end_d, _NN, exact)  # [ROWS, hb·P]
    per = _LANES // p
    d_products = jnp.zeros((ROWS, ROWS), _F32)  # q kᵀ is all the heads': so its gradient, summed
    d_reads, written, decays, leaving, entering, dGs, dTs = [], [], [], [], [], [], []
    for t in range(hb // per):
        lanes, mine = slice(t * _LANES, (t + 1) * _LANES), xs[t * per : (t + 1) * per]
        v, do = v_ref[:, lanes], do_ref[:, lanes]
        v32, do32 = v.astype(_F32), do.astype(_F32)
        e_in, e_out = _over_tile([x["e_in"] for x in mine], p), _over_tile([x["e_out"] for x in mine], p)
        do_in = do32 * e_in
        d_reads.append(do_in.astype(dt))
        written.append((v32 * e_out).astype(dt))
        decays.append(_over_tile([x["decay"] for x in mine], p, rows=1))
        dv = d_written[:, lanes] * e_out
        through_G = _tile_heads(do_in * read[:, lanes], p)
        through_T = _tile_heads(dv * v32, p)
        through_decay = _tile_heads(d_end[:, lanes] * S[:, lanes], p)
        for i, (x, do_head) in enumerate(zip(mine, _tile_heads(do, p))):
            dv = dv + _dot(x["a"].astype(dt), do_head, _TN, exact)
            da = jnp.where(lower, _dot(do_head, v, _NT, exact), 0.0)
            d_products = d_products + da * x["decayed"]
            # A = (q kᵀ) ⊙ e^between: the pair's sum gets dA ⊙ A, and position t is in the sums of the pairs j < t <= i.
            # Those of t and not of t + 1 are row t's, those of t + 1 and not of t column t's: g's gradient is the
            # sum from t on of what leaves less what enters — taken with dG's below, in the same product
            d_between = jnp.where(strict, da * x["a"], 0.0)
            leaving.append(_rows_sum(d_between))
            entering.append(jnp.sum(d_between, axis=0, keepdims=True))
            # the chunk's last row also sets the state's decay over the whole chunk
            dGs.append(_rows_sum(through_G[i]) + jnp.where(row == ROWS - 1, _total(through_decay[i]) * x["decay"], 0.0))
            dTs.append(_rows_sum(through_T[i]))
        dv_ref[:, lanes] = dv.astype(dv_ref.dtype)
    d_read, d_pairs = _wide_tiles(d_reads), _split(d_products, exact)
    # both products with the state over the step's heads' lanes at once: their sum over the heads is the contraction
    dq_ref[...] = (_dot(d_read, Sd, _NT, exact) + _dot_pieces(d_pairs, k, _NN, exact)).astype(dq_ref.dtype)
    dk_ref[...] = (_dot(_wide_tiles(written), d_end_d, _NT, exact) + _dot_pieces(d_pairs, q, _TN, exact)).astype(dk_ref.dtype)
    from_a_row_on = _lanes(dGs) + _lanes(leaving) - _rows_as_lanes(entering)
    dg = _exact_sum(lower, from_a_row_on, exact, _TN) + _exact_sum(later, _lanes(dTs), exact, _TN)
    dg_ref[...] = dg[:, :hb]
    dstate[...] = d_end * _wide_tiles(decays) + _dot(q, d_read, _TN, exact)

    @pl.when(n == pl.num_programs(2) - 1)
    def _finish():
        ds0_ref[...] = dstate[...]


def _ssd_specs(n_state, hb, p, at):
    """Block specs by role; ``at(n)`` gives the chunk a grid step works on."""
    vm, width = pltpu.VMEM, hb * p
    keys = pl.BlockSpec((None, ROWS, n_state), lambda b, h, n: (b, at(n), 0), memory_space=vm)
    wide = pl.BlockSpec((None, ROWS, width), lambda b, h, n: (b, at(n), h), memory_space=vm)
    small = pl.BlockSpec((None, None, ROWS, hb), lambda b, h, n: (b, h, at(n), 0), memory_space=vm)
    state = pl.BlockSpec((None, n_state, width), lambda b, h, n: (b, 0, h), memory_space=vm)
    starts = pl.BlockSpec((None, None, n_state, width), lambda b, h, n: (b, at(n), 0, h), memory_space=vm)
    return keys, wide, small, state, starts


def _state_lanes(state):  # [B, H, N, P] -> [B, N, H·P]: every head's lanes side by side, as the kernels hold it
    b, h, n_state, p = state.shape
    return jnp.moveaxis(state, 1, 2).reshape(b, n_state, h * p)


def _state_heads(state, p):  # and back
    b, n_state, width = state.shape
    return jnp.moveaxis(state.reshape(b, n_state, width // p, p), 2, 1)


@functools.partial(jax.jit, static_argnames="interpret")
def ssd_forward(q, k, v, g, initial_state, interpret=None):
    """q, k [B, S, N] and v [B, S, H·P] in one dtype, g [B, S, H] float32, the
    state [B, H, N, P] float32; S whole chunks (:data:`SSD_CHUNK`). Returns
    (o [B, S, H·P], the state at each chunk's start [B, S/128, N, H·P] as the
    kernels hold it — the one residual beside the inputs — and the final
    state [B, H, N, P])."""
    b, s, width = v.shape
    n_state, h = q.shape[-1], g.shape[-1]
    p = width // h
    hb = _ssd_heads(h, p)
    keys, wide, small, state, starts = _ssd_specs(n_state, hb, p, lambda n: n)
    o, at_starts, end = pl.pallas_call(
        functools.partial(_ssd_fwd_kernel, hb=hb, p=p, exact=q.dtype == jnp.float32),
        grid=(b, h // hb, s // ROWS),
        in_specs=[keys, keys, wide, small, state],
        out_specs=[wide, starts, state],
        out_shape=[
            jax.ShapeDtypeStruct(v.shape, v.dtype),
            jax.ShapeDtypeStruct((b, s // ROWS, n_state, width), jnp.float32),
            jax.ShapeDtypeStruct((b, n_state, width), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((n_state, hb * p), jnp.float32)],
        compiler_params=_PARAMS,
        interpret=_should_interpret() if interpret is None else interpret,
        name="ssd_fwd",
    )(q, k, v, _beta_blocks(g, hb), _state_lanes(initial_state))
    return o, at_starts, _state_heads(end, p)


@functools.partial(jax.jit, static_argnames="interpret")
def ssd_backward(q, k, v, g, starts, do, d_end, interpret=None):
    """The gradients of q, k ([B, S, N]: summed over all heads), v, g
    ([B, S, H] float32) and the initial state, from what :func:`ssd_forward`
    took, its ``starts``, and the cotangents of o and of the final state."""
    b, s, width = v.shape
    n_state, h = q.shape[-1], g.shape[-1]
    p = width // h
    hb = _ssd_heads(h, p)
    n, parts = s // ROWS, h // hb
    keys, wide, small, state, starts_spec = _ssd_specs(n_state, hb, p, lambda i: n - 1 - i)
    # a step writes q's and k's gradients over ITS heads; the head blocks' are summed after, in float32
    per_step = jax.ShapeDtypeStruct((b, s, parts * n_state), q.dtype if parts == 1 else jnp.float32)
    dq, dk, dv, dg, ds0 = pl.pallas_call(
        functools.partial(_ssd_bwd_kernel, hb=hb, p=p, exact=q.dtype == jnp.float32),
        grid=(b, parts, n),
        in_specs=[keys, keys, wide, small, starts_spec, wide, state],
        out_specs=[
            pl.BlockSpec((None, ROWS, n_state), lambda b, h, i: (b, n - 1 - i, h), memory_space=pltpu.VMEM),
        ] * 2 + [wide, small, state],
        out_shape=[
            per_step, per_step,
            jax.ShapeDtypeStruct(v.shape, v.dtype),
            jax.ShapeDtypeStruct((b, parts, s, hb), jnp.float32),
            jax.ShapeDtypeStruct((b, n_state, width), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((n_state, hb * p), jnp.float32)],
        compiler_params=_PARAMS,
        interpret=_should_interpret() if interpret is None else interpret,
        name="ssd_bwd",
    )(q, k, v, _beta_blocks(g, hb), starts, do, _state_lanes(d_end))
    if parts > 1:
        dq, dk = (x.reshape(b, s, parts, n_state).sum(axis=2).astype(q.dtype) for x in (dq, dk))
    return dq, dk, dv, jnp.moveaxis(dg, 1, 2).reshape(b, s, h), _state_heads(ds0, p)
