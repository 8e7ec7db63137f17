"""Building-block layers: RMSNorm, rotary embeddings, SwiGLU, and the
expert dispatches (dropless top-k, whole or over the block of experts a layer
holds, and the capacity einsum kept for ``ep`` > 1).

All pure functions over explicit params — XLA fuses the elementwise chains
into the adjacent matmuls, so there is nothing to hand-schedule here. The
only hand-written kernels in the repo are attention's (ops/pallas); the
experts' grouped matmul is JAX's own: ``jax.lax.ragged_dot`` off the TPU, and
on it the Pallas kernel that ships with JAX (``megablox.gmm``), see
:func:`_grouped_matmul` for why not XLA's.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "rms_norm", "rotary_embed", "yarn_inv_freq", "swiglu", "moe_dropless", "moe_dropless_held", "moe_dispatch",
]


def rms_norm(x: jnp.ndarray, weight: jnp.ndarray, eps: float = 1e-6, zero_centered: bool = False) -> jnp.ndarray:
    """``x̂ · weight`` with ``x̂ = x / sqrt(mean(x²) + eps)`` in float32. ``zero_centered``: ``x̂ · (1 + weight)``
    instead, a weight that starts at zero — the product in float32 too and rounded once: in bfloat16 ``1 + w`` is
    1 for every ``|w|`` under 2^-8, and a weight a few optimizer steps from zero would not be there at all."""
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    scale = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    if zero_centered:
        return (xf * scale * (1.0 + weight.astype(jnp.float32))).astype(dtype)
    return (xf * scale).astype(dtype) * weight


def yarn_inv_freq(
    rotary_dim: int, theta: float, factor: float = 0.0, original_max: int = 0,
    beta_fast: float = 32.0, beta_slow: float = 1.0,
) -> np.ndarray:
    """The ``rotary_dim / 2`` inverse frequencies of a rotation over the first
    ``rotary_dim`` lanes of a head: ``theta^(-2i / rotary_dim)``, and with a
    ``factor`` YaRN's blend of them — a frequency that turns more than
    ``beta_fast`` times inside ``original_max`` positions stays, one that turns
    less than ``beta_slow`` times is divided by ``factor``, a linear ramp over
    the lane index between the two (the ends floored and ceiled)."""
    half = rotary_dim // 2
    freqs = theta ** (-2.0 * np.arange(half, dtype=np.float64) / rotary_dim)
    if not factor:
        return freqs.astype(np.float32)

    def lane(turns: float) -> float:  # the lane whose frequency turns that often in original_max
        return rotary_dim * np.log(original_max / (2 * np.pi * turns)) / (2 * np.log(theta))

    low = max(int(np.floor(lane(beta_fast))), 0)
    high = min(int(np.ceil(lane(beta_slow))), rotary_dim - 1)
    ramp = np.clip((np.arange(half) - low) / max(high - low, 1e-3), 0.0, 1.0)
    return (freqs / factor * ramp + freqs * (1.0 - ramp)).astype(np.float32)


def _rotary_half(x: jnp.ndarray, positions: jnp.ndarray, inv_freq, scale: float) -> jnp.ndarray:
    """Lane i with lane i + r/2 inside the first r = 2·len(inv_freq) lanes;
    the lanes past r pass. cos and sin carry ``scale``."""
    half = len(inv_freq)
    angles = positions[:, None].astype(jnp.float32) * jnp.asarray(inv_freq, jnp.float32)[None, :]
    cos = (jnp.cos(angles) * scale)[None, :, None, :]
    sin = (jnp.sin(angles) * scale)[None, :, None, :]
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half : 2 * half].astype(jnp.float32)
    turned = [x1 * cos - x2 * sin, x2 * cos + x1 * sin]
    return jnp.concatenate([t.astype(x.dtype) for t in turned] + [x[..., 2 * half :]], axis=-1)


def _swap_pairs(x: jnp.ndarray) -> jnp.ndarray:
    """Lane 2i and lane 2i + 1 of the last axis exchanged, in ``x``'s own
    dtype: one fixed permutation, its own inverse. Carried as a product with
    the constant 0/1 matrix of it — one non-zero term a lane, so exact — which
    the TPU runs on the MXU and fuses with what reads it, where a stride-2
    walk over the lanes (``x[..., ::2]``) lowers to gathers and relayouts of
    the whole activation and its transpose to scatter-adds (PERF.md §6, PR
    53: the three forms measured on the whole dense step). Its one price: a
    non-finite lane spreads over its head (0 · inf), not over its pair."""
    lanes = np.arange(x.shape[-1])
    swap = jnp.asarray(lanes[:, None] == (lanes ^ 1)[None, :], x.dtype)
    # bfloat16 products accumulate in float32 everywhere; wider floats must not be cut to bfloat16 passes
    precision = None if x.dtype == jnp.bfloat16 else jax.lax.Precision.HIGHEST
    return jax.lax.dot_general(x, swap, (((x.ndim - 1,), (0,)), ((), ())), precision=precision)


def _pair_tables(positions: jnp.ndarray, theta: float, dh: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """:func:`_turn_pairs`'s float32 tables [1, S, 1, Dh]: pair i's angle
    ``positions · theta^(-2i / Dh)`` at both of its lanes, the cosine as it
    is and the sine negative at the even lane."""
    freqs = theta ** (-jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    angles = positions[:, None].astype(jnp.float32) * jnp.repeat(freqs, 2)[None, :]  # [S, Dh]
    sign = np.where(np.arange(dh) % 2, 1.0, -1.0).astype(np.float32)
    return jnp.cos(angles)[None, :, None, :], (jnp.sin(angles) * sign)[None, :, None, :]


@jax.custom_vjp
def _turn_pairs(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray) -> jnp.ndarray:
    """``x · cos + swap(x) · sin`` with float32 tables over the whole head
    width, ``sin`` negative at the even lanes: every pair (2i, 2i + 1) turned
    by its angle, two float32 products and their one sum, rounded once. Linear
    in ``x`` and ``swap(sin) = -sin``, so the cotangent is the same turn the
    other way and is WRITTEN so: autodiff of this body would transpose the two
    ``astype`` apart and add two bfloat16 roundings in bfloat16 (a whole ulp
    off where this rounds once; tests/test_rotary.py holds a case)."""
    out = x.astype(jnp.float32) * cos + _swap_pairs(x).astype(jnp.float32) * sin
    return out.astype(x.dtype)


def _turn_pairs_fwd(x, cos, sin):
    return _turn_pairs(x, cos, sin), (cos, sin)  # linear in x: no activation is kept


def _turn_pairs_bwd(tables, g):
    cos, sin = tables
    return _turn_pairs(g, cos, -sin), None, None


_turn_pairs.defvjp(_turn_pairs_fwd, _turn_pairs_bwd)


def rotary_embed(
    x: jnp.ndarray, positions: jnp.ndarray, theta: float = 10000.0, *, inv_freq=None, scale: float = 1.0
) -> jnp.ndarray:
    """RoPE. x: [B, S, H, Dh], positions: [S] (global positions, so the
    same code is correct under sequence sharding). Lane 2i turns with lane
    2i + 1 at ``theta^(-2i / Dh)`` (:func:`_turn_pairs`); with a table
    ``inv_freq`` [r/2] (:func:`yarn_inv_freq`) lane i turns with lane i + r/2
    inside the first r lanes instead, cos and sin times ``scale``, and the rest
    of the head passes."""
    if inv_freq is not None:
        return _rotary_half(x, positions, inv_freq, scale)
    return _turn_pairs(x, *_pair_tables(positions, theta, x.shape[-1]))


def swiglu(
    x: jnp.ndarray, w_gate: jnp.ndarray, w_in: jnp.ndarray, w_out: jnp.ndarray, activation=jax.nn.silu
) -> jnp.ndarray:
    """A gated FFN: (activation(x@w_gate) * (x@w_in)) @ w_out — SwiGLU under
    SiLU, ReGLU under ``jax.nn.relu``."""
    h = activation(x @ w_gate) * (x @ w_in)
    return h @ w_out


def _lanes_zeroed(gated: jnp.ndarray, live=None) -> jnp.ndarray:
    """How many lanes of the activated gate ``gated`` [m, f] are exactly zero
    (what a ReLU cuts), over the rows ``live`` [m] says are some expert's: a
    float32 count, which carries no gradient."""
    zero = gated == 0
    if live is not None:
        zero = zero & live[:, None]
    return jnp.sum(zero, dtype=jnp.float32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _take_rows(x: jnp.ndarray, idx: jnp.ndarray, inv: jnp.ndarray, k: int) -> jnp.ndarray:
    """``x[idx // k]`` for ``idx`` a permutation of ``k * len(x)`` row slots
    (slot ``t * k + j`` is token ``t``'s j-th copy) with inverse ``inv``. The
    backward is the inverse gather summed over a token's k copies — autodiff
    of a gather would be a scatter-add, which the TPU runs row by row."""
    return x.at[idx // k].get(mode="promise_in_bounds")


def _take_rows_fwd(x, idx, inv, k):
    return _take_rows(x, idx, inv, k), inv


def _take_rows_bwd(k, inv, g):
    back = g.at[inv].get(mode="promise_in_bounds", unique_indices=True)
    if k > 1:
        copies = back.reshape(-1, k, g.shape[-1]).astype(jnp.float32)
        back = jnp.sum(copies, axis=1).astype(g.dtype)
    return back, None, None


_take_rows.defvjp(_take_rows_fwd, _take_rows_bwd)


# megablox's (rows, contraction, columns) tile on the v5e, from a sweep of
# twelve tilings at OLMoE's shapes (131 072 rows, 2048 <-> 1024, 64 groups; PR
# 26, PERF.md §6): 3.7-4.0 ms for each of the forward, the rows' gradient and
# the weights' gradient at this one, within 7 % of the best of each; larger
# tiles do not fit the kernel's VMEM. XLA's own kernel: 5.0-5.5 ms.
_GMM_TILE = (512, 1024, 1024)


def _grouped_matmul_tpu(
    rows: jnp.ndarray, w: jnp.ndarray, counts: jnp.ndarray, interpret: bool = False
) -> jnp.ndarray:
    """``interpret``: run the kernel in Pallas's interpreter (the CPU tests)."""
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    m, k = rows.shape
    tm = min(_GMM_TILE[0], -(-m // 128) * 128)
    pad = -m % tm  # the kernel wants whole row tiles; the rows added belong to no group
    # the tile was swept for 2-byte operands and the kernel's VMEM is bytes: float32
    # operands take half the contraction and half the columns (at the full tile
    # the chip's compiler refuses the kernel: RESOURCE_EXHAUSTED in vmem, PR 41)
    narrow = 2 if jnp.dtype(rows.dtype).itemsize > 2 else 1
    out = gmm(
        jnp.pad(rows, ((0, pad), (0, 0))) if pad else rows,
        w,
        counts,
        preferred_element_type=rows.dtype,
        tiling=(tm, min(_GMM_TILE[1] // narrow, k), min(_GMM_TILE[2] // narrow, w.shape[2])),
        interpret=interpret,
    )
    return out[:m] if pad else out


def _grouped_matmul(rows: jnp.ndarray, w: jnp.ndarray, counts: jnp.ndarray) -> jnp.ndarray:
    """rows [M, k] ordered by group, w [E, k, n], counts [E] summing to M ->
    [M, n]: each row times its group's matrix, and no other.

    One algorithm, two lowerings, chosen by the platform the program is
    lowered for: ``jax.lax.ragged_dot`` everywhere but the TPU, where XLA
    would turn it into its own grouped-matmul kernel — correct and dropless,
    but its ops reach a profiler trace named ``ragged-dot-none``, without the
    ``moe/experts`` scope, so the yardstick cannot tell the experts' time from
    nothing (PR 26's first chip run read them as ``unscoped``). The Pallas
    kernel keeps the scope and was the faster of the two on the v5e."""
    # fresh closures at every call: jax 0.9.0 keeps a tracing cache per branch
    # function, and with JAX_EXPLAIN_CACHE_MISSES on (benchmark/run.py sets
    # it) explaining a second shape for a function it has seen raises inside
    # JAX; a function it has never seen is only logged
    return jax.lax.platform_dependent(
        rows,
        w,
        counts,
        tpu=lambda *args: _grouped_matmul_tpu(*args),
        default=lambda *args: jax.lax.ragged_dot(*args),
    )


def moe_dropless(
    tokens: jnp.ndarray,
    top_idx: jnp.ndarray,
    top_w: jnp.ndarray,
    w_gate: jnp.ndarray,
    w_in: jnp.ndarray,
    w_out: jnp.ndarray,
    activation=jax.nn.silu,
    gate_zeros: bool = False,
) -> Tuple[jnp.ndarray, ...]:
    """Dropless top-k experts with static shapes: every one of a token's k
    experts contributes, whatever its load — no capacity, no drop.

    tokens [T, d]; top_idx [T, k] the chosen experts; top_w [T, k] their
    weights as they are to be applied; w_gate, w_in [E, d, f]; w_out
    [E, f, d]. The T·k token-expert rows are ordered by expert (stable
    sort), each expert's contiguous group goes through its three matrices
    (:func:`_grouped_matmul`), and the rows are put back, weighted and summed
    per token. ``activation`` is the gate's (SiLU; ``jax.nn.relu`` for
    ReLU-gated experts). Returns (y [T, d], tokens per expert [E] int32), and
    under ``gate_zeros`` a third: the share of the rows' gate lanes that the
    activation left exactly zero (float32).

    Scopes ``dispatch`` / ``experts`` / ``combine`` nest under the caller's
    ``moe`` (docs/observability.md)."""
    t, k = top_idx.shape
    e = w_gate.shape[0]
    with jax.named_scope("dispatch"):
        flat = top_idx.reshape(t * k)
        order = jnp.argsort(flat, stable=True)  # row slots, by expert
        inv = jnp.argsort(order)
        # a compare and a sum: a bincount would be a scatter-add of T·k ones
        counts = jnp.sum(flat[:, None] == jnp.arange(e, dtype=flat.dtype), axis=0, dtype=jnp.int32)
        rows = _take_rows(tokens, order, inv, k)  # [T·k, d]
    with jax.named_scope("experts"):
        gated = activation(_grouped_matmul(rows, w_gate, counts))
        h = gated * _grouped_matmul(rows, w_in, counts)
        out = _grouped_matmul(h, w_out, counts)
    with jax.named_scope("combine"):
        out = _take_rows(out, inv, order, 1).reshape(t, k, -1)
        y = jnp.einsum("tkd,tk->td", out, top_w.astype(out.dtype))
    if gate_zeros:
        return y, counts, _lanes_zeroed(gated) / gated.size
    return y, counts


# tokens a group of the one-hot products that add a window's rows to their
# tokens (the MXU's width), and rows a step of the kernel
_TOKEN_TILE = 128
_TOKEN_ROWS = 512


def _rows_to_tokens_tpu(
    onehot: jnp.ndarray, rows: jnp.ndarray, sizes: jnp.ndarray, interpret: bool = False
) -> jnp.ndarray:
    """``interpret``: run the kernel in Pallas's interpreter (the CPU tests)."""
    from jax.experimental.pallas.ops.tpu.megablox.gmm import tgmm

    m, n = rows.shape
    tm = min(_TOKEN_ROWS, -(-m // 128) * 128)
    pad = -m % tm  # whole row tiles; the rows added are past every group
    narrow = 2 if jnp.dtype(rows.dtype).itemsize > 2 else 1  # as _grouped_matmul_tpu
    if pad:
        onehot, rows = (jnp.pad(x, ((0, pad), (0, 0))) for x in (onehot, rows))
    return tgmm(
        onehot.T,
        rows,
        sizes,
        preferred_element_type=rows.dtype,
        tiling=(tm, _TOKEN_TILE, min(_GMM_TILE[2] // narrow, n)),
        interpret=interpret,
    )


def _rows_to_tokens(onehot: jnp.ndarray, rows: jnp.ndarray, sizes: jnp.ndarray, t: int) -> jnp.ndarray:
    """rows [m, n] in token order, ``sizes`` [G] of them in each tile of 128
    tokens (summing to at most m: the rows past them count nowhere), onehot
    [m, 128] each row's weight at its token's place in its tile -> [t, n]:
    ``onehot[rows of tile g]^T @ rows[rows of tile g]`` for every tile, summed
    in float32. A sum over runs of rows as one grouped matmul: the transpose
    of :func:`_grouped_matmul` in its matrices, where a scatter-add would go
    row by row on the TPU. Two lowerings as there (and fresh closures, as
    there): the Pallas kernel that ships with JAX on the TPU, XLA's ragged
    product off it."""
    ragged = jax.lax.RaggedDotDimensionNumbers(
        dot_dimension_numbers=(((0,), (0,)), ((), ())), lhs_ragged_dimensions=[0], rhs_group_dimensions=[]
    )

    def xla(onehot, rows, sizes):
        # in no tile means read by nothing, whatever the memory holds: the
        # kernel selects by tile, a masked product would not
        in_a_tile = (jnp.arange(rows.shape[0]) < jnp.sum(sizes))[:, None]
        rows = jnp.where(in_a_tile, rows, jnp.zeros_like(rows))
        tiles = jax.lax.ragged_dot_general(onehot, rows, sizes, ragged, preferred_element_type=jnp.float32)
        return tiles.astype(rows.dtype)

    tiles = jax.lax.platform_dependent(
        onehot.astype(rows.dtype),
        rows,
        sizes,
        tpu=lambda *args: _rows_to_tokens_tpu(*args),
        default=xla,
    )
    return tiles.reshape(-1, rows.shape[1])[:t]


# rows a pass of the loop that moves a window's live rows (:func:`_live_rows`)
_MOVE_ROWS = 512


def _live_rows(src: jnp.ndarray, idx: jnp.ndarray, n_live, rows_a_pass: int = 0) -> jnp.ndarray:
    """``src[idx]`` -> [m, n] for the first ``n_live`` of ``idx``'s m places,
    ``rows_a_pass`` (``_MOVE_ROWS`` unless given) a pass of a loop that ends
    with the live ones — as the grouped matmuls' grids do — into a buffer
    nothing has written (``jax.lax.empty``: the TPU allocates, no pass fills
    it). The rows past the last pass stay whatever the memory held, and
    nothing may read them: the contract the layer keeps for the grouped
    matmul's output already. ``n_live`` None: one gather of all m places."""
    if n_live is None:
        return src.at[idx].get(mode="promise_in_bounds")
    m = idx.shape[0]
    step = min(rows_a_pass or _MOVE_ROWS, m)

    def move(i, out):
        start = jax.lax.min(i * step, m - step)  # the last pass of an m that is no whole number of them overlaps the one before
        rows = src.at[jax.lax.dynamic_slice(idx, (start,), (step,))].get(mode="promise_in_bounds")
        return jax.lax.dynamic_update_slice(out, rows, (start, 0))

    # ``jax.lax`` arithmetic: the ``jax.numpy`` forms are jitted functions, whose equations a printed program shares by
    # what the process traced before (the cells' pinned texts, tests/test_gdn_train.CELLS_PROGRAMS)
    passes = jax.lax.div(n_live + (step - 1), step)
    return jax.lax.fori_loop(0, passes, move, jax.lax.empty((m, src.shape[1]), src.dtype))


def _in_token_order(rows: jnp.ndarray, plan) -> jnp.ndarray:
    """A window's live rows from expert order into token order ([m, n]): a
    move and nothing else (:func:`_live_rows`), so the rows past the live ones
    stay whatever the memory held — :func:`_rows_to_tokens` counts them in no
    tile."""
    return _live_rows(rows, plan["from_place"], plan["n_live"])


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _take_window_rows(x: jnp.ndarray, plan, t: int) -> jnp.ndarray:
    """``x[plan["token"]]``: the [m, d] rows of a window of row slots (see
    :func:`_window_plan`), ordered by expert, the live ones moved and no
    other (:func:`_live_rows`). The backward puts the rows' gradients into
    token order, where a token's are one run of at most k, and sums the runs
    (:func:`_rows_to_tokens`) — no scatter, and nothing wider than the window."""
    return _live_rows(x, plan["token"], plan["n_live"])


def _take_window_rows_fwd(x, plan, t):
    return _take_window_rows(x, plan, t), plan


def _take_window_rows_bwd(t, plan, g):
    return _rows_to_tokens(plan["onehot"], _in_token_order(g, plan), plan["tile_sizes"], t), None


_take_window_rows.defvjp(_take_window_rows_fwd, _take_window_rows_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _combine_window_rows(out: jnp.ndarray, top_w: jnp.ndarray, plan, t: int) -> jnp.ndarray:
    """``y[t] = sum_j top_w[t, j] * out[place of slot (t, j)]`` over the
    window's slots: the m rows into token order, each weighted by its slot's
    gate (``plan`` carries it beside the slot: ``top_w`` is here for its
    gradient), a token's run summed in float32 -> [t, d]. Backward, row by
    row of the window: ``d out[r] = gate[r] * dy[token r]``,
    ``d top_w[slot r] = <out[r], dy[token r]>`` and zero at every slot
    outside the window."""
    weighted = plan["onehot"] * plan["gate_by_token"][:, None].astype(out.dtype)
    return _rows_to_tokens(weighted, _in_token_order(out, plan), plan["tile_sizes"], t)


def _combine_window_rows_fwd(out, top_w, plan, t):
    return _combine_window_rows(out, top_w, plan, t), (out, plan)


def _combine_window_rows_bwd(t, saved, dy):
    out, plan = saved
    k = plan["onehot_copy"].shape[1]
    live = plan["live"][:, None]  # the live rows are the first of either order
    g = _live_rows(dy, plan["token"], plan["n_live"])
    d_out = jnp.where(live, plan["gate"][:, None].astype(g.dtype) * g, jnp.zeros_like(g))
    d_gate = jnp.sum(out.astype(jnp.float32) * g.astype(jnp.float32), axis=1, keepdims=True)
    # a row's <out, dy> at its slot's copy j, as wide as a lane tile: the same
    # sum over runs puts it at [token, j]
    cells = jnp.where(live & plan["onehot_copy"], d_gate, 0.0).astype(dy.dtype)
    cells = jnp.pad(cells, ((0, 0), (0, -k % 128)))
    # the cells are a lane tile wide, a sixteenth of a row: one gather of the window's places, which XLA computes
    # them inside of — a loop would have them written out first (+32 MB a layer's backward at 65 536 places, and the
    # diffusion cell's step program stands within 30 MB of the chip's memory: PERF.md §6, PR 68)
    cells = cells.at[plan["from_place"]].get(mode="promise_in_bounds", unique_indices=True)
    d_top_w = _rows_to_tokens(plan["onehot"], cells, plan["tile_sizes"], t)
    return d_out, d_top_w[:, :k], None


_combine_window_rows.defvjp(_combine_window_rows_fwd, _combine_window_rows_bwd)


def _window_plan(slots: jnp.ndarray, gate: jnp.ndarray, n_live: jnp.ndarray, t: int, k: int, live_moves: bool = True):
    """What moving a window's rows needs, all of it index-sized ([m] or
    [m, 128]): ``slots`` [m] are row slots (slot ``t * k + j`` is token t's
    j-th copy) ordered by expert, the first ``n_live`` of them rows of held
    experts and in slot order inside an expert; ``gate`` [m] each slot's
    weight. Token order is slot order, so it takes one sort of m keys, which
    carries each row's place and gate along. ``live_moves``: the window's row
    moves follow ``n_live`` (:func:`_live_rows`); else each is one gather of m."""
    m = slots.shape[0]
    place = jnp.arange(m, dtype=jnp.int32)
    live = place < n_live
    by_token, from_place, gate_by_token = jax.lax.sort(
        (jnp.where(live, slots, t * k), place, gate), num_keys=1
    )
    tiles = -(-t // _TOKEN_TILE)
    token = by_token // k
    tile = jnp.where(live, token // _TOKEN_TILE, tiles)  # the rows past the live ones: in no tile
    return {
        "token": slots // k,  # by expert
        "gate": gate,  # by expert
        "onehot_copy": (slots % k)[:, None] == jnp.arange(k, dtype=slots.dtype),  # by expert, [m, k]
        "live": live,
        "n_live": n_live if live_moves else None,  # the rows a move moves (:func:`_live_rows`)
        "from_place": from_place,  # by token: the row's place in expert order
        "gate_by_token": gate_by_token,
        "onehot": (token % _TOKEN_TILE)[:, None] == jnp.arange(_TOKEN_TILE, dtype=token.dtype),  # by token
        "tile_sizes": jnp.sum(tile[:, None] == jnp.arange(tiles, dtype=tile.dtype), axis=0, dtype=jnp.int32),
    }


def moe_dropless_held(
    tokens: jnp.ndarray,
    top_idx: jnp.ndarray,
    top_w: jnp.ndarray,
    w_gate: jnp.ndarray,
    w_in: jnp.ndarray,
    w_out: jnp.ndarray,
    first_expert: int,
    row_bound: int,
    activation=jax.nn.silu,
    gate_zeros: bool = False,
) -> Tuple[jnp.ndarray, ...]:
    """:func:`moe_dropless` for a layer that holds a contiguous block of the
    experts a router chooses among (a chip's share of a deployment that
    divides each layer's experts over chips): ``top_idx`` counts over ALL
    the router's experts, the weights are those of experts
    ``[first_expert, first_expert + H)``. What the absent experts would add
    is left out — their rows are neither gathered nor computed. Returns
    (y [T, d], rows held = token-expert rows whose expert is here, int32), and
    under ``gate_zeros`` a third: the share of the held rows' gate lanes that
    ``activation`` left exactly zero (float32; 0 where no row is held).

    Static shapes without a drop: the row slots are sorted by held expert,
    absent ones last — a sort of the T·k keys, which carries each slot's
    gate along — and go through the grouped matmuls a window of
    ``row_bound`` slots at a time. A step whose held rows fit the first
    window — every step near balance — computes that window and no more; one
    that exceeds it (``lax.cond``) sums over all the windows in turn, each
    at the first's memory, so every row routed to a held expert contributes
    whatever the load.

    Only vectors are T·k long. Everything as wide as a row has the window's m
    rows or the T tokens, forward and backward: the window's rows are
    gathered by expert, computed, moved into token order (slot order: a
    token's rows are then one run of at most k) and the runs summed as a
    grouped one-hot product (:func:`_rows_to_tokens`). The four moves of rows
    a layer, as the grouped matmuls, cost by the window's LIVE rows
    (:func:`_live_rows`) in a step whose held rows fit the first window: at
    balance half the window."""
    t, k = top_idx.shape
    held = w_gate.shape[0]
    if t == 0:  # a batch of one sequence sliced past its end (benchmark/worker.py's second sequence): no row, no window
        nothing = (jnp.zeros(tokens.shape, tokens.dtype), jnp.int32(0))
        return (*nothing, jnp.float32(0.0)) if gate_zeros else nothing
    m = min(row_bound, t * k)
    with jax.named_scope("dispatch"):
        local = top_idx.reshape(t * k) - first_expert
        key = jnp.where((local >= 0) & (local < held), local, held)
        # row slots by held expert, the absent last, and their gates with them
        _, order, gates = jax.lax.sort(
            (key, jnp.arange(t * k, dtype=jnp.int32), jax.lax.stop_gradient(top_w).reshape(t * k)),
            num_keys=1,
            is_stable=True,
        )
        counts = jnp.sum(key[:, None] == jnp.arange(held, dtype=key.dtype), axis=0, dtype=jnp.int32)
        ends = jnp.cumsum(counts)
        n_held = ends[-1]
        # whole windows: the slots added are past every held row, and masked with them
        order, gates = (jnp.pad(x, (0, -(t * k) % m)) for x in (order, gates))

    def window(start, live_moves=True):
        """(what the sorted slots ``[start, start + m)`` add to y, under
        ``gate_zeros`` the gate lanes of their held rows left zero, else ())."""
        with jax.named_scope("dispatch"):
            # the slots past the held ones are in no expert's group: the
            # grouped matmul leaves their rows as it found them (whatever the
            # memory held), so nothing may read them, forward or backward
            plan = _window_plan(
                jax.lax.dynamic_slice_in_dim(order, start, m),
                jax.lax.dynamic_slice_in_dim(gates, start, m),
                jnp.clip(n_held - start, 0, m),
                t,
                k,
                live_moves,
            )
            rows = _take_window_rows(tokens, plan, t)  # [m, d]
            # each held expert's rows inside the window
            edges = jnp.clip(jnp.concatenate([jnp.zeros((1,), ends.dtype), ends]), start, start + m)
            sizes = edges[1:] - edges[:-1]
        with jax.named_scope("experts"):
            gated = activation(_grouped_matmul(rows, w_gate, sizes))
            h = gated * _grouped_matmul(rows, w_in, sizes)
            out = _grouped_matmul(h, w_out, sizes)
            zeroed = _lanes_zeroed(gated, plan["live"]) if gate_zeros else ()
        with jax.named_scope("combine"):
            return _combine_window_rows(out, top_w, plan, t), zeroed

    def every_window():
        # a gather of m places a move, as before PR 68: with the moves' loops inside this scan too the diffusion
        # cell's step program no longer fits the chip (over 15.75 GB by 5 MB; PERF.md §6, PR 68), and a step takes
        # this branch only past the bound
        def add(total, start):
            return jax.tree_util.tree_map(jnp.add, total, jax.checkpoint(functools.partial(window, live_moves=False))(start)), None

        nothing = (jnp.zeros(tokens.shape, tokens.dtype), jnp.float32(0.0) if gate_zeros else ())
        return jax.lax.scan(add, nothing, jnp.arange(0, t * k, m))[0]

    if m == t * k:
        y, zeroed = window(0)
    else:
        y, zeroed = jax.lax.cond(n_held <= m, lambda: window(0), every_window)
    if gate_zeros:
        return y, n_held, zeroed / (jnp.maximum(n_held, 1).astype(jnp.float32) * w_gate.shape[2])
    return y, n_held


def moe_dispatch(
    gates: jnp.ndarray, capacity: int
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Top-2 token→expert dispatch with capacity (mesh-tensorflow style —
    static shapes, einsum-friendly, so XLA turns the expert axis sharding
    into an all-to-all over ``ep``). Since the dropless :func:`moe_dropless`
    this is the path of ``ep`` > 1 ONLY (``models/transformer._ffn_moe_ep``),
    kept until experts over chips get a ``shard_map`` dispatch (ROADMAP R4).

    gates: [G, E] softmax router probabilities for G tokens.
    Returns (dispatch [G, E, C] one-hot-ish float, combine [G, E, C]).
    Tokens over capacity are dropped (standard MoE behavior).
    """
    g, e = gates.shape

    # top-1 choice
    idx1 = jnp.argmax(gates, axis=-1)
    mask1 = jax.nn.one_hot(idx1, e, dtype=gates.dtype)  # [G, E]
    # top-2: mask out the first choice
    gates2 = gates * (1.0 - mask1)
    idx2 = jnp.argmax(gates2, axis=-1)
    mask2 = jax.nn.one_hot(idx2, e, dtype=gates.dtype)

    # position of each token within its expert's buffer (first-come order)
    pos1 = jnp.cumsum(mask1, axis=0) * mask1 - mask1  # [G, E], 0-indexed
    # second choices queue behind all first choices
    pos2 = (jnp.cumsum(mask2, axis=0) - mask2 + jnp.sum(mask1, axis=0)[None, :]) * mask2

    keep1 = mask1 * (pos1 < capacity)
    keep2 = mask2 * (pos2 < capacity)

    # renormalize the two gate values over the kept choices
    g1 = jnp.sum(gates * keep1, axis=-1)
    g2 = jnp.sum(gates * keep2, axis=-1)
    denom = jnp.maximum(g1 + g2, 1e-9)
    g1, g2 = g1 / denom, g2 / denom

    c_range = jnp.arange(capacity, dtype=gates.dtype)
    onehot_pos1 = (pos1[..., None] == c_range) * keep1[..., None]  # [G,E,C]
    onehot_pos2 = (pos2[..., None] == c_range) * keep2[..., None]

    dispatch = onehot_pos1 + onehot_pos2
    combine = onehot_pos1 * g1[:, None, None] + onehot_pos2 * g2[:, None, None]
    return dispatch, combine
