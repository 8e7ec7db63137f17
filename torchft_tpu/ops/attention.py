"""Attention: plain softmax attention and ring attention for sequence
parallelism.

Ring attention (Liu et al., arxiv 2310.01889) is the long-context mechanism
the reference lacks entirely (SURVEY.md §5.7): the sequence axis is sharded
over the ``sp`` mesh axis; each device holds a Q block and streams K/V
blocks around the ring via ``ppermute``, maintaining a numerically-stable
running softmax (the flash-attention recurrence), so attention memory is
O(S/sp) per chip and the K/V transfer overlaps compute on the ICI ring.

Implemented with ``lax.scan`` (reverse-differentiable, unlike fori_loop)
inside a partial-manual ``shard_map`` over only the ``sp`` axis — dp/tp
stay under GSPMD so the same code serves every mesh layout.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

__all__ = [
    "attention",
    "block_diffusion_mask",
    "chunked_attention",
    "indexer_scores",
    "kth_largest",
    "ring_attention",
    "ring_attention_local",
    "select_top",
    "selected_head_probs",
    "selected_softmax",
    "selection_kl",
]

_NEG_INF = -1e30


def _causal_mask(q_pos: jnp.ndarray, k_pos: jnp.ndarray, window: Optional[int] = None) -> jnp.ndarray:
    """[Sq, Sk] True where k may attend: k_pos <= q_pos, and under a band
    q_pos - window < k_pos as well."""
    seen = k_pos[None, :] <= q_pos[:, None]
    if window:
        seen &= k_pos[None, :] > q_pos[:, None] - window
    return seen


def block_diffusion_mask(seq_len: int, block: int) -> jnp.ndarray:
    """[2S, 2S] True where row r sees key c of a block-diffusion sequence
    ``[noised ; clean]`` (S = ``seq_len`` rows each; row r has position
    ``r mod S`` and block ``position // block``): a noised row the noised keys
    of its own block and the clean keys of the blocks BEFORE it, a clean row
    the clean keys of its block and of those before; no row sees a noised key
    of another block. Inside a block the rows see each other in both directions."""
    row = jnp.arange(2 * seq_len)
    noised, blk = row < seq_len, (row % seq_len) // block
    q_noised, k_noised = noised[:, None], noised[None, :]
    return jnp.where(
        k_noised, q_noised & (blk[None, :] == blk[:, None]),
        jnp.where(q_noised, blk[None, :] < blk[:, None], blk[None, :] <= blk[:, None]),
    )


def _each_query_head(q: jnp.ndarray, kv: jnp.ndarray) -> jnp.ndarray:
    """Grouped-query heads for the code that wants a key/value head a query
    head: query head a reads head a // (H / Hkv). The kernel
    (``ops/pallas/flash_attention``) reads a group's one head in place."""
    group = q.shape[2] // kv.shape[2]
    return kv if group == 1 else jnp.repeat(kv, group, axis=2)


def attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    causal: bool = True,
    window: Optional[int] = None,
    block_diffusion: int = 0,
    selected: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Plain attention. q/k/v: [B, S, H, Dh] -> [B, S, H, Dh]; the values
    may have another width than the keys (the output's), and k and v fewer
    heads than q (grouped-query: a whole number of query heads a key/value
    head), here and in :func:`chunked_attention`. ``window`` (causal only):
    position i sees the keys j with i - window < j <= i. ``block_diffusion``
    (in place of both): the S rows are ``[noised ; clean]`` under
    :func:`block_diffusion_mask`. ``selected`` (in place of all three): [B, Sq,
    Sk], True where the query sees the key — every head of a query the same
    keys; the table holds causality and carries no gradient."""
    assert causal or not window, "a band is causal"
    assert not (block_diffusion and window), "the staircase has no band"
    assert selected is None or not (window or block_diffusion), "the table is the whole rule"
    k, v = _each_query_head(q, k), _each_query_head(q, v)
    scale = q.shape[-1] ** -0.5
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if selected is not None:
        scores = jnp.where(selected[:, None], scores, _NEG_INF)
    elif block_diffusion:
        scores = jnp.where(block_diffusion_mask(q.shape[1] // 2, block_diffusion)[None, None], scores, _NEG_INF)
    elif causal:
        s = q.shape[1]
        pos = jnp.arange(s)
        scores = jnp.where(_causal_mask(pos, pos, window)[None, None], scores, _NEG_INF)
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def indexer_scores(q_i: jnp.ndarray, k_i: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
    """A key selector's scores ``I[b, t, s] = Σ_j w[b, t, j]·ReLU(q_i[b, t, j]·k_i[b, s])``, float32 [B, Sq, Sk]:
    ``q_i`` [B, Sq, Hi, Di] over ONE key head ``k_i`` [B, Sk, Di], ``w`` [B, Sq, Hi] float32 (any constant is on it).
    Products in the operands' dtype, accumulated in float32; every pair, causal or not."""
    x = jnp.einsum("btjd,bsd->bjts", q_i, k_i, preferred_element_type=jnp.float32)
    return jnp.einsum("bjts,btj->bts", jax.nn.relu(x), w.astype(jnp.float32))


def _sortable(x: jnp.ndarray) -> jnp.ndarray:
    """float32 -> uint32 in the same order (-0.0 just under +0.0); a finite value or an infinity is above 0."""
    bits = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.int32)
    key = bits ^ ((bits >> 31) & jnp.int32(0x7FFFFFFF))
    return jax.lax.bitcast_convert_type(key, jnp.uint32) ^ jnp.uint32(0x80000000)


def _unsortable(u: jnp.ndarray) -> jnp.ndarray:
    key = jax.lax.bitcast_convert_type(u ^ jnp.uint32(0x80000000), jnp.int32)
    return jax.lax.bitcast_convert_type(key ^ ((key >> 31) & jnp.int32(0x7FFFFFFF)), jnp.float32)


def kth_largest(u: jnp.ndarray, k: int, axis: int) -> jnp.ndarray:
    """The ``k``-th largest of the uint32 keys ``u`` along ``axis`` — the largest T with ``count(u >= T) >= k`` — and
    0 where fewer than k keys are above 0: exact, by a search over T's bits from the top, four bits a pass (15
    thresholds counted in one reduction over the keys: eight reads of ``u`` in all, where a sort moves it
    log² S times and ``approx_max_k`` has a recall under one)."""
    axis %= u.ndim
    tau = jnp.zeros(u.shape[:axis] + u.shape[axis + 1:], jnp.uint32)
    digits = jnp.arange(1, 16, dtype=jnp.uint32).reshape((15,) + (1,) * tau.ndim)
    for shift in range(28, -1, -4):
        above = u[None] >= jnp.expand_dims(tau[None] | (digits << shift), axis + 1)
        counts = jnp.sum(above, axis=axis + 1, dtype=jnp.int32)  # [15, ...]: falls as the threshold rises
        tau = tau | (jnp.sum(counts >= k, axis=0).astype(jnp.uint32) << shift)
    return tau


def select_top(scores: jnp.ndarray, causal: jnp.ndarray, k: int, axis: int):
    """(seen, tau, over): the pairs a selector's float32 ``scores`` select — the causal keys whose score is at least
    the row's ``k``-th largest causal score, every causal key of a row with no more than k of them; ties at the
    threshold select more than k. ``causal``: bool, broadcast against ``scores``; ``axis``: the keys'. ``tau`` is the
    threshold a row (-inf where every causal key is taken), ``over`` whether a row took more than ``min(k, its causal
    keys)``. Nothing here is differentiated."""
    scores = jax.lax.stop_gradient(scores)
    scores = jnp.where(scores == 0.0, 0.0, scores)  # -0.0 (a negative weight on a ReLU's zero) ties with +0.0, as floats compare
    u = jnp.where(causal, _sortable(scores), jnp.uint32(0))
    tau = kth_largest(u, k, axis)
    seen = (u >= jnp.expand_dims(tau, axis)) & causal
    taken = jnp.sum(seen, axis=axis, dtype=jnp.int32)
    allowed = jnp.minimum(jnp.sum(jnp.broadcast_to(causal, u.shape), axis=axis, dtype=jnp.int32), k)
    return seen, jnp.where(tau == 0, -jnp.inf, _unsortable(tau)), taken > allowed


def selected_head_probs(q: jnp.ndarray, k: jnp.ndarray, seen: jnp.ndarray) -> jnp.ndarray:
    """``p[b, t, s]`` float32: the mean over the query heads of each head's softmax over the keys ``seen`` [B, Sq, Sk]
    lets query t see — a selector's target, which sums to one over a row's keys."""
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, _each_query_head(q, k)) * q.shape[-1] ** -0.5
    probs = jax.nn.softmax(jnp.where(seen[:, None], scores.astype(jnp.float32), _NEG_INF), axis=-1)
    return jnp.where(seen, jnp.mean(probs, axis=1), 0.0)


def selected_softmax(scores: jnp.ndarray, seen: jnp.ndarray, axis: int = -1):
    """(softmax, log softmax) of a selector's float32 ``scores`` over the keys ``seen`` lets a query see, along
    ``axis``; zero and the floor elsewhere."""
    logits = jnp.where(seen, scores, _NEG_INF)
    top = jnp.max(logits, axis=axis, keepdims=True)
    soft = jnp.where(seen, jnp.exp(logits - top), 0.0)
    norm = jnp.sum(soft, axis=axis, keepdims=True)
    return soft / norm, logits - top - jnp.log(norm)


def selection_kl(scores: jnp.ndarray, p: jnp.ndarray, seen: jnp.ndarray, axis: int = -1) -> jnp.ndarray:
    """``Σ_s p·(log p - log softmax_seen(scores))`` a query: the KL divergence of a selector's softmax over the keys
    it selected from the target ``p`` (zero off the selection, detached by the caller), float32, ``axis`` the keys'."""
    log_soft = selected_softmax(scores, seen, axis)[1]
    return jnp.sum(jnp.where(seen & (p > 0), p * (jnp.log(jnp.maximum(p, 1e-38)) - log_soft), 0.0), axis=axis)


def chunked_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    causal: bool = True,
    chunk: int = 512,
    tiers: Optional[int] = None,
    window: Optional[int] = None,
) -> jnp.ndarray:
    """Plain attention, one q-block at a time: same contract and numerics
    as :func:`attention` ([B, S, H, Dh] -> [B, S, H, Dh]) but the [S, S]
    score matrix is never materialized — a ``lax.scan`` over S/chunk
    q-blocks computes [chunk, S] scores with the softmax fused into the
    block, and ``jax.checkpoint`` recomputes them in the backward.

    Pure XLA: it needs no shard_map manual region, so it composes with
    GSPMD sharding and the pipeline's manual region where a Mosaic kernel
    cannot, and it runs on a CPU. On a v5e at b8 x s2048 x 16 heads x 128
    (forward, ``remat``'s forward, backward; my chip run, PR 31) it takes
    16.3 ms where plain attention takes 22.2 (f32 scores round-trip HBM)
    and the Pallas kernel 8.3, so ``attention_impl`` "auto" takes the
    kernel there. At head_dim 64 (my chip run, PR 52) it takes 92.4 ms at
    b2 x s8192 x 32 heads over 8 — f32 scores and probabilities through
    HBM, a group's key/value head repeated — where the kernel, its forward
    kept across ``remat``, takes 28.5; 9.2 against 6.3 at b8 x s2048 x 16;
    at b4 x s1024 x 16 its 1.28 ms beat plain (2.67) and only tied the
    kernel while that ran its forward twice (1.25), and lose to it now
    (1.02; the scan reads 1.28 with and without ``remat`` around it) but
    need 0.40 GB less where nothing is rematerialised (``scale_647M``):
    "auto" takes the kernel at 64 lanes from s2048 on, and this path keeps
    s1024 there and what the kernel cannot take.

    Causal runs additionally skip provably-masked key blocks via static
    k-prefix TIERS: q-segment t of ``tiers`` only scores against keys
    ``[0, (t+1)·S/tiers)`` — at 4 tiers that is 62.5% of the full S²
    score flops (53% at 16) for ~tiers compiled bodies (still one jit).
    ``tiers=None`` picks 4 below s=16k and 16 from there on (from sweeps
    of a d512 / head_dim 64 model before the benchmark existed; not
    measured at the cells' widths).

    Under a band (``window``) no prefix is full: a q-block scores against
    the ``chunk + window - 1`` keys that end with its last position and no
    tier is taken — ``(chunk + window - 1) / chunk`` of the band's own work.

    Requires ``S % chunk == 0`` (callers fall back to plain otherwise).
    """
    b, s, h, d = q.shape
    assert s % chunk == 0, f"seq {s} not divisible by chunk {chunk}"
    k, v = _each_query_head(q, k), _each_query_head(q, v)
    if window:
        assert causal, "a band is causal"
        return _banded_chunks(q, k, v, chunk, window)
    if tiers is None:
        tiers = 16 if s >= 16384 else 4
    # the divisibility gate below would otherwise silently drop tiering
    # for (s, chunk) pairs the pick doesn't divide — fall to the largest
    # compatible tier count instead. Applies to EXPLICIT tier counts too:
    # an env override hitting the gate would otherwise disable tiering
    # entirely rather than degrade gracefully (round-5 review).
    while tiers > 1 and s % (tiers * chunk) != 0:
        tiers -= 1
    scale = d**-0.5

    def scan_segment(q_seg: jnp.ndarray, k_seg, v_seg, q0: int) -> jnp.ndarray:
        """q_seg [B,Sq,H,D] against k_seg/v_seg [B,Sk,H,D]; q positions
        start at q0 (static)."""
        sq = q_seg.shape[1]
        nq = sq // chunk
        qb = jnp.moveaxis(q_seg.reshape(b, nq, chunk, h, d), 1, 0)
        k_pos = jnp.arange(k_seg.shape[1])

        def body(carry, xs):
            qc, i = xs
            scores = jnp.einsum("bqhd,bkhd->bhqk", qc, k_seg) * scale
            if causal:
                q_pos = q0 + i * chunk + jnp.arange(chunk)
                m = k_pos[None, :] <= q_pos[:, None]
                scores = jnp.where(m[None, None], scores, _NEG_INF)
            p = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(
                q_seg.dtype
            )
            return carry, jnp.einsum("bhqk,bkhd->bqhd", p, v_seg)

        _, out = jax.lax.scan(jax.checkpoint(body), 0, (qb, jnp.arange(nq)))
        return jnp.moveaxis(out, 0, 1).reshape(b, sq, h, v_seg.shape[-1])

    if not causal or tiers <= 1 or s % (tiers * chunk) != 0:
        return scan_segment(q, k, v, 0)
    seg = s // tiers
    outs = []
    for t in range(tiers):
        outs.append(
            scan_segment(
                q[:, t * seg : (t + 1) * seg],
                k[:, : (t + 1) * seg],
                v[:, : (t + 1) * seg],
                t * seg,
            )
        )
    return jnp.concatenate(outs, axis=1)


def _banded_chunks(q, k, v, chunk: int, window: int) -> jnp.ndarray:
    """:func:`chunked_attention` under a band: q-block i against the keys
    ``[i·chunk - (window - 1), (i + 1)·chunk)``, zeros (masked) before the
    sequence's start."""
    b, s, h, d = q.shape
    scale = d**-0.5
    before = min(window - 1, s)  # keys ahead of a block's first position that it can see
    nq = s // chunk
    qb = jnp.moveaxis(q.reshape(b, nq, chunk, h, d), 1, 0)
    pad = ((0, 0), (before, 0), (0, 0), (0, 0))
    k_pad, v_pad = jnp.pad(k, pad), jnp.pad(v, pad)

    def body(carry, xs):
        qc, i = xs
        k_blk = jax.lax.dynamic_slice_in_dim(k_pad, i * chunk, before + chunk, axis=1)
        v_blk = jax.lax.dynamic_slice_in_dim(v_pad, i * chunk, before + chunk, axis=1)
        k_pos = i * chunk - before + jnp.arange(before + chunk)
        q_pos = i * chunk + jnp.arange(chunk)
        seen = _causal_mask(q_pos, k_pos, window) & (k_pos >= 0)[None, :]
        scores = jnp.einsum("bqhd,bkhd->bhqk", qc, k_blk) * scale
        scores = jnp.where(seen[None, None], scores, _NEG_INF)
        p = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(q.dtype)
        return carry, jnp.einsum("bhqk,bkhd->bqhd", p, v_blk)

    _, out = jax.lax.scan(jax.checkpoint(body), 0, (qb, jnp.arange(nq)))
    return jnp.moveaxis(out, 0, 1).reshape(b, s, h, v.shape[-1])


def ring_attention_local(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    sp_size: int,
    causal: bool = True,
    axis: str = "sp",
) -> jnp.ndarray:
    """Per-shard ring attention body: q/k/v are the local [B, Sl, H, Dh]
    blocks. Call directly when already inside a manual region over ``sp``
    (e.g. the pp pipeline — Shardy forbids nesting another shard_map);
    otherwise use :func:`ring_attention`, which wraps this in its own
    shard_map."""
    my = jax.lax.axis_index(axis)
    b, sl, h, dh = q.shape
    scale = dh**-0.5
    q_pos = my * sl + jnp.arange(sl)

    qf = q.astype(jnp.float32)

    def step(carry, _):
        # k/v blocks rotate right each step, so at step t we hold the block
        # originally owned by shard (my - t) % sp
        acc, m, l, k_cur, v_cur, owner = carry
        k_pos = owner * sl + jnp.arange(sl)
        scores = jnp.einsum("bqhd,bkhd->bhqk", qf, k_cur.astype(jnp.float32))
        scores = scores * scale
        if causal:
            mask = _causal_mask(q_pos, k_pos)
            scores = jnp.where(mask[None, None], scores, _NEG_INF)

        blk_max = jnp.max(scores, axis=-1)  # [B,H,Sl]
        new_m = jnp.maximum(m, blk_max)
        # rescale previous accumulator, add this block's contribution
        correction = jnp.exp(m - new_m)
        p = jnp.exp(scores - new_m[..., None])  # [B,H,Sq,Sk]
        acc = acc * correction[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p, v_cur.astype(jnp.float32)
        )
        l = l * correction + jnp.sum(p, axis=-1)

        perm = [(r, (r + 1) % sp_size) for r in range(sp_size)]
        k_nxt = jax.lax.ppermute(k_cur, axis, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis, perm)
        owner = (owner - 1) % sp_size
        return (acc, new_m, l, k_nxt, v_nxt, owner), ()

    # Initial accumulators must carry the same varying-manual-axes type as
    # the scan outputs (jax>=0.9 VMA typing). Deriving them from q (zeroed,
    # XLA folds it) inherits q's full varying set — which includes any
    # *other* manual axes active when ring attention is nested inside e.g.
    # the pp pipeline, not just 'sp'.
    zero_bhq = jnp.einsum("bqhd->bhq", qf) * 0.0
    acc0 = jnp.einsum("bqhd->bhqd", qf) * 0.0
    m0 = zero_bhq + _NEG_INF
    l0 = zero_bhq
    (acc, m, l, _, _, _), _ = jax.lax.scan(
        step, (acc0, m0, l0, k, v, my), None, length=sp_size
    )
    # rows with no visible keys (can't happen with causal self-attn) guard
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return jnp.einsum("bhqd->bqhd", out).astype(q.dtype)


def ring_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    mesh,
    causal: bool = True,
    axis: str = "sp",
) -> jnp.ndarray:
    """Sequence-parallel attention over mesh axis ``axis``.

    q/k/v: [B, S, H, Dh] with S sharded over ``axis``; other axes remain
    GSPMD-managed. Falls back to plain attention when the axis is size 1.
    """
    sp_size = mesh.shape[axis]
    if sp_size == 1:
        return attention(q, k, v, causal=causal)

    body = functools.partial(
        ring_attention_local, sp_size=sp_size, causal=causal, axis=axis
    )
    spec = P(None, axis, None, None)
    # mesh is intentionally not forwarded: inside another partial-manual
    # region (e.g. the pp pipeline) the context mesh already has those axes
    # marked Manual, and shard_map requires an exact match — the ambient
    # mesh is always the right one. `mesh` is only used for sp_size above.
    return jax.shard_map(
        body,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        axis_names={axis},
    )(q, k, v)
