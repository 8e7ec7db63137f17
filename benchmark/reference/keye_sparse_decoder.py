"""Plain reference for the language model of Keye-VL-2.0-30B-A3B (``model_type``
``KeyeVL2``) on TEXT, TRAINED UNDER ITS LEARNED KEY SELECTOR, as the
benchmark's ``keye-*`` configurations run it: a lightning indexer in front of
every grouped-query core, the selection, the core under it, the selector's own
KL term and the held experts in straightforward float32 ``jax.numpy``, written
from the equations of ISSUE 71 (after the published ``config.json`` and
DeepSeek Sparse Attention as DeepSeek-V3.2-Exp's report and arXiv:2512.02556
§2.1 give it), independent of ``torchft_tpu.models`` and ``torchft_tpu.ops``.
No kernel, no table of tiles, no search over bits, no grouped matmul, no remat:
the selector's scores are a dense ``[block, S]`` array a block of queries, the
k-th largest comes from ``jnp.sort``, the mask is applied to dense scores, the
KL term is computed from dense probabilities, an expert is applied to every
row and weighted.

**A layer**, x ``[S, d]``, positions 0..S-1. ``h1 = RMSNorm(x; ln1)`` (eps
``norm_eps``); ``q = h1·W_q`` → ``n_heads x D_h``, ``k, v = h1·W_k, h1·W_v`` →
``n_kv_heads x D_h``, no bias; q and k normed head by head over their D_h lanes
under ONE weight the heads share; both rotated over the whole head, lane i with
lane i + D_h/2 at ``rope_theta^(-2i/D_h)``. Query head a reads key/value head
``a // (n_heads / n_kv_heads)``.

*The indexer*, from ``sg(h1)`` (``sg`` = ``stop_gradient``): ``q^I_t =
sg(h1_t)·W_q^I`` → ``indexer_heads x indexer_head_dim``, ``k^I_s =
sg(h1_s)·W_k^I`` → ``indexer_head_dim`` (ONE key head), ``w_t = sg(h1_t)·W_w^I``
→ ``indexer_heads``; q^I and k^I rotated over their whole width, lane i with
lane i + D_I/2 at ``rope_theta^(-2i/D_I)``; ``I_{t,s} = (H_I·D_I)^(-1/2) · Σ_j
w_{t,j}·ReLU(q^I_{t,j}·k^I_s)`` for s ≤ t.

*The selection*: ``S_t`` = the causal keys s with ``I_{t,s} ≥`` the
``min(t + 1, k)``-th largest causal score of the row (``sparse_topk`` = k; a
tie at the threshold selects more than k; every query head of a token the same
set). ``selection=`` (a boolean ``[S, S]`` a layer) REPLACES these sets.

*The core*: ``x' = x + concat_a(Σ_{s∈S_t} softmax_{s∈S_t}(q_{t,a}·k_s/√D_h)·v_s)
·W_o``.

*The indexer's loss*: ``p_{t,s} = (1/n_heads)·Σ_a A^a_{t,s}`` on ``S_t``, ``A^a``
head a's softmax over ``S_t``; ``L^I_ℓ = (1/S)·Σ_t KL(sg(p_{t,·}) ‖
softmax_{s∈S_t}(I_{t,·}))``. The indexer's three leaves get gradient from
``L^I`` alone and every other leaf from the next-token loss alone.

*The experts*: ``h2 = RMSNorm(x'; ln2)``; ``softmax(h2·W_r)`` over all E in
float32, the k largest renormalised; ``out = x' + Σ_{e chosen AND held}
w_e·W_down,e(silu(h2·W_gate,e) ⊙ h2·W_up,e)``.

**The loss of a sequence** = next-token cross entropy over S − 1 positions
(final RMSNorm, the separate output table) ``+ Σ_ℓ L^I_ℓ``.

``slip`` names ONE thing done wrong (:data:`SLIPS`), for the checks that have
to tell the program from a near miss (``check_keye.py``,
``tests/test_sparse_attention.py``). ``sizes`` is a configuration's
``program.transformer_config``; parameter names and shapes are the interface
with the program (``init_params``): ``embed``, ``out``, ``final_norm`` and
``layers[name] [1, L, ...]``, the selector's leaves ``idx_wq``, ``idx_wk``,
``idx_ww``.

On a TPU a float32 matmul runs in lower precision unless
``jax.default_matmul_precision("highest")`` is set; :func:`loss` sets it.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Sequence

import jax
import jax.numpy as jnp

__all__ = [
    "indexer_scores", "selection", "layer", "hidden", "logits", "parts", "sequence_loss", "per_sequence_loss", "loss",
    "mrope_angles", "sets_apart", "SLIPS",
]

QUERY_BLOCK = 256
SLIPS = (
    "no_selection",  # the dense causal core: every causal key is seen
    "topk_by_head",  # each QUERY HEAD takes the k keys of its own largest core scores, not the indexer's one set a token
    "indexer_sees_gradient",  # no stop_gradient on h1 into the indexer: its term reaches the layers below
    "target_not_detached",  # no stop_gradient on p: the term reaches the core's projections
    "kl_reversed",  # KL(softmax(I) ‖ p)
    "indexer_unrotated",  # q^I and k^I as they are projected
    "not_renormalised",  # the chosen experts' weights as the softmax over all E gives them
)


def _rms_norm(x, weight, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight


def _rotate(x, positions, theta):
    """x [S, H, D]: lane i with lane i + D/2 at ``theta^(-2i/D)``, the whole head."""
    d = x.shape[-1]
    inv_freq = jnp.asarray([theta ** (-2.0 * i / d) for i in range(d // 2)], jnp.float32)
    angle = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    a, b = x[..., : d // 2], x[..., d // 2 :]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def mrope_angles(streams, section: Sequence[int], theta: float, head_dim: int):
    """[S, head_dim / 2]: the rotation angle of each of a head's lane pairs
    under M-RoPE — ``streams`` [3, S] the temporal, height and width positions
    of every token, pair i taking the stream whose ``section`` it falls in
    (``mrope_section`` [16, 24, 24]: pairs 0-15 temporal, 16-39 height, 40-63
    width) at ``theta^(-2i/head_dim)``. On text the three streams are equal
    and this is :func:`_rotate`'s table."""
    inv_freq = jnp.asarray([theta ** (-2.0 * i / head_dim) for i in range(head_dim // 2)], jnp.float32)
    which = jnp.repeat(jnp.arange(len(section)), jnp.asarray(section), total_repeat_length=head_dim // 2)
    return jnp.take(jnp.asarray(streams, jnp.float32), which, axis=0).T * inv_freq[None, :]


def _indexer(w, h1, sizes, slip):
    """(q^I [S, H_I, D_I], k^I [S, D_I], w [S, H_I] with the constant on it) from ``h1`` (the caller detaches it)."""
    s = h1.shape[0]
    heads, dim = sizes["indexer_heads"], sizes["indexer_head_dim"]
    q = (h1 @ w["idx_wq"]).reshape(s, heads, dim)
    k = (h1 @ w["idx_wk"]).reshape(s, 1, dim)
    if slip != "indexer_unrotated":
        q, k = _rotate(q, jnp.arange(s), sizes["rope_theta"]), _rotate(k, jnp.arange(s), sizes["rope_theta"])
    return q, k[:, 0], (h1 @ w["idx_ww"]) * (heads * dim) ** -0.5


def indexer_scores(q_i, k_i, weight):
    """``I`` [T, S] float32 of the queries ``q_i`` [T, H_I, D_I] against every key: every pair, causal or not."""
    return jnp.einsum("tjs,tj->ts", jax.nn.relu(jnp.einsum("tjd,sd->tjs", q_i, k_i)), weight)


def selection(scores, causal, k: int):
    """[T, S] bool: the ``causal`` keys whose score is at least the row's
    k-th largest causal score — every causal key of a row with no more than k."""
    s = scores.shape[-1]
    ranked = jnp.sort(jnp.where(causal, scores, -jnp.inf), axis=-1)  # ascending: the k-th largest is at S - k
    tau = ranked[:, s - k] if k <= s else jnp.full(scores.shape[:1], -jnp.inf)
    return causal & (scores >= tau[:, None])


def _kl(scores, p, seen, slip):
    """``KL(p_t ‖ softmax_{S_t}(I_t))`` a query [T], from dense arrays."""
    live = seen & (p > 0)  # a selected pair whose probability underflowed adds nothing
    log_q = jnp.where(live, jax.nn.log_softmax(jnp.where(seen, scores, -jnp.inf), axis=-1), 0.0)
    log_p = jnp.log(jnp.where(live, p, 1.0))
    if slip == "kl_reversed":
        return jnp.sum(jnp.where(live, jnp.exp(log_q) * (log_q - log_p), 0.0), axis=-1)
    return jnp.sum(jnp.where(live, p * (log_p - log_q), 0.0), axis=-1)


def _mix(w, h1, sizes, slip, given):
    """(the core's output [S, heads·D_h], the selector's term, the sets used
    [S, S] bool, the scores [S, S]) of one layer, a block of queries at a time."""
    sg = jax.lax.stop_gradient
    s = h1.shape[0]
    heads, hd = sizes["n_heads"], sizes["head_dim"]
    kv_heads = sizes.get("n_kv_heads") or heads
    group, eps, top = heads // kv_heads, sizes.get("norm_eps", 1e-6), sizes["sparse_topk"]
    q = _rms_norm((h1 @ w["wq"]).reshape(s, heads, hd), w["q_norm"], eps)
    k = _rms_norm((h1 @ w["wk"]).reshape(s, kv_heads, hd), w["k_norm"], eps)
    v = (h1 @ w["wv"]).reshape(s, kv_heads, hd)
    q, k = _rotate(q, jnp.arange(s), sizes["rope_theta"]), _rotate(k, jnp.arange(s), sizes["rope_theta"])
    q = q.reshape(s, kv_heads, group, hd)  # query head a reads key/value head a // group
    q_i, k_i, weight = _indexer(w, h1 if slip == "indexer_sees_gradient" else sg(h1), sizes, slip)
    block = QUERY_BLOCK if s % QUERY_BLOCK == 0 else s

    def some(start):  # a block of queries against every key
        rows = lambda a: jax.lax.dynamic_slice_in_dim(a, start, block, axis=0)
        causal = jnp.arange(s)[None, :] <= (start + jnp.arange(block))[:, None]
        scores = indexer_scores(rows(q_i), k_i, rows(weight))
        seen = rows(given) if given is not None else causal if slip == "no_selection" else selection(sg(scores), causal, top)
        core = jnp.einsum("qngd,knd->ngqk", rows(q), k) / jnp.sqrt(jnp.float32(hd))
        mask = seen[None, None]
        if slip == "topk_by_head":  # each head the k largest of its OWN core scores among the causal keys
            ranked = jnp.sort(jnp.where(causal, core, -jnp.inf), axis=-1)
            mask = causal & (core >= ranked[..., max(s - top, 0)][..., None])
        probs = jax.nn.softmax(jnp.where(mask, core, -jnp.inf), axis=-1)
        p = jnp.where(seen, jnp.mean(probs, axis=(0, 1)), 0.0)
        term = _kl(scores, p if slip == "target_not_detached" else sg(p), seen, slip)
        return jnp.einsum("ngqk,knd->qngd", probs, v), term, seen, scores

    # ``jax.checkpoint``: a gradient through the map computes a block again and keeps none of its [block, S] arrays
    att, term, seen, scores = jax.lax.map(jax.checkpoint(some), jnp.arange(0, s, block))
    return att.reshape(s, heads * hd), jnp.mean(term), seen.reshape(s, s), scores.reshape(s, s)


def sets_apart(scores, mine, theirs, eps: float):
    """(pairs that differ, pairs that differ OUTSIDE the band) between the
    reference's own sets ``mine`` and another's ``theirs`` [..., S, S] bool,
    given the reference's ``scores``: a selection flips on rounding where two
    keys' scores straddle a row's threshold by less than the other side's
    rounding, so a differing pair is in the band if its reference score lies
    within ``eps`` times the row's spread (largest less smallest causal score)
    of the row's reference threshold (its smallest selected score)."""
    s = scores.shape[-1]
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    tau = jnp.min(jnp.where(mine, scores, jnp.inf), axis=-1, keepdims=True)
    spread = jnp.max(jnp.where(causal, scores, -jnp.inf), axis=-1, keepdims=True) - jnp.min(
        jnp.where(causal, scores, jnp.inf), axis=-1, keepdims=True
    )
    differ = mine != theirs
    return jnp.sum(differ), jnp.sum(differ & (jnp.abs(scores - tau) > eps * spread))


def gate(w, h, sizes, slip=None):
    """[S, E]: the weight of every expert for every row, zero where the expert is not among the row's k."""
    n_experts, top_k = sizes["n_experts"], sizes["top_k"]
    probs = jax.nn.softmax(h @ w["router"], axis=-1)
    kth = jnp.sort(probs, axis=-1)[..., n_experts - top_k]
    weight = jnp.where(probs >= kth[..., None], probs, 0.0)  # the k largest (seeded random weights: no ties)
    if sizes.get("router_renormalize", False) and slip != "not_renormalised":
        weight = weight / jnp.sum(weight, axis=-1, keepdims=True)
    return weight


def _experts(w, h, weight, sizes):
    held = sizes.get("n_experts_held") or sizes["n_experts"]
    first = sizes.get("expert_share_index", 0) * held

    def add(y, e):  # the experts held here; the absent ones' part is left out
        mine = jax.lax.dynamic_index_in_dim(weight, first + e, axis=-1, keepdims=True)
        return y + mine * ((jax.nn.silu(h @ w["w_gate"][e]) * (h @ w["w_in"][e])) @ w["w_out"][e]), None

    return jax.lax.scan(add, jnp.zeros_like(h), jnp.arange(held))[0]


def layer(w, x, sizes, slip: Optional[str] = None, given=None):
    """(the layer's output [S, d], its selector's term, the sets it used [S, S]
    bool, its scores [S, S]) on the rows ``x`` of one sequence with its own
    float32 parameters ``w``; ``given`` replaces the layer's own sets."""
    eps = sizes.get("norm_eps", 1e-6)
    att, term, seen, scores = _mix(w, _rms_norm(x, w["ln1"], eps), sizes, slip, given)
    x = x + att @ w["wo"]
    h2 = _rms_norm(x, w["ln2"], eps)
    return x + _experts(w, h2, gate(w, h2, sizes, slip), sizes), term, seen, scores


def hidden(params: Dict[str, Any], seq, sizes: Dict[str, Any], slip: Optional[str] = None, selection=None):
    """One sequence's ids [S] -> (the state after ``final_norm`` [S, d], the
    selector's terms [L], the sets used [L, S, S], the scores [L, S, S]);
    ``selection`` [L, S, S] bool replaces the reference's own sets."""
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    x = f32(params["embed"])[seq]
    terms, sets, scores = [], [], []
    for i in range(sizes["n_layers"]):
        w = {k: f32(v[0, i]) for k, v in params["layers"].items()}
        x, term, seen, score = layer(w, x, sizes, slip, None if selection is None else selection[i])
        terms.append(term), sets.append(seen), scores.append(score)
    h = _rms_norm(x, f32(params["final_norm"]), sizes.get("norm_eps", 1e-6))
    return h, jnp.stack(terms), jnp.stack(sets), jnp.stack(scores)


def logits(params, seq, sizes, slip: Optional[str] = None, selection=None):
    """[S, V] float32 of one sequence."""
    return hidden(params, seq, sizes, slip, selection)[0] @ jnp.asarray(params["out"], jnp.float32)


def parts(params, seq, sizes, slip: Optional[str] = None, selection=None):
    """(next-token cross entropy over S − 1 positions, the selector's terms [L]) of one sequence."""
    h, terms, _, _ = hidden(params, seq, sizes, slip, selection)
    logp = jax.nn.log_softmax(h[:-1] @ jnp.asarray(params["out"], jnp.float32), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, seq[1:, None], axis=-1)), terms


def sequence_loss(params, seq, sizes, slip: Optional[str] = None, selection=None):
    """The training loss of one sequence [S]: next-token loss + Σ_ℓ L^I_ℓ."""
    lm, terms = parts(params, seq, sizes, slip, selection)
    return lm + jnp.sum(terms)


def per_sequence_loss(params, tokens, sizes, slip: Optional[str] = None):
    """The training loss of each sequence by itself, [B] — what the program's
    ``loss_fn`` gives for a batch of that one sequence."""
    return jax.lax.map(lambda seq: sequence_loss(params, seq, sizes, slip), tokens)


def loss(params, tokens, sizes):
    """The training loss of one call, under ``highest`` matmul precision."""
    with jax.default_matmul_precision("highest"):
        return jnp.mean(per_sequence_loss(params, tokens, sizes))
