"""Plain reference for the decoder of LFM2-MoE (``model_type`` ``lfm2_moe``), as
the benchmark's ``lfm2-*`` configurations run it: forward pass and training loss
in straightforward float32 ``jax.numpy``, written from the layer equations of
ISSUE 51 (after the published ``config.json`` and its model class), independent
of ``torchft_tpu.models`` and ``torchft_tpu.ops``. No kernel, no chunked scan,
no sort, no grouped matmul: the convolution is three shifted adds, a
mask is an explicit comparison of positions; the one ``jax.checkpoint`` is around
a block of queries, whose scores a backward pass forms again: the same numbers.

Every layer is ``x = x + mix(RMSNorm(x; ln1)); x = x + ff(RMSNorm(x; ln2))``
(``operator_norm`` and ``ffn_norm`` of the source), layers counted from 1 as the
program counts them (the source counts from 0).

* **Gated short convolution** (``conv_layers``): ``[B, C, X] = split3(h·W_in)``
  in this order along the features (``W_in`` d x 3d, no bias), ``u = B ⊙ X``,
  ``c_t = Σ_{i<K} w[i] ⊙ u_{t-K+1+i}`` with ``K = conv_kernel`` taps a channel
  (depthwise, causal: zeros ahead of position 0, the last tap is the current
  position; no bias), ``out = (C ⊙ c)·W_out``. No softmax, no rotation, no
  activation function.
* **Attention** (every other layer): ``q = h·W_q`` → ``n_heads x D``,
  ``k = h·W_k``, ``v = h·W_v`` → ``n_kv_heads x D`` (no bias); q and k normalised
  head by head — RMSNorm over a head's D lanes under ONE D-wide weight the heads
  share (``q_norm``, ``k_norm``: ``q_layernorm``, ``k_layernorm`` of the source) —
  then rotated over the whole head, lane i with lane i + D/2, at
  ``rope_theta^(-2i/D)``; query head a reads key/value head
  ``a // (n_heads / n_kv_heads)``; scores ``q·k / √D``, softmax over the keys
  j <= i, ``·W_o``. Computed in blocks of ``QUERY_BLOCK`` queries against every
  key so that 8 192 positions fit a chip: a block's scores are the same numbers
  as the full matrix's rows.
* **Feed-forward**: the first ``n_dense_layers`` layers a SwiGLU of ``d_ff``;
  the others ``s = sigmoid(h·W_g)`` over all E experts, the k largest of
  ``s + router_bias`` chosen (the bias chooses only: no gradient reaches it),
  weights ``routed_scaling_factor · s_e / (Σ_chosen s + router_norm_eps)``,
  ``y = Σ_{e chosen AND held} w_e SwiGLU_e(h)``: a loop over the experts held,
  each applied to every token and masked to the tokens that chose it. No shared
  expert.
* loss: ``RMSNorm(x; final_norm)·W_out`` over the vocabulary rows held, mean
  next-token cross entropy; no balance term.

**Departures from the published model, all stated in the configuration file.**
(1) *The share*: of the E experts the router chooses among this chip holds
``n_experts_held`` (the block ``expert_share_index``); what the absent experts
would add is left out, here as in the program; the weights are still
renormalised over all k chosen. (2) a separate output table (the source ties
it to the embedding).

``sizes`` is a configuration's ``program.transformer_config``. Parameter names
and shapes are the interface with the program (``init_params``): ``embed``,
``out``, ``final_norm``, and the layers grouped by kind ``"<mixer>.<ff>"``
(mixers ``conv`` and ``full``): ``lead[kind][name] [n, ...]`` for the leading
layers, ``periods[kind][name] [repeats, n, ...]`` for those the program scans.

On a TPU a float32 matmul runs in lower precision unless
``jax.default_matmul_precision("highest")`` is set; :func:`loss` sets it.
"""

from __future__ import annotations

from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp

__all__ = ["forward", "hidden", "loss", "per_sequence_loss", "layer_params", "inv_frequencies"]

QUERY_BLOCK = 256


def _rms_norm(x, weight, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight


def _swiglu(h, gate, up, down):
    return (jax.nn.silu(h @ gate) * (h @ up)) @ down


def _kinds(sizes) -> List[Tuple[str, str]]:
    out = []
    for i in range(1, sizes["n_layers"] + 1):
        mixer = "conv" if i in sizes.get("conv_layers", ()) else "full"
        ff = "experts" if sizes.get("n_experts") and i > sizes.get("n_dense_layers", 0) else "dense"
        out.append((mixer, ff))
    return out


def layer_params(params, sizes) -> List[Dict[str, Any]]:
    """Each layer's own parameters, in layer order, from the program's tree:
    how many leading layers and how long a period is read off the tree's own
    leading axes, the order of kinds inside them off ``sizes``."""
    kinds = [f"{m}.{f}" for m, f in _kinds(sizes)]
    if "layers" in params:
        return [{k: v[0, i] for k, v in params["layers"].items()} for i in range(len(kinds))]
    lead = sum(len(group["ln1"]) for group in params["lead"].values())
    period = sum(group["ln1"].shape[1] for group in params["periods"].values())
    out, seen = [], {}
    for kind in kinds[:lead]:
        out.append({k: v[seen.get(kind, 0)] for k, v in params["lead"][kind].items()})
        seen[kind] = seen.get(kind, 0) + 1
    for r in range((len(kinds) - lead) // period):
        seen = {}
        for kind in kinds[lead : lead + period]:
            out.append({k: v[r, seen.get(kind, 0)] for k, v in params["periods"][kind].items()})
            seen[kind] = seen.get(kind, 0) + 1
    return out


def _shift(u, by: int):
    """``u`` [B, S, C] moved ``by`` positions later, zeros ahead of position 0."""
    return jnp.pad(u, ((0, 0), (by, 0), (0, 0)))[:, : u.shape[1]] if by else u


def _gated_conv(w, h, sizes):
    d, taps = h.shape[-1], sizes["conv_kernel"]
    bcx = h @ w["conv_in"]
    gate_in, gate_out, x = bcx[..., :d], bcx[..., d : 2 * d], bcx[..., 2 * d :]
    u = gate_in * x
    c = sum(w["conv_w"][i] * _shift(u, taps - 1 - i) for i in range(taps))  # the last tap: the position itself
    return (gate_out * c) @ w["conv_out"]


def inv_frequencies(sizes) -> List[float]:
    """The D/2 inverse frequencies of the rotation, as plain Python floats."""
    r, theta = sizes["head_dim"], sizes["rope_theta"]
    return [theta ** (-2.0 * i / r) for i in range(r // 2)]


def _rotate(x, inv_freq):
    """x [B, S, H, D]: lane i with lane i + D/2."""
    half = len(inv_freq)
    angle = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * jnp.asarray(inv_freq, jnp.float32)[None, :]
    cos, sin = jnp.cos(angle)[None, :, None, :], jnp.sin(angle)[None, :, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _head_norm(x, weight, eps):
    """x [B, S, H, D]: each head's D lanes by themselves, one weight for all heads."""
    return _rms_norm(x, weight, eps)


def _attention(w, h, sizes):
    b, s, _ = h.shape
    heads, hd = sizes["n_heads"], sizes["head_dim"]
    kv_heads = sizes.get("n_kv_heads") or heads
    group, eps = heads // kv_heads, sizes.get("norm_eps", 1e-6)
    inv_freq = inv_frequencies(sizes)
    q = (h @ w["wq"]).reshape(b, s, heads, hd)
    k = (h @ w["wk"]).reshape(b, s, kv_heads, hd)
    v = (h @ w["wv"]).reshape(b, s, kv_heads, hd)
    q = _rotate(_head_norm(q, w["q_norm"], eps), inv_freq)
    k = _rotate(_head_norm(k, w["k_norm"], eps), inv_freq)
    # query head a reads key/value head a // group: [B, S, kv, group, D] against [B, S, kv, D]
    q = q.reshape(b, s, kv_heads, group, hd)
    block = QUERY_BLOCK if s % QUERY_BLOCK == 0 else s
    key_pos = jnp.arange(s)

    def rows(start):  # a block of queries against every key
        q_blk = jax.lax.dynamic_slice_in_dim(q, start, block, axis=1)
        scores = jnp.einsum("bqngd,bknd->bngqk", q_blk, k) / jnp.sqrt(jnp.float32(hd))
        seen = key_pos[None, :] <= (start + jnp.arange(block))[:, None]
        probs = jax.nn.softmax(jnp.where(seen[None, None, None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("bngqk,bknd->bqngd", probs, v)

    # a block's scores are formed again in a backward pass: the same numbers, a block's memory at a time
    att = jax.lax.map(jax.checkpoint(rows), jnp.arange(0, s, block))  # [blocks, B, block, kv, group, D]
    att = jnp.moveaxis(att, 0, 1).reshape(b, s, heads * hd)
    return att @ w["wo"]


def _weigh(scores, biased):
    """What a chosen expert weighs before the renormalisation: its own score.
    The bias chooses and does not weigh."""
    return scores


def _route(w, h, sizes):
    """[..., E] float32: each token's weight on every expert, 0 on those not chosen."""
    n_experts, top_k = sizes["n_experts"], sizes["top_k"]
    scores = jax.nn.sigmoid(h @ w["router"])
    biased = scores + jax.lax.stop_gradient(w["router_bias"]) if "router_bias" in w else scores
    kth = jnp.sort(biased, axis=-1)[..., n_experts - top_k]
    chosen = biased >= kth[..., None]  # the k largest (seeded random weights: no ties)
    weight = jnp.where(chosen, _weigh(scores, biased), 0.0)
    if sizes.get("router_renormalize", False):
        weight = weight / (jnp.sum(weight, axis=-1, keepdims=True) + sizes.get("router_norm_eps", 1e-20))
    return weight * sizes.get("routed_scaling_factor", 1.0)


def _experts(w, h, sizes):
    held = sizes.get("n_experts_held") or sizes["n_experts"]
    first = sizes.get("expert_share_index", 0) * held
    weight = _route(w, h, sizes)

    def add(y, e):  # the experts held here; the absent ones' part is left out
        mine = jax.lax.dynamic_index_in_dim(weight, first + e, axis=-1, keepdims=True)
        return y + mine * _swiglu(h, w["w_gate"][e], w["w_in"][e], w["w_out"][e]), None

    y, _ = jax.lax.scan(add, jnp.zeros_like(h), jnp.arange(held))
    return y


def _layer(w, x, sizes, kind: Tuple[str, str]):
    mixer, ff = kind
    eps = sizes.get("norm_eps", 1e-6)
    h = _rms_norm(x, w["ln1"], eps)
    x = x + (_gated_conv(w, h, sizes) if mixer == "conv" else _attention(w, h, sizes))
    h = _rms_norm(x, w["ln2"], eps)
    return x + (_experts(w, h, sizes) if ff == "experts" else _swiglu(h, w["w_gate"], w["w_in"], w["w_out"]))


def hidden(params: Dict[str, Any], tokens, sizes: Dict[str, Any]):
    """tokens [B, S] -> the final hidden state after ``final_norm`` [B, S, d]."""
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    x = f32(params["embed"])[tokens]
    for kind, w in zip(_kinds(sizes), layer_params(params, sizes)):
        x = _layer({k: f32(v) for k, v in w.items()}, x, sizes, kind)
    return _rms_norm(x, f32(params["final_norm"]), sizes.get("norm_eps", 1e-6))


def forward(params: Dict[str, Any], tokens, sizes: Dict[str, Any]):
    """tokens [B, S] -> logits [B, S, V], float32 throughout."""
    return hidden(params, tokens, sizes) @ jnp.asarray(params["out"], jnp.float32)


def _nll(logits, tokens):
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp[:, :-1], tokens[:, 1:, None], axis=-1)[..., 0]


def per_sequence_loss(params, tokens, sizes):
    """The training loss of each sequence by itself, [B]: its mean next-token
    cross entropy (the last position has no target) — what the program's
    ``loss_fn`` gives for a batch of that one sequence. One sequence at a
    time, so that a block's scores are one sequence's."""
    one = lambda seq: jnp.mean(_nll(forward(params, seq[None], sizes), seq[None]))
    return jax.lax.map(one, tokens)


def loss(params, tokens, sizes):
    """The training loss of one call, under ``highest`` matmul precision."""
    with jax.default_matmul_precision("highest"):
        return jnp.mean(_nll(forward(params, tokens, sizes), tokens))
