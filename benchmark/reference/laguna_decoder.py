"""Plain reference for the decoder of Laguna (``model_type`` ``laguna``), as the
benchmark's ``laguna-*`` configurations run it: forward pass and training loss
in straightforward float32 ``jax.numpy``, written from the layer equations of
ISSUE 41 (after the published ``config.json``), independent of
``torchft_tpu.models`` and ``torchft_tpu.ops``. No flash kernel, no band that
skips tiles, no sort, no grouped matmul, no remat: masks are explicit
comparisons of positions.

Every layer is ``x = x + mix(RMSNorm(x; ln1)); x = x + ff(RMSNorm(x; ln2))``,
layers counted from 1 as the program counts them (the source counts from 0).

* **Attention**, layer l with ``H_l`` query heads (``n_heads_per_layer[l-1]``)
  over ``n_kv_heads`` key/value heads of ``head_dim``: ``q = h·W_q`` →
  ``H_l x D``, ``k = h·W_k``, ``v = h·W_v`` → ``n_kv_heads x D``; query head a
  reads key/value head ``a // (H_l / n_kv_heads)``. Rotation of q and k, lane i
  with lane i + r/2 inside the first r lanes, the rest passing: a **window**
  layer (``window_layers``) r = D at ``window_rope_theta^(-2i/D)``; a **global**
  layer r = ``rotary_dim`` under YaRN — ``f_i = rope_theta^(-2i/r)``;
  ``ramp_i = clip((i - lo) / (hi - lo), 0, 1)`` with ``lo = floor(c(beta_fast))``,
  ``hi = ceil(c(beta_slow))``, ``c(n) = r·ln(original_max / 2πn) / (2·ln rope_theta)``;
  ``inv_freq_i = (f_i / factor)·ramp_i + f_i·(1 - ramp_i)``; cos and sin times
  ``yarn_attention_factor``. Scores ``q·k / √D``, softmax over the keys j <= i
  (global) or i - window < j <= i (window), ``·W_o``. Computed in blocks of
  ``QUERY_BLOCK`` queries against every key so that two sequences of 8 192 fit
  a chip: a block's scores are the same numbers as the full matrix's rows.
* **Feed-forward**: the first ``n_dense_layers`` layers a SwiGLU of ``d_ff``;
  the others ``s = sigmoid(h·W_r)`` over all E experts, the k largest chosen
  (no selection bias), weights ``routed_scaling_factor · s_e / Σ_chosen s``;
  ``y = SwiGLU_shared(h) + Σ_{e chosen AND held} w_e SwiGLU_e(h)``: a loop over
  the experts held, each applied to every token and masked to the tokens that
  chose it.
* loss: ``RMSNorm(x; final_norm)·W_out`` over the vocabulary rows held, mean
  next-token cross entropy; no balance term.

**Departures from the published model, all stated in the configuration file.**
(1) *The share*: of the E experts the router chooses among this chip holds
``n_experts_held`` (the block ``expert_share_index``); what the absent experts
would add is left out, here as in the program; the weights are still
renormalised over all k chosen. (2) ``gating: true`` is read as gated (SwiGLU)
feed-forward blocks; no gate on the attention output. (3) no norm on q or k.

``sizes`` is a configuration's ``program.transformer_config``. Parameter names
and shapes are the interface with the program (``init_params``): ``embed``,
``out``, ``final_norm``, and the layers grouped by kind ``"<mixer>.<ff>"``
(mixers ``full`` and ``window``): ``lead[kind][name] [n, ...]`` for the leading
layers, ``periods[kind][name] [repeats, n, ...]`` for those the program scans.

On a TPU a float32 matmul runs in lower precision unless
``jax.default_matmul_precision("highest")`` is set; :func:`loss` sets it.
"""

from __future__ import annotations

import math
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
        mixer = "window" if i in sizes.get("window_layers", ()) else "full"
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


def inv_frequencies(sizes, mixer: str):
    """(the r/2 inverse frequencies of a layer of ``mixer``, what cos and sin
    are multiplied by), as plain Python floats."""
    if mixer == "window":
        r, theta = sizes["head_dim"], sizes.get("window_rope_theta") or sizes["rope_theta"]
        return [theta ** (-2.0 * i / r) for i in range(r // 2)], 1.0
    r, theta = sizes.get("rotary_dim") or sizes["head_dim"], sizes["rope_theta"]
    plain = [theta ** (-2.0 * i / r) for i in range(r // 2)]
    factor = sizes.get("yarn_factor", 0.0)
    if not factor:
        return plain, 1.0

    def c(n):
        return r * math.log(sizes["yarn_original_max"] / (2 * math.pi * n)) / (2 * math.log(theta))

    lo = max(math.floor(c(sizes.get("yarn_beta_fast", 32.0))), 0)
    hi = min(math.ceil(c(sizes.get("yarn_beta_slow", 1.0))), r - 1)
    ramp = [min(max((i - lo) / (hi - lo), 0.0), 1.0) for i in range(r // 2)]
    blended = [f / factor * t + f * (1.0 - t) for f, t in zip(plain, ramp)]
    return blended, sizes.get("yarn_attention_factor", 1.0)


def _rotate(x, inv_freq, scale):
    """x [B, S, H, D]: lane i with lane i + r/2, r = 2·len(inv_freq); the rest passes."""
    half = len(inv_freq)
    angle = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * jnp.asarray(inv_freq, jnp.float32)[None, :]
    cos, sin = scale * jnp.cos(angle)[None, :, None, :], scale * jnp.sin(angle)[None, :, None, :]
    a, b = x[..., :half], x[..., half : 2 * half]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, x[..., 2 * half :]], axis=-1)


def _attention(w, h, sizes, layer: int, mixer: str):
    b, s, _ = h.shape
    per_layer = sizes.get("n_heads_per_layer")
    heads = per_layer[layer - 1] if per_layer else sizes["n_heads"]
    kv_heads, hd = sizes.get("n_kv_heads") or sizes["n_heads"], sizes["head_dim"]
    group = heads // kv_heads
    inv_freq, scale = inv_frequencies(sizes, mixer)
    q = _rotate((h @ w["wq"]).reshape(b, s, heads, hd), inv_freq, scale)
    k = _rotate((h @ w["wk"]).reshape(b, s, kv_heads, hd), inv_freq, scale)
    v = (h @ w["wv"]).reshape(b, s, kv_heads, hd)
    # query head a reads key/value head a // group: [B, S, kv, group, D] against [B, S, kv, D]
    q = q.reshape(b, s, kv_heads, group, hd)
    block = QUERY_BLOCK if s % QUERY_BLOCK == 0 else s
    key_pos = jnp.arange(s)
    window = sizes["window"] if mixer == "window" else None

    def rows(start):  # a block of queries against every key
        q_blk = jax.lax.dynamic_slice_in_dim(q, start, block, axis=1)
        scores = jnp.einsum("bqngd,bknd->bngqk", q_blk, k) / jnp.sqrt(jnp.float32(hd))
        q_pos = (start + jnp.arange(block))[:, None]
        seen = key_pos[None, :] <= q_pos
        if window is not None:
            seen = seen & (key_pos[None, :] > q_pos - window)
        probs = jax.nn.softmax(jnp.where(seen[None, None, None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("bngqk,bknd->bqngd", probs, v)

    att = jax.lax.map(rows, jnp.arange(0, s, block))  # [blocks, B, block, kv, group, D]
    att = jnp.moveaxis(att, 0, 1).reshape(b, s, heads * hd)
    return att @ w["wo"]


def _experts(w, h, sizes):
    n_experts, top_k = sizes["n_experts"], sizes["top_k"]
    held = sizes.get("n_experts_held") or n_experts
    first = sizes.get("expert_share_index", 0) * held
    scores = jax.nn.sigmoid(h @ w["router"])
    kth = jnp.sort(scores, axis=-1)[..., n_experts - top_k]
    chosen = scores >= kth[..., None]  # the k largest (seeded random weights: no ties)
    weight = jnp.where(chosen, scores, 0.0)
    if sizes.get("router_renormalize", False):
        weight = weight / jnp.sum(weight, axis=-1, keepdims=True)
    weight = weight * sizes.get("routed_scaling_factor", 1.0)

    def add(y, e):  # the experts held here; the absent ones' part is left out
        mine = jax.lax.dynamic_index_in_dim(weight, first + e, axis=-1, keepdims=True)
        return y + mine * _swiglu(h, w["w_gate"][e], w["w_in"][e], w["w_out"][e]), None

    y, _ = jax.lax.scan(add, jnp.zeros_like(h), jnp.arange(held))
    if sizes.get("n_shared_experts"):
        y = y + _swiglu(h, w["shared_gate"], w["shared_in"], w["shared_out"])
    return y


def hidden(params: Dict[str, Any], tokens, sizes: Dict[str, Any]):
    """tokens [B, S] -> the final hidden state after ``final_norm`` [B, S, d]."""
    eps = sizes.get("norm_eps", 1e-6)
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    x = f32(params["embed"])[tokens]
    for layer, ((mixer, ff), w) in enumerate(zip(_kinds(sizes), layer_params(params, sizes)), 1):
        w = {k: f32(v) for k, v in w.items()}
        x = x + _attention(w, _rms_norm(x, w["ln1"], eps), sizes, layer, mixer)
        h = _rms_norm(x, w["ln2"], eps)
        x = x + (_experts(w, h, sizes) if ff == "experts" else _swiglu(h, w["w_gate"], w["w_in"], w["w_out"]))
    return _rms_norm(x, f32(params["final_norm"]), eps)


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
