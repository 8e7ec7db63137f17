"""Plain reference for the decoder of Kimi-Linear (``model_type``
``kimi_linear``), as the benchmark's ``kimi-linear-*`` configurations run it:
forward pass and training loss in straightforward float32 ``jax.numpy``,
written from the layer equations (ISSUE 35, after the published model
description), independent of ``torchft_tpu.models`` and ``torchft_tpu.ops``.
No chunked recurrence, no flash kernel, no sort, no grouped matmul, no remat.

Every layer is ``x = x + mix(RMSNorm(x; ln1)); x = x + ff(RMSNorm(x; ln2))``.
Layers are counted from 1; ``kda_layers`` and ``mla_layers`` say which mixer
a layer has, the first ``n_dense_layers`` layers have a dense SwiGLU, the
others experts.

* **KDA** (gated delta rule, per-channel decay), H heads of width D, computed
  as the recurrence itself, one position after another (``lax.scan`` over the
  sequence): ``q, k, v = silu(conv(h·W))`` with a causal depthwise convolution
  over the sequence (one filter a channel, the last tap at the current
  position); q and k L2-normalised per head (``x / sqrt(Σx² + 1e-6)``), q
  times D^-½; ``g_t = -exp(a_log_h) · softplus((h_t·W_fa)·W_fb + dt_bias)``
  per channel; ``β_t = sigmoid(h_t·W_β)`` per head; per head a state ``S``
  [D, D]: ``S ← diag(exp(g_t)) S``; ``S ← S + β_t k_t (v_t − Sᵀk_t)ᵀ``;
  ``o_t = Sᵀ q_t``; out ``(RMSNorm(o_t; o_norm) ⊙ sigmoid((h_t·W_ga)·W_gb))·W_o``
  (the norm over one head's D, one weight for all heads). No positions.
* **MLA without positions**: ``q = h·W_q`` → H x (nope + rope);
  ``[c | k_s] = h·W_kva`` → rank + rope; ``c ← RMSNorm(c; kv_norm)``;
  ``[k_n | v] = c·W_kvb`` → H x (nope + dv); key of head i = ``[k_n,i | k_s]``
  (``k_s`` shared by all heads and NOT rotated); scores ``q·k / √(nope+rope)``,
  causal softmax, ``·W_o``. Full scores, computed in blocks of
  ``QUERY_BLOCK`` queries against all keys so that two sequences of 8 192 fit
  a chip: a block's scores are the same numbers as the full matrix's rows.
* **Experts**: ``s = sigmoid(h·W_r)`` over all E; the k largest of ``s + b``
  (``b``: selection only); weights ``s_e / Σ_chosen s`` (with
  ``router_renormalize``) times ``routed_scaling_factor``;
  ``y = Σ_{e chosen AND held} w_e E_e(h) + E_shared(h)``, every ``E`` a SwiGLU.
  A dense loop over the experts held, each applied to every token and masked
  to the tokens that chose it.
* loss: next-token cross entropy; no balance term.

**Departures from the published model, all stated in the configuration
file.** (1) *The share*: of the E experts the router chooses among, this
chip holds ``n_experts_held`` (the block ``expert_share_index``); what the
absent experts would add to ``y`` is left out, here as in the program; the
weights are still renormalised over all k chosen. (2) The low-rank gates are
``linear_head_dim`` wide inside, none of the projections has a bias, the
convolution has none. (3) Initial values of ``a_log``, ``dt_bias``, the
selection bias: the program's ``init_params``. (4) The L2 norm's and the
renormalisation's epsilons (1e-6, 1e-20) are this file's and the program's.

``sizes`` is a configuration's ``program.transformer_config``. Parameter names
and shapes are the interface with the program (``init_params``): ``embed``,
``out``, ``final_norm``, and the layers grouped by kind ``"<mixer>.<ff>"``:
``lead[kind][name] [n, ...]`` for the leading layers, ``periods[kind][name]
[repeats, n, ...]`` for those the program scans over (a model of one kind:
``layers[name] [1, L, ...]``).

On a TPU a float32 matmul runs in lower precision unless
``jax.default_matmul_precision("highest")`` is set; :func:`loss` sets it.
"""

from __future__ import annotations

from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp

__all__ = ["forward", "loss", "per_sequence_loss", "layer_params"]

QUERY_BLOCK = 512


def _rms_norm(x, weight, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight


def _swiglu(h, gate, up, down):
    return (jax.nn.silu(h @ gate) * (h @ up)) @ down


def _kinds(sizes) -> List[Tuple[str, str]]:
    out = []
    for i in range(1, sizes["n_layers"] + 1):
        mixer = "kda" if i in sizes.get("kda_layers", ()) else "mla" if i in sizes.get("mla_layers", ()) else "full"
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


def _conv(x, w):
    """x [B, S, C], w [K, C]: y_t = Σ_i w[i] x_{t-K+1+i}, zeros before the sequence."""
    taps = w.shape[0]
    y = jnp.zeros_like(x)
    for i in range(taps):
        shift = taps - 1 - i
        y = y + w[i] * jnp.pad(x, ((0, 0), (shift, 0), (0, 0)))[:, : x.shape[1]]
    return y


def _kda(w, h, sizes, eps):
    b, s, _ = h.shape
    heads, hd = sizes["linear_n_heads"], sizes["linear_head_dim"]
    split = lambda x: x.reshape(b, s, heads, hd)
    q, k, v = (split(jax.nn.silu(_conv(h @ w[n], w[c]))) for n, c in (("wq", "conv_q"), ("wk", "conv_k"), ("wv", "conv_v")))
    unit = lambda x: x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)
    q, k = unit(q) * hd**-0.5, unit(k)
    g = -jnp.exp(w["a_log"])[:, None] * split(jax.nn.softplus((h @ w["w_fa"]) @ w["w_fb"] + w["dt_bias"]))
    beta = jax.nn.sigmoid(h @ w["w_beta"])  # [B, S, H]

    def position(S, xs):
        q_t, k_t, v_t, g_t, b_t = xs  # [B, H, D], beta [B, H]
        S = jnp.exp(g_t)[..., None] * S
        S = S + jnp.einsum("bhk,bhd->bhkd", k_t, b_t[..., None] * (v_t - jnp.einsum("bhkd,bhk->bhd", S, k_t)))
        return S, jnp.einsum("bhkd,bhk->bhd", S, q_t)

    over_positions = tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta))
    _, o = jax.lax.scan(position, jnp.zeros((b, heads, hd, hd), jnp.float32), over_positions)
    o = jnp.moveaxis(o, 0, 1)  # [B, S, H, D]
    gate = jax.nn.sigmoid(split((h @ w["w_ga"]) @ w["w_gb"]))
    return (_rms_norm(o, w["o_norm"], eps) * gate).reshape(b, s, heads * hd) @ w["wo"]


def _mla(w, h, sizes, eps):
    b, s, _ = h.shape
    heads, rank = sizes["n_heads"], sizes["kv_lora_rank"]
    nope, rope, dv = sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"], sizes["v_head_dim"]
    q = (h @ w["wq"]).reshape(b, s, heads, nope + rope)
    latent = h @ w["w_kva"]
    kv = (_rms_norm(latent[..., :rank], w["kv_norm"], eps) @ w["w_kvb"]).reshape(b, s, heads, nope + dv)
    shared = jnp.broadcast_to(latent[:, :, None, rank:], (b, s, heads, rope))
    k, v = jnp.concatenate([kv[..., :nope], shared], axis=-1), kv[..., nope:]
    block = QUERY_BLOCK if s % QUERY_BLOCK == 0 else s
    key_pos = jnp.arange(s)

    def rows(start):  # a block of queries against every key
        q_blk = jax.lax.dynamic_slice_in_dim(q, start, block, axis=1)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q_blk, k) / jnp.sqrt(jnp.float32(nope + rope))
        seen = key_pos[None, :] <= (start + jnp.arange(block))[:, None]
        probs = jax.nn.softmax(jnp.where(seen[None, None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", probs, v)

    att = jax.lax.map(rows, jnp.arange(0, s, block))  # [blocks, B, block, H, dv]
    att = jnp.moveaxis(att, 0, 1).reshape(b, s, heads * dv)
    return att @ w["wo"]


def _experts(w, h, sizes):
    n_experts, top_k = sizes["n_experts"], sizes["top_k"]
    held = sizes.get("n_experts_held") or n_experts
    first = sizes.get("expert_share_index", 0) * held
    scores = jax.nn.sigmoid(h @ w["router"])
    biased = scores + w["router_bias"]
    kth = jnp.sort(biased, axis=-1)[..., n_experts - top_k]
    chosen = biased >= kth[..., None]  # the k largest (seeded random weights: no ties)
    weight = jnp.where(chosen, scores, 0.0)
    if sizes.get("router_renormalize", False):
        weight = weight / (jnp.sum(weight, axis=-1, keepdims=True) + 1e-20)
    weight = weight * sizes.get("routed_scaling_factor", 1.0)
    y = jnp.zeros_like(h)
    for e in range(held):  # the experts held here; the absent ones' part is left out
        y = y + weight[..., first + e, None] * _swiglu(h, w["w_gate"][e], w["w_in"][e], w["w_out"][e])
    if sizes.get("n_shared_experts"):
        y = y + _swiglu(h, w["shared_gate"], w["shared_in"], w["shared_out"])
    return y, chosen


def _forward(params: Dict[str, Any], tokens, sizes: Dict[str, Any]):
    """tokens [B, S] -> (logits [B, S, V], per expert layer chosen [B, S, E] bool)."""
    eps = sizes.get("norm_eps", 1e-6)
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    x = f32(params["embed"])[tokens]
    routers = []
    for (mixer, ff), w in zip(_kinds(sizes), layer_params(params, sizes)):
        w = {k: f32(v) for k, v in w.items()}
        h = _rms_norm(x, w["ln1"], eps)
        if mixer == "kda":
            x = x + _kda(w, h, sizes, eps)
        elif mixer == "mla":
            x = x + _mla(w, h, sizes, eps)
        else:
            raise NotImplementedError(f"this reference has no {mixer!r} mixer: dense_decoder.py does")
        h = _rms_norm(x, w["ln2"], eps)
        if ff == "experts":
            y, chosen = _experts(w, h, sizes)
            routers.append(chosen)
            x = x + y
        else:
            x = x + _swiglu(h, w["w_gate"], w["w_in"], w["w_out"])
    x = _rms_norm(x, f32(params["final_norm"]), eps)
    return x @ f32(params["out"]), routers


def _nll(logits, tokens):
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp[:, :-1], tokens[:, 1:, None], axis=-1)[..., 0]


def forward(params: Dict[str, Any], tokens, sizes: Dict[str, Any]):
    """tokens [B, S] -> logits [B, S, V], float32 throughout."""
    return _forward(params, tokens, sizes)[0]


def per_sequence_loss(params, tokens, sizes):
    """The training loss of each sequence by itself, [B]: its mean next-token
    cross entropy (the last position has no target) — what the program's
    ``loss_fn`` gives for a batch of that one sequence."""
    return jnp.mean(_nll(_forward(params, tokens, sizes)[0], tokens), axis=-1)


def loss(params, tokens, sizes):
    """The training loss of one call, under ``highest`` matmul precision."""
    with jax.default_matmul_precision("highest"):
        return jnp.mean(_nll(_forward(params, tokens, sizes)[0], tokens))
