"""Plain reference for the decoder of SmallThinker (``model_name``
``smallthinker_21b_instruct``), as the benchmark's ``smallthinker-*``
configurations run it: forward pass and training loss in straightforward
float32 ``jax.numpy``, written from the layer equations of ISSUE 60 (after the
published ``config.json`` and the catalog row's ``described_as``), independent
of ``torchft_tpu.models`` and ``torchft_tpu.ops``. No flash kernel, no band
that skips tiles, no sort, no grouped matmul, no remat: masks are explicit
comparisons of positions, an expert is applied to every token and masked.

A layer, layers counted from 1 as the program counts them (the source's
``rope_layout`` / ``sliding_window_layout`` count from 0), x ``[S, d]``:

* ``h1 = RMSNorm(x; ln1)`` (eps ``norm_eps``, a plain weight).
* **Router, on h1, BEFORE the attention** (``router_input`` "layer"):
  ``z = h1·W_r`` over all E experts; ``p = softmax(z)``; the k largest chosen;
  ``w_e = p_e / Σ_{j chosen} p_j`` (``router_renormalize``) — the softmax over
  all E renormalised over the chosen k, which is ``exp(z_e) / Σ_chosen exp(z_j)``.
* **Attention on h1**: ``q = h1·W_q`` → ``n_heads x D``, ``k = h1·W_k``,
  ``v = h1·W_v`` → ``n_kv_heads x D``; no bias, no norm on q or k; query head a
  reads key/value head ``a // (n_heads / n_kv_heads)``. A **window** layer
  (``window_layers``: ``rope_layout`` 1 and ``sliding_window_layout`` 1) rotates
  q and k over the whole head, lane i with lane i + D/2 at
  ``rope_theta^(-2i/D)``, and position i sees the keys ``i - window < j <= i``;
  a **nope** layer (``nope_layers``: 0 and 0) rotates NOTHING and sees every
  ``j <= i``. Scores ``q·k / √D``, softmax over the keys seen, ``·v``, ``·W_o``;
  ``x' = x + that``. Computed in blocks of ``QUERY_BLOCK`` queries against
  every key so that one sequence of 16 384 under 28 heads fits a chip beside
  the training state (a block's scores: 28 x 256 x 16 384 float32 = 0.47 GB):
  a block's scores are the same numbers as the full matrix's rows.
* ``h2 = RMSNorm(x'; ln2)``; ``y = Σ_{e chosen AND held} w_e · W_down,e(
  relu(h2·W_gate,e) ⊙ h2·W_up,e)`` (``expert_activation`` "relu": ReGLU); the
  layer's output is ``x' + y``. No shared expert, no dense layer.
* loss: ``RMSNorm(x; final_norm)·W_out`` (a separate output table) over the
  vocabulary rows held, mean next-token cross entropy; no balance term.

**Departures from the published model, all stated in the configuration file.**
(1) *The share*: of the E experts the router chooses among this chip holds
``n_experts_held`` (the block ``expert_share_index``); what the absent experts
would add is left out, here as in the program; the weights are still
renormalised over all k chosen. (2) That the router reads the NORMED layer
input ``h1`` (and not ``x``) is an inference from the source's figure, which
puts the router beside the attention behind the layer's first norm. (3) The
source's inference-only parts — the activation-sparsity predictor on the head
and the "secondary" experts — have no key in the published config and are not
built. (4) All 52 published layers are expert layers: no key names a dense one.

The other values of ``router_input`` ("ffn": the gate reads ``h2``),
``expert_activation`` ("silu"), a layer in neither list (global AND rotated) and
``router_renormalize`` false are what the program's other settings mean; the
chip check (``check_smallthinker.py``) and the CPU tests use them as the
reference "with one thing slipped" (and empty :data:`UNROTATED` to rotate the
layers without positions over the same parameters).

``sizes`` is a configuration's ``program.transformer_config``. Parameter names
and shapes are the interface with the program (``init_params``): ``embed``,
``out``, ``final_norm``, and the layers grouped by kind ``"<mixer>.experts"``
(mixers ``nope``, ``window`` and ``full``): ``lead[kind][name] [n, ...]`` for
the leading layers, ``periods[kind][name] [repeats, n, ...]`` for those the
program scans, ``layers[name] [1, L, ...]`` for a stack of one kind.

On a TPU a float32 matmul runs in lower precision unless
``jax.default_matmul_precision("highest")`` is set; :func:`loss` sets it.
"""

from __future__ import annotations

from typing import Any, Dict, List

import jax
import jax.numpy as jnp

__all__ = ["forward", "hidden", "layer", "loss", "per_sequence_loss", "layer_params", "gate"]

QUERY_BLOCK = 256
UNROTATED = ("nope",)  # the mixers whose q and k go to the scores as they are projected
ACTIVATIONS = {"relu": jax.nn.relu, "silu": jax.nn.silu}


def _rms_norm(x, weight, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight


def _mixers(sizes) -> List[str]:
    window, nope = sizes.get("window_layers", ()), sizes.get("nope_layers", ())
    return ["window" if i in window else "nope" if i in nope else "full" for i in range(1, sizes["n_layers"] + 1)]


def layer_params(params, sizes) -> List[Dict[str, Any]]:
    """Each layer's own parameters, in layer order, from the program's tree:
    how many leading layers and how long a period is read off the tree's own
    leading axes, the order of kinds inside them off ``sizes``."""
    kinds = [m + ".experts" for m in _mixers(sizes)]
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


def _rotate(x, theta):
    """x [B, S, H, D]: lane i with lane i + D/2 at ``theta^(-2i/D)``, the whole head."""
    d = x.shape[-1]
    inv_freq = jnp.asarray([theta ** (-2.0 * i / d) for i in range(d // 2)], jnp.float32)
    angle = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angle)[None, :, None, :], jnp.sin(angle)[None, :, None, :]
    a, b = x[..., : d // 2], x[..., d // 2 :]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _attention(w, h, sizes, mixer: str):
    b, s, _ = h.shape
    heads, hd = sizes["n_heads"], sizes["head_dim"]
    kv_heads = sizes.get("n_kv_heads") or heads
    group = heads // kv_heads
    q = (h @ w["wq"]).reshape(b, s, heads, hd)
    k = (h @ w["wk"]).reshape(b, s, kv_heads, hd)
    v = (h @ w["wv"]).reshape(b, s, kv_heads, hd)
    if mixer not in UNROTATED:  # a layer without positions takes q and k as they are projected
        q, k = _rotate(q, sizes["rope_theta"]), _rotate(k, sizes["rope_theta"])
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


def gate(w, h, sizes):
    """[..., E]: the weight of every expert for every token, zero where the
    expert is not among the token's k — the router on ``h``."""
    n_experts, top_k = sizes["n_experts"], sizes["top_k"]
    probs = jax.nn.softmax(h @ w["router"], axis=-1)
    kth = jnp.sort(probs, axis=-1)[..., n_experts - top_k]
    weight = jnp.where(probs >= kth[..., None], probs, 0.0)  # the k largest (seeded random weights: no ties)
    if sizes.get("router_renormalize", False):
        weight = weight / jnp.sum(weight, axis=-1, keepdims=True)
    return weight


def _experts(w, h, weight, sizes):
    held = sizes.get("n_experts_held") or sizes["n_experts"]
    first = sizes.get("expert_share_index", 0) * held
    act = ACTIVATIONS[sizes.get("expert_activation", "silu")]

    def add(y, e):  # the experts held here; the absent ones' part is left out
        mine = jax.lax.dynamic_index_in_dim(weight, first + e, axis=-1, keepdims=True)
        return y + mine * ((act(h @ w["w_gate"][e]) * (h @ w["w_in"][e])) @ w["w_out"][e]), None

    return jax.lax.scan(add, jnp.zeros_like(h), jnp.arange(held))[0]


def layer(w, x, sizes, mixer: str):
    """One layer on ``x`` [B, S, d] with its own float32 parameters ``w``."""
    eps = sizes.get("norm_eps", 1e-6)
    h1 = _rms_norm(x, w["ln1"], eps)
    ahead = sizes.get("router_input", "ffn") == "layer"
    weight = gate(w, h1, sizes) if ahead else None  # ahead of the attention, on the layer's normed input
    x = x + _attention(w, h1, sizes, mixer)
    h2 = _rms_norm(x, w["ln2"], eps)
    return x + _experts(w, h2, weight if ahead else gate(w, h2, sizes), sizes)


def hidden(params: Dict[str, Any], tokens, sizes: Dict[str, Any]):
    """tokens [B, S] -> the final hidden state after ``final_norm`` [B, S, d]."""
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    x = f32(params["embed"])[tokens]
    for mixer, w in zip(_mixers(sizes), layer_params(params, sizes)):
        x = layer({k: f32(v) for k, v in w.items()}, x, sizes, mixer)
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
