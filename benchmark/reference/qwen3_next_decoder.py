"""Plain reference for the decoder of Qwen3-Next (``model_type``
``qwen3_next``), as the benchmark's ``qwen3-next-*`` configurations run it:
forward pass and training loss in straightforward float32 ``jax.numpy``,
written from the layer equations of ISSUE 54 (after the published
``config.json`` and model description), independent of ``torchft_tpu.models``
and ``torchft_tpu.ops``. No chunked recurrence, no flash kernel, no sort, no
grouped matmul: the delta rule runs one position after another and masks are
explicit comparisons of positions. The two ``jax.checkpoint``s are around a block
of queries and a block of positions — the same numbers, and a block's memory
where a gradient is taken through 8 192 positions (``check_qwen3_next.py``).

``x̂ = RMSNorm_zc(h; w) = h / sqrt(mean(h²) + eps) · (1 + w)`` — the layers'
two norms, the final norm and the per-head q/k norms, ``w`` zero at init.
Every layer is ``x = x + mix(x̂(x; ln1)); x = x + moe(x̂(x; ln2))``, layers
counted from 1 as the program counts them (the source counts from 0):
``gdn_layers`` are Gated DeltaNet, the others gated softmax attention
(``full_attention_interval`` 4: three to one); every layer has experts
(``decoder_sparse_step`` 1, ``mlp_only_layers`` []).

* **Gated DeltaNet**, ``Hv = linear_n_heads`` value heads over ``Hk =
  linear_n_key_heads`` key heads, all ``D = linear_head_dim`` wide:
  ``q, k, v = SiLU(conv(h·W_q | h·W_k | h·W_v))`` with a causal depthwise
  convolution over the sequence (``conv_kernel`` taps a channel, the last at
  the position itself, zeros ahead of position 0, no bias); q, k L2-normalised
  per head (``x / sqrt(Σx² + 1e-6)``), q times ``D^-½``; value head j reads
  query/key head ``j // (Hv / Hk)``. ``[b | a] = h·W_ba`` (Hv | Hv);
  ``β = sigmoid(b)``; ``g = −exp(a_log) ⊙ softplus(a + dt_bias)`` — ONE
  number a value head a position. Per value head a state ``S`` [D, D]:
  ``S ← e^{g_t} S``; ``S ← S + β_t k_t (v_t − Sᵀk_t)ᵀ``; ``o_t = Sᵀ q_t``.
  Out ``(RMSNorm(o_t; o_norm) ⊙ SiLU(h·W_z))·W_o``: the norm over one head's D
  under a PLAIN weight (ones at init), one weight for all heads. No positions.
* **Gated softmax attention**, ``H = n_heads`` query heads over ``n_kv_heads``
  key/value heads of ``head_dim``: ``h·W_q`` is ``H x 2·head_dim``, a head's
  lanes ``[q | gate]``; ``k, v = h·W_k, h·W_v``. q and k through ``RMSNorm_zc``
  over a head's lanes (one weight for the query heads, one for the key heads).
  The first ``r = rotary_dim`` lanes rotated, lane i with lane i + r/2 at
  ``rope_theta^(-2i/r)``, the rest passing; no scaling. Scores ``q·k /
  √head_dim``, softmax over the keys j <= i, query head a reads key/value head
  ``a // (H / n_kv_heads)``; ``(o ⊙ sigmoid(gate))·W_o``. In blocks of
  ``QUERY_BLOCK`` queries against every key, so that 8 192 positions fit a
  chip: a block's scores are the same numbers as the full matrix's rows.
* **Experts**: ``p = softmax(h·W_r)`` over all E; the k largest chosen;
  ``w_e = p_e / Σ_chosen p`` (``router_renormalize``); ``y = Σ_{e chosen AND
  held} w_e SwiGLU_e(h) + sigmoid(h·w_s) · SwiGLU_shared(h)``, ``w_s`` [d, 1]
  (``shared_scale``). No selection bias, no scaling factor, no balance term.
* loss: ``x̂(x; final_norm)·W_out`` over the vocabulary rows held, mean
  next-token cross entropy.

**Departures from the published model, all stated in the configuration
file.** (1) *The share*: of the E experts the router chooses among this chip
holds ``n_experts_held`` (the block ``expert_share_index``); what the absent
experts would add is left out, here as in the program; the weights are still
renormalised over all k chosen. (2) The published class stores ``W_q | W_k |
W_v | W_z`` as one matrix (and ``W_ba`` as one) with the heads' parts
interleaved; here each is a matrix of its own, q and k heads in order — the
same function of differently ordered columns. (3) No multi-token-prediction
module: the published ``config.json`` has no key for one. (4) Initial values
(``a_log`` = log U(0, 16], ``dt_bias`` 1, norm weights) are the program's
``init_params``; the 1e-6 of the L2 norm is this file's and the program's.

``sizes`` is a configuration's ``program.transformer_config``. Parameter names
and shapes are the interface with the program (``init_params``): ``embed``,
``out``, ``final_norm``, and the layers grouped by kind ``"<mixer>.<ff>"``
(mixers ``gdn`` and ``full``): ``lead[kind][name] [n, ...]`` for the leading
layers, ``periods[kind][name] [repeats, n, ...]`` for those the program scans.

On a TPU a float32 matmul runs in lower precision unless
``jax.default_matmul_precision("highest")`` is set; :func:`loss` sets it.
"""

from __future__ import annotations

from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp

__all__ = ["forward", "hidden", "loss", "per_sequence_loss", "layer_params"]

QUERY_BLOCK = 256
POSITION_BLOCK = 128


def _rms_norm_zc(x, weight, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * (1.0 + weight)


def _rms_norm(x, weight, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight


def _swiglu(h, gate, up, down):
    return (jax.nn.silu(h @ gate) * (h @ up)) @ down


def _kinds(sizes) -> List[Tuple[str, str]]:
    ff = "experts" if sizes.get("n_experts") else "dense"  # dense: a SwiGLU of d_ff, what a test's model of one kind has
    return [("gdn" if i in sizes.get("gdn_layers", ()) else "full", ff) for i in range(1, sizes["n_layers"] + 1)]


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


def _key_head_of(value_heads: int, key_heads: int):
    """The key head each value head reads: j // (Hv / Hk)."""
    return jnp.arange(value_heads) // (value_heads // key_heads)


def _decay(w, a):
    """The log-decay [B, S, Hv] from the decay's input ``a``: one number a value head a position."""
    return -jnp.exp(w["a_log"]) * jax.nn.softplus(a + w["dt_bias"])


def _positions(S, xs):
    """One block of positions, one after another: S [B, Hv, D, D], xs (q, k, v [P, B, Hv, D]; g, beta [P, B, Hv])."""

    def position(S, x):
        q_t, k_t, v_t, g_t, b_t = x
        S = jnp.exp(g_t)[..., None, None] * S
        S = S + jnp.einsum("bhk,bhd->bhkd", k_t, b_t[..., None] * (v_t - jnp.einsum("bhkd,bhk->bhd", S, k_t)))
        return S, jnp.einsum("bhkd,bhk->bhd", S, q_t)

    return jax.lax.scan(position, S, xs)


def _delta_rule(q, k, v, g, beta):
    """q, k, v [B, S, Hv, D], g and beta [B, S, Hv] -> o [B, S, Hv, D]: the rule one position after another, the
    positions taken in blocks of ``POSITION_BLOCK`` (the same steps in the same order; a block is computed again in
    the backward, so that a gradient through 8 192 positions keeps a state a block and not a state a position)."""
    b, s, heads, hd = v.shape
    blk = POSITION_BLOCK if s % POSITION_BLOCK == 0 else s
    blocks = tuple(jnp.moveaxis(x, 1, 0).reshape((s // blk, blk) + x.shape[:1] + x.shape[2:]) for x in (q, k, v, g, beta))
    _, o = jax.lax.scan(jax.checkpoint(lambda S, xs: _positions(S, xs)), jnp.zeros((b, heads, hd, hd), jnp.float32), blocks)
    return jnp.moveaxis(o.reshape((s,) + o.shape[2:]), 0, 1)


def _gdn(w, h, sizes, eps):
    b, s, _ = h.shape
    heads, hd = sizes["linear_n_heads"], sizes["linear_head_dim"]
    key_heads = sizes.get("linear_n_key_heads") or heads
    act = lambda n, c, nh: jax.nn.silu(_conv(h @ w[n], w[c])).reshape(b, s, nh, hd)
    q, k, v = act("wq", "conv_q", key_heads), act("wk", "conv_k", key_heads), act("wv", "conv_v", heads)
    unit = lambda x: x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)
    reads = _key_head_of(heads, key_heads)
    q, k = (unit(q) * hd**-0.5)[:, :, reads], unit(k)[:, :, reads]
    ba = h @ w["w_ba"]
    beta, g = jax.nn.sigmoid(ba[..., :heads]), _decay(w, ba[..., heads:])
    o = _delta_rule(q, k, v, g, beta)
    gate = jax.nn.silu(h @ w["w_z"]).reshape(b, s, heads, hd)
    return (_rms_norm(o, w["o_norm"], eps) * gate).reshape(b, s, heads * hd) @ w["wo"]


def _rotate(x, sizes):
    """x [B, S, H, D]: lane i with lane i + r/2 inside the first r = rotary_dim lanes; the rest passes."""
    r = sizes.get("rotary_dim") or sizes["head_dim"]
    half = r // 2
    inv_freq = jnp.asarray([sizes["rope_theta"] ** (-2.0 * i / r) for i in range(half)], jnp.float32)
    angle = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angle)[None, :, None, :], jnp.sin(angle)[None, :, None, :]
    a, b = x[..., :half], x[..., half:r]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, x[..., r:]], axis=-1)


def _gated(o, gate):
    """The core's output [B, S, H·D] through its gate, lane by lane."""
    return o * jax.nn.sigmoid(gate)


def _attention(w, h, sizes, eps):
    b, s, _ = h.shape
    heads, hd = sizes["n_heads"], sizes["head_dim"]
    kv_heads = sizes.get("n_kv_heads") or heads
    group = heads // kv_heads
    both = (h @ w["wq"]).reshape(b, s, heads, 2 * hd)  # a head's lanes: [q | gate]
    q, gate = both[..., :hd], both[..., hd:].reshape(b, s, heads * hd)
    k = (h @ w["wk"]).reshape(b, s, kv_heads, hd)
    v = (h @ w["wv"]).reshape(b, s, kv_heads, hd)
    q = _rotate(_rms_norm_zc(q, w["q_norm"], eps), sizes)
    k = _rotate(_rms_norm_zc(k, w["k_norm"], eps), sizes)
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

    att = jax.lax.map(jax.checkpoint(rows), jnp.arange(0, s, block))  # [blocks, B, block, kv, group, D]
    att = jnp.moveaxis(att, 0, 1).reshape(b, s, heads * hd)
    return _gated(att, gate) @ w["wo"]


def _weigh(probs, chosen):
    """The chosen experts' weights [.., E] (zero elsewhere): their probabilities renormalised over the chosen."""
    weight = jnp.where(chosen, probs, 0.0)
    return weight / jnp.sum(weight, axis=-1, keepdims=True)


def _shared_gate(w, h):
    return jax.nn.sigmoid(h @ w["shared_scale"])


def _router_probs(w, h):
    return jax.nn.softmax(h @ w["router"], axis=-1)


def _experts(w, h, sizes):
    n_experts, top_k = sizes["n_experts"], sizes["top_k"]
    held = sizes.get("n_experts_held") or n_experts
    first = sizes.get("expert_share_index", 0) * held
    probs = _router_probs(w, h)
    kth = jnp.sort(probs, axis=-1)[..., n_experts - top_k]
    chosen = probs >= kth[..., None]  # the k largest (seeded random weights: no ties)
    weight = _weigh(probs, chosen) if sizes.get("router_renormalize", False) else jnp.where(chosen, probs, 0.0)

    def add(y, e):  # the experts held here; the absent ones' part is left out
        mine = jax.lax.dynamic_index_in_dim(weight, first + e, axis=-1, keepdims=True)
        return y + mine * _swiglu(h, w["w_gate"][e], w["w_in"][e], w["w_out"][e]), None

    y, _ = jax.lax.scan(add, jnp.zeros_like(h), jnp.arange(held))
    if sizes.get("n_shared_experts"):
        shared = _swiglu(h, w["shared_gate"], w["shared_in"], w["shared_out"])
        y = y + (_shared_gate(w, h) * shared if sizes.get("shared_expert_gate") else shared)
    return y


def _layer(w, x, sizes, kind):
    eps = sizes.get("norm_eps", 1e-6)
    h = _rms_norm_zc(x, w["ln1"], eps)
    x = x + (_gdn(w, h, sizes, eps) if kind[0] == "gdn" else _attention(w, h, sizes, eps))
    h = _rms_norm_zc(x, w["ln2"], eps)
    return x + (_experts(w, h, sizes) if kind[1] == "experts" else _swiglu(h, w["w_gate"], w["w_in"], w["w_out"]))


def hidden(params: Dict[str, Any], tokens, sizes: Dict[str, Any]):
    """tokens [B, S] -> the final hidden state after ``final_norm`` [B, S, d]."""
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    x = f32(params["embed"])[tokens]
    for kind, w in zip(_kinds(sizes), layer_params(params, sizes)):
        x = _layer({k: f32(v) for k, v in w.items()}, x, sizes, kind)
    return _rms_norm_zc(x, f32(params["final_norm"]), sizes.get("norm_eps", 1e-6))


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
