"""Plain reference for the decoder of Granite 4.0-H (``model_type``
``granitemoehybrid`` with no experts), as the benchmark's ``granite-*``
configurations run it: forward pass and training loss in straightforward
float32 ``jax.numpy``, written from the layer equations of ISSUE 69 (after the
published ``config.json``), independent of ``torchft_tpu.models`` and
``torchft_tpu.ops``. No kernel, NO CHUNKS: the state-space recurrence is
stepped one position after another under a ``lax.scan`` — that is what makes it
independent of the program's chunked form — the convolution is four shifted
adds, a mask is an explicit comparison of positions; the one ``jax.checkpoint``
is around a block of queries, whose scores a backward pass forms again.

Every layer is ``x' = x + r·Mix(RMSNorm(x; ln1))``, ``out = x' + r·SwiGLU(RMSNorm(x'; ln2))``
with ``r = residual_scale`` on BOTH branches, RMSNorm under a plain weight,
layers counted from 1 as the program counts them (the source counts from 0).

* **State-space mixer** (``ssd_layers``; Mamba-2, one group): ``[z | xBC | dt] =
  h·ssd_in`` (d -> H·P + (H·P + 2N) + H); ``xBC <- SiLU(conv(xBC) + conv_b)``
  with ``conv`` the causal depthwise convolution of ``conv_kernel`` taps a
  channel (zeros ahead of position 0, the last tap the position itself) over
  all H·P + 2N channels; split ``x`` [H, P], ``B`` [N], ``C`` [N] — one B and
  one C a position for ALL heads; ``Δ_t,h = softplus(dt_t,h + dt_bias_h)``,
  ``A_h = -exp(a_log_h)``; a head's state ``S ∈ R^{N x P}``:
  ``S_t = exp(Δ_t A_h) S_{t-1} + Δ_t B_t ⊗ x_t``, ``y_t = S_tᵀ C_t + d_skip_h x_t``;
  ``y <- RMSNorm(y ⊙ SiLU(z); y_norm)`` over ALL H·P channels (the gate goes
  in BEFORE the norm; one group); ``·ssd_out``. No positions.
* **Attention** (``nope_layers``): ``n_heads`` query heads over ``n_kv_heads``
  key/value heads of ``head_dim``, no bias, NOTHING rotated, scores
  ``q·k · attn_scale`` (not ``1/√D``), softmax over the keys j <= i, ``·wo``;
  in blocks of ``QUERY_BLOCK`` queries against every key so that 8 192
  positions fit a chip.
* **Ends**: ``h_0 = embed_scale · embed[ids]``; final RMSNorm;
  ``logits = (h · embedᵀ) / logits_scale`` over the SAME table
  (``tie_embeddings``; without it ``h · out``); mean next-token cross entropy.

**Departure from the published model, stated in the configuration file:** the
vocabulary rows held are a slice (``share``), here as in the program.

``sizes`` is a configuration's ``program.transformer_config``. Parameter names
and shapes are the interface with the program (``init_params``): ``embed``,
``final_norm`` (``out`` where the table is not tied) and the layers grouped by
kind ``"<mixer>.dense"`` (mixers ``ssd`` and ``nope``): ``lead[kind][name]
[n, ...]`` for the leading layers, ``periods[kind][name] [repeats, n, ...]``
for those the program scans, ``layers[name] [1, L, ...]`` for a model of one kind.

On a TPU a float32 matmul runs in lower precision unless
``jax.default_matmul_precision("highest")`` is set; :func:`loss` sets it.
"""

from __future__ import annotations

from typing import Any, Dict, List

import jax
import jax.numpy as jnp

__all__ = ["forward", "hidden", "loss", "per_sequence_loss", "layer_params"]

QUERY_BLOCK = 256


def _rms_norm(x, weight, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight


def _swiglu(h, gate, up, down):
    return (jax.nn.silu(h @ gate) * (h @ up)) @ down


def _kinds(sizes) -> List[str]:
    return [
        ("ssd" if i in sizes.get("ssd_layers", ()) else "nope" if i in sizes.get("nope_layers", ()) else "full") + ".dense"
        for i in range(1, sizes["n_layers"] + 1)
    ]


def layer_params(params, sizes) -> List[Dict[str, Any]]:
    """Each layer's own parameters, in layer order, from the program's tree:
    how many leading layers and how long a period is read off the tree's own
    leading axes, the order of kinds inside them off ``sizes``."""
    kinds = _kinds(sizes)
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


def _conv(w, u, taps: int):
    """The causal depthwise convolution with its bias: the last tap is the position itself."""
    return sum(w["conv_w"][i] * _shift(u, taps - 1 - i) for i in range(taps)) + w["conv_b"]


def _split(xbc, inner: int, n_state: int):
    """(x, B, C) of the convolved channels, in this order along the features."""
    return xbc[..., :inner], xbc[..., inner : inner + n_state], xbc[..., inner + n_state :]


def _log_decay(delta, a):
    """What a head's state is multiplied by at a position, as a logarithm: Δ·A."""
    return delta * a


def _skip(w, x):
    """``d_skip_h · x`` [B, S, H, P]: the input passed around the state."""
    return w["d_skip"][:, None] * x


def _gated_norm(y, z, weight, eps, heads: int):
    """RMSNorm over ALL the channels of ``y ⊙ SiLU(z)`` [B, S, H·P]: the gate first, one group."""
    return _rms_norm(y * jax.nn.silu(z), weight, eps)


def _positions(S, xs):
    """The recurrence, one position after another. S [B, H, N, P]; xs per position:
    C_t, B_t [B, N], Δ_t x_t [B, H, P], the log-decay [B, H]."""

    def position(S, x):
        c_t, b_t, v_t, g_t = x
        S = jnp.exp(g_t)[..., None, None] * S + b_t[:, None, :, None] * v_t[:, :, None, :]
        return S, jnp.einsum("bhnp,bn->bhp", S, c_t)

    return jax.lax.scan(position, S, xs)


def _state_space(w, h, sizes):
    b, s, _ = h.shape
    heads, hd, n_state = sizes["ssd_n_heads"], sizes["ssd_head_dim"], sizes["ssd_state_dim"]
    inner = heads * hd
    mixed = inner + 2 * n_state
    zxd = h @ w["ssd_in"]
    z, xbc, dt = zxd[..., :inner], zxd[..., inner : inner + mixed], zxd[..., inner + mixed :]
    x, b_in, c_out = _split(jax.nn.silu(_conv(w, xbc, sizes.get("conv_kernel", 4))), inner, n_state)
    x = x.reshape(b, s, heads, hd)
    delta = jax.nn.softplus(dt + w["dt_bias"])  # [B, S, H]
    g = _log_decay(delta, -jnp.exp(w["a_log"]))
    xs = tuple(jnp.moveaxis(a, 1, 0) for a in (c_out, b_in, delta[..., None] * x, g))
    _, y = _positions(jnp.zeros((b, heads, n_state, hd), jnp.float32), xs)
    y = jnp.moveaxis(y, 0, 1) + _skip(w, x)
    return _gated_norm(y.reshape(b, s, inner), z, w["y_norm"], sizes.get("norm_eps", 1e-6), heads) @ w["ssd_out"]


def _placed(q, k, sizes):
    """q and k as the scores read them: as they were projected — nothing is rotated."""
    return q, k


def _attention(w, h, sizes):
    b, s, _ = h.shape
    heads, hd = sizes["n_heads"], sizes["head_dim"]
    kv_heads = sizes.get("n_kv_heads") or heads
    group = heads // kv_heads
    scale = sizes.get("attn_scale") or hd**-0.5
    q = (h @ w["wq"]).reshape(b, s, heads, hd)
    k = (h @ w["wk"]).reshape(b, s, kv_heads, hd)
    v = (h @ w["wv"]).reshape(b, s, kv_heads, hd)
    q, k = _placed(q, k, sizes)
    # query head a reads key/value head a // group: [B, S, kv, group, D] against [B, S, kv, D]
    q = q.reshape(b, s, kv_heads, group, hd)
    block = QUERY_BLOCK if s % QUERY_BLOCK == 0 else s
    key_pos = jnp.arange(s)

    def rows(start):  # a block of queries against every key
        q_blk = jax.lax.dynamic_slice_in_dim(q, start, block, axis=1)
        scores = jnp.einsum("bqngd,bknd->bngqk", q_blk, k) * scale
        seen = key_pos[None, :] <= (start + jnp.arange(block))[:, None]
        probs = jax.nn.softmax(jnp.where(seen[None, None, None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("bngqk,bknd->bqngd", probs, v)

    # a block's scores are formed again in a backward pass: the same numbers, a block's memory at a time
    att = jax.lax.map(jax.checkpoint(rows), jnp.arange(0, s, block))  # [blocks, B, block, kv, group, D]
    att = jnp.moveaxis(att, 0, 1).reshape(b, s, heads * hd)
    return att @ w["wo"]


def _layer(w, x, sizes, kind: str):
    eps, r = sizes.get("norm_eps", 1e-6), sizes.get("residual_scale", 1.0)
    h = _rms_norm(x, w["ln1"], eps)
    x = x + r * (_state_space(w, h, sizes) if kind.startswith("ssd") else _attention(w, h, sizes))
    h = _rms_norm(x, w["ln2"], eps)
    return x + r * _swiglu(h, w["w_gate"], w["w_in"], w["w_out"])


def hidden(params: Dict[str, Any], tokens, sizes: Dict[str, Any]):
    """tokens [B, S] -> the final hidden state after ``final_norm`` [B, S, d]."""
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    x = sizes.get("embed_scale", 1.0) * f32(params["embed"])[tokens]
    for kind, w in zip(_kinds(sizes), layer_params(params, sizes)):
        x = _layer({k: f32(v) for k, v in w.items()}, x, sizes, kind)
    return _rms_norm(x, f32(params["final_norm"]), sizes.get("norm_eps", 1e-6))


def forward(params: Dict[str, Any], tokens, sizes: Dict[str, Any]):
    """tokens [B, S] -> logits [B, S, V], float32 throughout."""
    table = jnp.asarray(params["embed"], jnp.float32).T if sizes.get("tie_embeddings") else jnp.asarray(params["out"], jnp.float32)
    return hidden(params, tokens, sizes) @ table / sizes.get("logits_scale", 1.0)


def _nll(logits, tokens):
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp[:, :-1], tokens[:, 1:, None], axis=-1)[..., 0]


def per_sequence_loss(params, tokens, sizes):
    """The training loss of each sequence by itself, [B]: its mean next-token
    cross entropy (the last position has no target) — what the program's
    ``loss_fn`` gives for a batch of that one sequence. One sequence at a time."""
    one = lambda seq: jnp.mean(_nll(forward(params, seq[None], sizes), seq[None]))
    return jax.lax.map(one, tokens)


def loss(params, tokens, sizes):
    """The training loss of one call, under ``highest`` matmul precision."""
    with jax.default_matmul_precision("highest"):
        return jnp.mean(_nll(forward(params, tokens, sizes), tokens))
