"""Plain reference for the looped decoder of Ouro (``model_type`` ``ouro``,
"Scaling Latent Reasoning via Looped Language Models": LoopLM), as the
benchmark's ``ouro-*`` configurations run it: forward pass and FIRST-STAGE
training loss in straightforward float32 ``jax.numpy``, written from the layer
equations of ISSUE 58 (after the published ``config.json`` and the family's
description), independent of ``torchft_tpu.models`` and ``torchft_tpu.ops``. No
kernels, no remat, no scan over layers or loop steps, no chunked head.

::

    h⁰ = E[x]                                            E [V, d]
    for t = 1..T (T = ut_steps), the SAME L layers each time:
        u ← hᵗ⁻¹
        for l = 1..L:  a = u + N²ₗ(Attnₗ(N¹ₗ(u)))         N¹ = ln1, N² = post_ln1
                       u = a + N⁴ₗ(FFNₗ(N³ₗ(a)))          N³ = ln2, N⁴ = post_ln2
        hᵗ = N_f(u)        (the final norm's OUTPUT is what turn t + 1 takes in)
        zᵗ = hᵗ · W_out    (untied)        λₜ = σ(hᵗ · w_g + b_g),  w_g [d, 1]
    p₁ = λ₁,  pₜ = λₜ ∏_{j<t}(1 − λⱼ) for t < T,  p_T = ∏_{j<T}(1 − λⱼ)     (Σₜ pₜ = 1, a token at a time)
    loss = mean over supervised tokens of [ Σₜ pₜ · CE(zᵗ, next id) − β · H(p) ],  H(p) = −Σₜ pₜ log pₜ

* ``N(x; w) = x / sqrt(mean(x²) + eps) · w`` — RMSNorm with a weight, four a
  layer ("sandwich": each sublayer's input AND output) and the final one.
* ``Attn``: multi-head causal softmax attention, ``n_heads`` heads of
  ``head_dim``, no bias; q and k rotated over the WHOLE head, lane i with lane
  i + head_dim/2 at ``rope_theta^(-2i/head_dim)`` (the "half" pairing); scores
  ``q·k / √head_dim`` over the keys j <= i. In blocks of ``QUERY_BLOCK`` queries
  against every key, so that two sequences of 4 096 positions fit beside a
  training state: a block's scores are the same numbers as the full matrix's rows.
* ``FFN``: SwiGLU, ``(silu(x·W_gate) ⊙ (x·W_in))·W_out``.
* The head: one pass of ``W_out`` a loop step, one after another
  (``jax.lax.map`` over the exits, as over the sequences: the same numbers, one
  exit's [S, V] logits alive at a time).

**Departures from the published model, all stated in the configuration file
under ``assumed``** ("as the published modeling code does" where the catalog's
``config`` does not fix the item): the four norms a layer and the final norm
carried into the next turn; the gate's shape (one ``[d, 1]`` projection and a
bias, shared by the loop steps) and its float32; β (``exit_entropy_coef``)
0.1; the last step taking the remaining probability. Not here: the family's
SECOND training stage (the gate alone against a frozen model) and adaptive
early exit at inference (``early_exit_threshold``). Initial values are the
program's ``init_params``.

Parameter names and shapes are the interface with the program
(``init_params``): ``embed [V, D]``, ``out [D, V]``, ``final_norm [D]``,
``exit_gate`` {``w`` [D, 1], ``b`` [1]} and ``layers[name] [1, L, ...]`` for
``ln1 post_ln1 ln2 post_ln2 wq wk wv wo w_gate w_in w_out``.

On a TPU a float32 matmul runs in lower precision unless
``jax.default_matmul_precision("highest")`` is set; :func:`loss` sets it.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

__all__ = ["hidden_states", "exit_distribution", "forward", "loss", "per_sequence_loss"]

QUERY_BLOCK = 1024


def _rms_norm(x, weight, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight


def _rope_half(x, theta):
    """x [S, H, Dh]; lane i turns with lane i + Dh/2 by pos·theta^(-2i/Dh)."""
    s, _, dh = x.shape
    inv_freq = theta ** (-jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : dh // 2], x[..., dh // 2 :]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _attention(q, k, v):
    """q, k, v [S, H, Dh] -> [S, H·Dh]: causal softmax attention, a block of queries at a time."""
    s, heads, dh = q.shape
    keys = jnp.arange(s)
    out = []
    for start in range(0, s, QUERY_BLOCK):
        rows = slice(start, min(start + QUERY_BLOCK, s))
        scores = jnp.einsum("qhd,khd->hqk", q[rows], k) / jnp.sqrt(jnp.float32(dh))
        seen = keys[None, :] <= keys[rows, None]
        scores = jnp.where(seen[None], scores, -jnp.inf)
        out.append(jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v))
    return jnp.concatenate(out, axis=0).reshape(s, heads * dh)


def _layer(w, u, sizes):
    heads, dh = sizes["n_heads"], sizes["head_dim"]
    eps, theta = sizes.get("norm_eps", 1e-6), sizes.get("rope_theta", 10000.0)
    s = u.shape[0]
    h = _rms_norm(u, w["ln1"], eps)
    q = _rope_half((h @ w["wq"]).reshape(s, heads, dh), theta)
    k = _rope_half((h @ w["wk"]).reshape(s, heads, dh), theta)
    v = (h @ w["wv"]).reshape(s, heads, dh)
    a = u + _rms_norm(_attention(q, k, v) @ w["wo"], w["post_ln1"], eps)
    h = _rms_norm(a, w["ln2"], eps)
    ffn = (jax.nn.silu(h @ w["w_gate"]) * (h @ w["w_in"])) @ w["w_out"]
    return a + _rms_norm(ffn, w["post_ln2"], eps)


def hidden_states(params: Dict[str, Any], tokens, sizes: Dict[str, Any]):
    """ONE sequence, tokens [S] -> the loop steps' normed states [T, S, D], float32 throughout."""
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    eps = sizes.get("norm_eps", 1e-6)
    u = f32(params["embed"])[tokens]
    states = []
    for _ in range(sizes["ut_steps"]):
        for i in range(sizes["n_layers"]):  # the same leaves every turn
            u = _layer({name: f32(leaf[0, i]) for name, leaf in params["layers"].items()}, u, sizes)
        u = _rms_norm(u, f32(params["final_norm"]), eps)  # ... and the normed state goes on into the next turn
        states.append(u)
    return jnp.stack(states)


def exit_distribution(params: Dict[str, Any], states):
    """states [T, S, D] -> p [T, S]: the probability that a token leaves at each loop step."""
    w, b = jnp.asarray(params["exit_gate"]["w"], jnp.float32), jnp.asarray(params["exit_gate"]["b"], jnp.float32)
    lam = jax.nn.sigmoid((states @ w)[..., 0] + b)
    probs, left = [], jnp.ones_like(lam[0])
    for t in range(lam.shape[0] - 1):
        probs.append(lam[t] * left)
        left = left * (1.0 - lam[t])
    return jnp.stack(probs + [left])


def forward(params: Dict[str, Any], tokens, sizes: Dict[str, Any]):
    """tokens [B, S] -> the LAST loop step's logits [B, S, V]."""
    out = jnp.asarray(params["out"], jnp.float32)
    return jnp.stack([hidden_states(params, row, sizes)[-1] @ out for row in tokens])


def _sequence_loss(params, tokens, sizes):
    states = hidden_states(params, tokens, sizes)
    p = exit_distribution(params, states)[:, :-1]  # the last position has no target
    out = jnp.asarray(params["out"], jnp.float32)

    def exit_nll(h):
        logp = jax.nn.log_softmax(h[:-1] @ out, axis=-1)
        return -jnp.take_along_axis(logp, tokens[1:, None], axis=-1)[:, 0]

    nll = jax.lax.map(exit_nll, states)  # [T, S - 1]
    entropy = -jnp.sum(jnp.where(p > 0, p * jnp.log(jnp.where(p > 0, p, 1.0)), 0.0), axis=0)
    return jnp.mean(jnp.sum(p * nll, axis=0) - sizes.get("exit_entropy_coef", 0.0) * entropy)


def per_sequence_loss(params, tokens, sizes):
    """The training loss of each sequence, [B]: the mean over its supervised
    positions of the expected cross entropy over the exits less β times the
    exit distribution's entropy."""
    return jax.lax.map(lambda row: _sequence_loss(params, row, sizes), tokens)


def loss(params, tokens, sizes):
    """The training loss over all supervised positions (every sequence has as
    many), under ``highest`` matmul precision."""
    with jax.default_matmul_precision("highest"):
        return jnp.mean(per_sequence_loss(params, tokens, sizes))
