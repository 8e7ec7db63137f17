"""Plain reference for OLMoE's decoder block (OLMoE-1B-7B: ``model_type``
``olmoe``), as the benchmark's ``olmoe-*`` configurations run it: forward
pass and training loss in straightforward float32 ``jax.numpy``. No sort, no
grouped matmul, no scan, no remat, no chunking, no sharding — written from
the layer equations (ISSUE 26 §1, after the published model class),
independent of ``torchft_tpu.models`` and ``torchft_tpu.ops``.

A layer, input ``x`` [T, d], E experts of width f, k experts a token:

* attention: ``h = RMSNorm(x; ln1)``; ``q = RMSNorm(h·Wq; q_norm)``,
  ``k = RMSNorm(h·Wk; k_norm)`` — each norm over the WHOLE projection
  (all heads together), learned weight, before the split into heads and
  before RoPE; ``v = h·Wv``; RoPE on q and k (interleaved pairs, as
  ``dense_decoder.py``); causal softmax attention scaled by head_dim^-½;
  ``x ← x + att·Wo``. No bias, no clipping.
* experts: ``h = RMSNorm(x; ln2)``; ``p = softmax(h·Wr)``; ``S(t)`` = the k
  largest ``p[t]``; ``y[t] = Σ_{e∈S(t)} p[t,e]·(silu(h[t]·Wg[e]) ⊙
  (h[t]·Wi[e]))·Wo[e]`` — the weights as they are, NOT divided by their sum
  (``norm_topk_prob`` false); every chosen expert counts, whatever its load
  (dropless); ``x ← x + y``. Here: a loop over the experts, each applied to
  every token and masked to the tokens that chose it.
* loss: next-token cross entropy plus ``router_aux_loss_coef · L_lb``,
  ``L_lb`` = the mean over layers of ``E·Σ_e f_e·P_e``; ``f_e`` = the share
  of tokens with ``e`` among their k (sums to k), ``P_e`` = the mean of
  ``p[·, e]``; 8 under a uniform router at k = 8. The router z-loss of
  OLMoE's training recipe is not part of the published model class and is
  left out (the configuration file's ``assumed`` says so).

``sizes`` (a configuration's ``program.transformer_config``): ``n_heads``,
``head_dim``, ``n_layers``, ``n_experts``, ``top_k``, and optionally
``router_aux_loss_coef`` (absent: 0), ``norm_eps``, ``rope_theta``,
``norm_topk_prob`` (absent: false; true exists so that a test can show the
check would see a renormalised router).

Parameter names and shapes are the interface with the program
(``init_params``): ``embed [V, D]``, ``out [D, V]``, ``final_norm [D]`` and
``layers[name] [1, L, ...]`` for ``ln1 ln2 wq wk wv wo q_norm k_norm router
[d, E] w_gate w_in [E, d, f] w_out [E, f, d]``.

On a TPU a float32 matmul runs in lower precision unless
``jax.default_matmul_precision("highest")`` is set; :func:`loss` sets it.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

__all__ = ["forward", "loss", "per_sequence_loss"]


def _rms_norm(x, weight, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight


def _rope(x, theta):
    """x [B, S, H, Dh]; rotates the pairs (x[2i], x[2i+1]) by pos·theta^(-2i/Dh)."""
    s, dh = x.shape[1], x.shape[3]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    rot = jnp.stack([even * cos - odd * sin, even * sin + odd * cos], axis=-1)
    return rot.reshape(x.shape)


def _forward(params: Dict[str, Any], tokens, sizes: Dict[str, Any]):
    """tokens [B, S] -> (logits [B, S, V], per layer the router's (p [B, S, E],
    chosen [B, S, E] bool: e is among the token's k))."""
    heads, dh = sizes["n_heads"], sizes["head_dim"]
    n_experts, top_k = sizes["n_experts"], sizes["top_k"]
    eps, theta = sizes.get("norm_eps", 1e-6), sizes.get("rope_theta", 10000.0)
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    b, s = tokens.shape
    x = f32(params["embed"])[tokens]
    layers = params["layers"]
    causal = jnp.tril(jnp.ones((s, s), bool))
    routers = []
    for i in range(sizes["n_layers"]):
        w = {k: f32(v[0, i]) for k, v in layers.items()}
        h = _rms_norm(x, w["ln1"], eps)
        q = _rms_norm(h @ w["wq"], w["q_norm"], eps).reshape(b, s, heads, dh)
        k = _rms_norm(h @ w["wk"], w["k_norm"], eps).reshape(b, s, heads, dh)
        v = (h @ w["wv"]).reshape(b, s, heads, dh)
        q, k = _rope(q, theta), _rope(k, theta)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(jnp.float32(dh))
        scores = jnp.where(causal[None, None], scores, -jnp.inf)
        att = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v)
        x = x + att.reshape(b, s, heads * dh) @ w["wo"]

        h = _rms_norm(x, w["ln2"], eps)
        p = jax.nn.softmax(h @ w["router"], axis=-1)
        kth = jnp.sort(p, axis=-1)[..., n_experts - top_k]
        chosen = p >= kth[..., None]  # the k largest (seeded random weights: no ties)
        weight = jnp.where(chosen, p, 0.0)
        if sizes.get("norm_topk_prob", False):
            weight = weight / jnp.sum(weight, axis=-1, keepdims=True)
        y = jnp.zeros_like(x)
        for e in range(n_experts):
            out = (jax.nn.silu(h @ w["w_gate"][e]) * (h @ w["w_in"][e])) @ w["w_out"][e]
            y = y + weight[..., e : e + 1] * out
        x = x + y
        routers.append((p, chosen))
    x = _rms_norm(x, f32(params["final_norm"]), eps)
    return x @ f32(params["out"]), routers


def _balance(routers, over):
    """The mean over layers of E·Σ_e f_e·P_e, with f_e (the share of tokens
    that have e among their k; sums to k) and P_e (the mean of p[·, e]) taken
    over the axes ``over`` of [B, S]."""
    terms = [
        p.shape[-1] * jnp.sum(jnp.mean(chosen.astype(jnp.float32), axis=over) * jnp.mean(p, axis=over), axis=-1)
        for p, chosen in routers
    ]
    return sum(terms) / len(terms)


def _nll(logits, tokens):
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp[:, :-1], tokens[:, 1:, None], axis=-1)[..., 0]


def forward(params: Dict[str, Any], tokens, sizes: Dict[str, Any]):
    """tokens [B, S] -> logits [B, S, V], float32 throughout."""
    return _forward(params, tokens, sizes)[0]


def per_sequence_loss(params, tokens, sizes):
    """The training loss of each sequence by itself, [B]: its mean next-token
    cross entropy (the last position has no target) plus
    ``router_aux_loss_coef`` times the balance term over ITS tokens — what
    the program's ``loss_fn`` gives for a batch of that one sequence."""
    logits, routers = _forward(params, tokens, sizes)
    coef = sizes.get("router_aux_loss_coef", 0.0)
    return jnp.mean(_nll(logits, tokens), axis=-1) + coef * _balance(routers, over=1)


def loss(params, tokens, sizes):
    """The training loss of one call, under ``highest`` matmul precision: the
    mean cross entropy over all supervised positions plus the coefficient
    times the balance term over ALL the call's tokens (f_e and P_e are
    shares of the batch, so this is not the mean of :func:`per_sequence_loss`)."""
    with jax.default_matmul_precision("highest"):
        logits, routers = _forward(params, tokens, sizes)
        coef = sizes.get("router_aux_loss_coef", 0.0)
        return jnp.mean(_nll(logits, tokens)) + coef * _balance(routers, over=(0, 1))
