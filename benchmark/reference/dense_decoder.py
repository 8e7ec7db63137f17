"""Plain reference for the dense decoder the benchmark's OLMo-1B-width
configurations run: forward pass and next-token loss in straightforward
float32 ``jax.numpy``. No kernels, no remat, no chunking, no scan, no
sharding — written from the published description of the architecture
(pre-norm decoder blocks; multi-head causal attention with rotary position
embeddings on q and k, interleaved pairs; SwiGLU feed-forward; a final norm
and an output projection), independent of ``torchft_tpu.models`` and
``torchft_tpu.ops``.

Departures from OLMo-1B, the same ones the configuration file lists under
``assumed``: RMSNorm with a learned weight where OLMo-1B has a
non-parametric LayerNorm, and separate ``embed`` / ``out`` tables where
OLMo-1B ties them.

Parameter names and shapes are the interface with the program
(``init_params``): ``embed [V, D]``, ``out [D, V]``, ``final_norm [D]`` and
``layers[name] [1, L, ...]`` for ``ln1 ln2 wq wk wv wo w_gate w_in w_out``.

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


def forward(params: Dict[str, Any], tokens, sizes: Dict[str, Any]):
    """tokens [B, S] -> logits [B, S, V], float32 throughout."""
    heads, dh = sizes["n_heads"], sizes["head_dim"]
    eps, theta = sizes.get("norm_eps", 1e-6), sizes.get("rope_theta", 10000.0)
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    b, s = tokens.shape
    x = f32(params["embed"])[tokens]
    layers = params["layers"]
    causal = jnp.tril(jnp.ones((s, s), bool))
    for i in range(sizes["n_layers"]):
        w = {k: f32(v[0, i]) for k, v in layers.items()}
        h = _rms_norm(x, w["ln1"], eps)
        q = _rope((h @ w["wq"]).reshape(b, s, heads, dh), theta)
        k = _rope((h @ w["wk"]).reshape(b, s, heads, dh), theta)
        v = (h @ w["wv"]).reshape(b, s, heads, dh)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(jnp.float32(dh))
        scores = jnp.where(causal[None, None], scores, -jnp.inf)
        att = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v)
        x = x + att.reshape(b, s, heads * dh) @ w["wo"]
        h = _rms_norm(x, w["ln2"], eps)
        x = x + (jax.nn.silu(h @ w["w_gate"]) * (h @ w["w_in"])) @ w["w_out"]
    x = _rms_norm(x, f32(params["final_norm"]), eps)
    return x @ f32(params["out"])


def per_sequence_loss(params, tokens, sizes):
    """Mean next-token cross entropy of each sequence, [B]; the last
    position has no target."""
    logp = jax.nn.log_softmax(forward(params, tokens, sizes), axis=-1)
    nll = -jnp.take_along_axis(logp[:, :-1], tokens[:, 1:, None], axis=-1)[..., 0]
    return jnp.mean(nll, axis=-1)


def loss(params, tokens, sizes):
    """Mean next-token cross entropy over all supervised positions, under
    ``highest`` matmul precision."""
    with jax.default_matmul_precision("highest"):
        return jnp.mean(per_sequence_loss(params, tokens, sizes))
