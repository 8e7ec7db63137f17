"""Operations a training step needs, from shapes alone — the yardstick's
count, kept here so that no PR that claims a gain can change it.

Counted: the matrix multiplications of the forward pass and twice that for
the backward pass (6 operations per matmul parameter per token), and causal
attention's two score-sized matmuls at half the square. Not counted: the
``embed`` table (a gather, no matmul), norms, RoPE, softmax and the
optimizer (elementwise), and anything rematerialisation computes a second
time — model utilisation is about the operations the algorithm needs.
"""

from __future__ import annotations

from typing import Any, Dict

__all__ = ["matmul_params", "flops_per_token", "flops_per_step", "n_params"]


def _sizes(tc: Dict[str, Any]):
    d = tc["d_model"]
    qkv = tc["n_heads"] * tc["head_dim"]
    return d, qkv, tc["d_ff"], tc["n_layers"], tc["vocab_size"]


def n_params(tc: Dict[str, Any]) -> int:
    """Every parameter the program holds for this dense configuration
    (``models.transformer.init_params``: untied embed and out, two norm
    weights a layer, one final norm)."""
    d, qkv, f, layers, vocab = _sizes(tc)
    per_layer = 3 * d * qkv + qkv * d + 3 * d * f + 2 * d
    return layers * per_layer + 2 * vocab * d + d


def matmul_params(tc: Dict[str, Any]) -> int:
    """Parameters that sit in a matmul on the token path: q, k, v, o and
    the three SwiGLU matrices per layer, plus the output head. The
    ``embed`` table is a gather and is left out."""
    d, qkv, f, layers, vocab = _sizes(tc)
    return layers * (3 * d * qkv + qkv * d + 3 * d * f) + d * vocab


def flops_per_token(tc: Dict[str, Any], seq: int) -> float:
    """Forward + backward operations per trained token at sequence length
    ``seq``. Causal attention: q·kᵀ and p·v are 2·seq·qkv operations each
    per token over the full square; a causal mask needs half of it."""
    d, qkv, f, layers, vocab = _sizes(tc)
    attn_fwd = layers * 2 * (2 * seq * qkv) / 2
    return 6.0 * matmul_params(tc) + 3.0 * attn_fwd


def flops_per_step(tc: Dict[str, Any], batch: int, seq: int) -> float:
    return flops_per_token(tc, seq) * batch * seq
