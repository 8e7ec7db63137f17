"""The count of a decoder whose sequence mixer is, in most layers, a gated short
convolution — two gates and a few causal depthwise taps between two projections,
no score and no state — and in the others grouped-query softmax attention with
its heads normed one by one; leading dense layers, then experts held under a
share with no shared expert: ``program.opcount`` ``lfm2_moe``, with
``opcount.INTERFACE`` and, for the readers of the convolution's core,
:func:`core_flops_per_token` and :func:`core_bytes_per_step`.

What is read from ``program.transformer_config``: ``d_model``, ``n_layers``,
``conv_layers``, ``conv_kernel``, ``n_heads``, ``n_kv_heads``, ``head_dim``,
``qk_norm`` / ``qk_norm_per_head``, ``n_dense_layers``, ``d_ff``, ``moe_d_ff``,
``n_experts`` (the router's width), ``n_experts_held``, ``top_k``,
``router_gate`` / ``router_selection_bias``, ``vocab_size`` (the rows HELD) and
``dtype``.

Counted as ``opcount.py`` counts: 6 operations per matmul parameter on a
token's path (forward, and twice that backward), what the algorithm needs and
no more, recomputation, padding and masked tiles not counted, never clamped.

* **Gated convolution**, a layer: ``W_in`` (d x 3d) and ``W_out`` (d x d) are
  matmuls, ``6 · 4d²`` a token. The core between them — ``u = B ⊙ X``, K
  multiply-adds over the taps, ``C ⊙ ·`` — is elementwise: ``2K + 1``
  operations a channel a token forward (K multiplies, K - 1 adds, two gates),
  times three with the backward. Like every elementwise operation they stay
  out of ``attn`` (0.02 % of a layer's matmuls at K = 3); :func:`core_flops_per_token`
  holds them so that the core's roofline can say which bound is nearer: the bytes.
* **Attention**, a layer: the projections ``d x H D`` (q), twice ``d x G D``
  (k, v) and ``H D x d`` (o); the core is the two score-sized products over
  the ``(s + 1) / 2`` keys a query sees on average, ``2 · keys · 2D`` a head a
  token forward, times three. The per-head norms and the rotation are
  elementwise and not counted.
* **Experts**, a layer: the router (``d x E``, every token) and the routed
  experts at the share of a token's k that is held here: ``k · held / E``
  passes a token (4 · 8 / 32 = 1: on average ONE of a token's four).
* the dense layers' SwiGLU; the head over the vocabulary rows held; ``embed``
  is a gather.

Bytes: a scope's weights HELD, read forward and backward and their gradient
written, and its activations at the scope's edge (``opcount.py``'s five
``[tokens, d_model]`` a layer, the head's three); the optimizer's 24 B of
float32 state and one gradient in the compute dtype a parameter held. The
core's bytes are its operands and results at ITS edge: see
:func:`core_bytes_per_step`.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

ADAMW_STATE_BYTES_PER_PARAM = 24
ITEMSIZE = {"bfloat16": 2, "float16": 2, "float32": 4}
CORE = "gated_conv_core"


def _layers(tc: Dict[str, Any]) -> Dict[str, int]:
    """How many layers have each mixer and each feed-forward."""
    n = tc["n_layers"]
    conv = len(tc.get("conv_layers", ()))
    dense = tc.get("n_dense_layers", 0) if tc.get("n_experts") else n
    return {"conv": conv, "full": n - conv, "dense": dense, "experts": n - dense}


def conv_params(tc: Dict[str, Any]) -> Tuple[int, int]:
    """(matmul parameters on a token's path, parameters held) of one conv mixer."""
    d = tc["d_model"]
    return 4 * d * d, 4 * d * d + tc["conv_kernel"] * d


def attn_params(tc: Dict[str, Any]) -> Tuple[int, int]:
    """(matmul parameters, parameters held) of one attention layer."""
    d, hd, heads = tc["d_model"], tc["head_dim"], tc["n_heads"]
    kv = tc.get("n_kv_heads") or heads
    matmul = 2 * d * heads * hd + 2 * d * kv * hd
    norms = 0
    if tc.get("qk_norm"):
        norms = 2 * hd if tc.get("qk_norm_per_head") else (heads + kv) * hd
    return matmul, matmul + norms


def expert_params(tc: Dict[str, Any]) -> Dict[str, float]:
    """One expert layer: ``router`` and ``routed`` (held) parameters, and
    ``per_token``: the routed parameters a token passes through HERE."""
    d, f = tc["d_model"], tc.get("moe_d_ff") or tc["d_ff"]
    e = tc["n_experts"]
    held = tc.get("n_experts_held") or e
    one = 3 * d * f
    bias = e if tc.get("router_gate") == "sigmoid" and tc.get("router_selection_bias", True) else 0
    return {
        "router": d * e,
        "router_held": d * e + bias,
        "routed": held * one,
        "per_token": tc["top_k"] * held / e * one,
    }


def _ff_held(tc: Dict[str, Any], sparse: bool) -> float:
    if not sparse:
        return 3 * tc["d_model"] * tc["d_ff"]
    ex = expert_params(tc)
    return ex["router_held"] + ex["routed"]


def n_params(tc: Dict[str, Any]) -> int:
    """Every parameter HELD here (``models.transformer.init_params``)."""
    d, of = tc["d_model"], _layers(tc)
    return int(
        of["conv"] * conv_params(tc)[1] + of["full"] * attn_params(tc)[1]
        + of["dense"] * _ff_held(tc, False) + (of["experts"] * _ff_held(tc, True) if of["experts"] else 0)
        + tc["n_layers"] * 2 * d + d + 2 * tc["vocab_size"] * d
    )


def core_flops_per_token(tc: Dict[str, Any], seq: int) -> Dict[str, float]:
    """Forward + backward operations a token of the convolution's elementwise
    core, all conv layers: ``2K + 1`` a channel forward, times three."""
    return {CORE: 3.0 * _layers(tc)["conv"] * (2 * tc["conv_kernel"] + 1) * tc["d_model"]}


def core_bytes_per_step(tc: Dict[str, Any], batch: int, seq: int) -> Dict[str, float]:
    """The least bytes the core moves at its own edge a step, all conv layers,
    ``[tokens, d_model]`` arrays in the compute dtype: forward ``B``, ``X`` and
    ``C`` read and ``y`` written (4); backward those three and ``y``'s gradient
    read, the three gradients written (7) — the convolved product is formed
    again on the chip, not read. The forward that ``remat`` runs a second time
    is the program's, not the algorithm's, and is not counted."""
    c = ITEMSIZE[tc.get("dtype", "bfloat16")]
    return {CORE: float(_layers(tc)["conv"] * 11 * batch * seq * tc["d_model"] * c)}


def _attn_core_flops(tc: Dict[str, Any], seq: int) -> float:
    return 3.0 * _layers(tc)["full"] * tc["n_heads"] * 2 * ((seq + 1) / 2) * 2 * tc["head_dim"]


def ffn_scopes(tc: Dict[str, Any]) -> Tuple[str, ...]:
    of = _layers(tc)
    return tuple(s for s, n in (("ffn", of["dense"]), ("moe", of["experts"])) if n)


def flops_per_token_by_scope(tc: Dict[str, Any], seq: int) -> Dict[str, float]:
    d, of = tc["d_model"], _layers(tc)
    out = {
        "attn": 6.0 * (of["conv"] * conv_params(tc)[0] + of["full"] * attn_params(tc)[0])
        + _attn_core_flops(tc, seq),
        "head_loss": 6.0 * d * tc["vocab_size"],
    }
    if of["dense"]:
        out["ffn"] = 6.0 * of["dense"] * 3 * d * tc["d_ff"]
    if of["experts"]:
        ex = expert_params(tc)
        out["moe"] = 6.0 * of["experts"] * (ex["router"] + ex["per_token"])
    return out


def flops_per_token(tc: Dict[str, Any], seq: int) -> float:
    return sum(flops_per_token_by_scope(tc, seq).values())


def bytes_per_step_by_scope(tc: Dict[str, Any], batch: int, seq: int) -> Dict[str, float]:
    d, of = tc["d_model"], _layers(tc)
    c = ITEMSIZE[tc.get("dtype", "bfloat16")]
    act = batch * seq * d * c
    out = {
        "attn": 3.0 * c * (of["conv"] * conv_params(tc)[1] + of["full"] * attn_params(tc)[1]) + 5 * act * tc["n_layers"],
        "head_loss": 3.0 * c * d * tc["vocab_size"] + 3 * act,
        "optimizer": float((ADAMW_STATE_BYTES_PER_PARAM + c) * n_params(tc)),
    }
    if of["dense"]:
        out["ffn"] = of["dense"] * (3.0 * c * _ff_held(tc, False) + 5 * act)
    if of["experts"]:
        out["moe"] = of["experts"] * (3.0 * c * _ff_held(tc, True) + 5 * act)
    return out
