"""The count of a hybrid of Gated DeltaNet layers (a delta rule with one decay
a head a position, key heads shared by value heads) and gated softmax layers
(grouped-query heads, a gate a lane carried by the query projection), every
layer with experts held under a share beside a gated shared expert —
``program.opcount`` ``qwen3_next`` — with ``opcount.INTERFACE`` and, for the
readers of the two cores, :func:`core_flops_per_token` and
:func:`core_bytes_per_step` (``gdn_core``, ``global_core``).

What is read from ``program.transformer_config``: ``d_model``, ``n_layers``,
``gdn_layers``, ``linear_n_heads`` (value heads), ``linear_n_key_heads``,
``linear_head_dim``, ``conv_kernel``, ``n_heads``, ``n_kv_heads``,
``head_dim``, ``attn_output_gate``, ``qk_norm`` / ``qk_norm_per_head``,
``moe_d_ff``, ``n_experts`` (the router's width), ``n_experts_held``,
``top_k``, ``n_shared_experts``, ``shared_expert_gate``, ``vocab_size`` (the
rows HELD) and ``dtype``.

Counted as ``opcount.py`` counts: 6 operations per matmul parameter on a
token's path (forward, and twice that backward), what the algorithm needs and
no more, recomputation, padding and masked tiles not counted, never clamped.

* **Gated DeltaNet**, a layer: the projections q, k (``d x Hk D``), v, z
  (``d x Hv D``), ``[b | a]`` (``d x 2 Hv``) and o (``Hv D x d``); the core is
  the RECURRENCE's own work whatever implements it: three ``D x D``
  matrix-vector products a value head a token forward (the decayed state read
  by the key, the rank-one write, the state read by the query: 3 · 2 · D²),
  three times that with the backward. The short convolution, the norms, the
  decay and the gates are elementwise and not counted.
* **Gated softmax attention**, a layer: ``W_q`` (``d x 2 H D`` under the gate),
  ``W_k``, ``W_v`` (``d x G D``), ``W_o`` (``H D x d``); the core is causal
  attention's two score-sized products at ``(s + 1) / 2`` keys a query,
  ``2 · keys · 2D`` a head a token forward, times three.
* **Experts**, a layer: the router (``d x E``, every token), the shared expert
  and its gate (every token), and the routed experts at the share of a token's
  k that is held here: ``k · held / E`` passes a token (10 · 32 / 512 = 0.625).
* the head over the vocabulary rows held; ``embed`` is a gather.

Bytes: a scope's weights HELD, read forward and backward and their gradient
written, and its activations at the scope's edge (``opcount.py``'s five
``[tokens, d_model]`` a layer, the head's three); the optimizer's 24 B of
float32 state and one gradient in the compute dtype a parameter held. A core's
bytes are its operands and results at ITS edge: see :func:`core_bytes_per_step`.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

ADAMW_STATE_BYTES_PER_PARAM = 24
ITEMSIZE = {"bfloat16": 2, "float16": 2, "float32": 4}


def _layers(tc: Dict[str, Any]) -> Dict[str, int]:
    """How many layers have each mixer; every layer has experts."""
    gdn = len(tc.get("gdn_layers", ()))
    return {"gdn": gdn, "full": tc["n_layers"] - gdn}


def _linear_heads(tc: Dict[str, Any]) -> Tuple[int, int, int]:
    """(value heads, key heads, a head's width) of the Gated DeltaNet layers."""
    heads = tc["linear_n_heads"]
    return heads, tc.get("linear_n_key_heads") or heads, tc["linear_head_dim"]


def gdn_params(tc: Dict[str, Any]) -> Tuple[int, int]:
    """(matmul parameters on a token's path, parameters held) of one Gated DeltaNet mixer."""
    d = tc["d_model"]
    heads, key_heads, hd = _linear_heads(tc)
    kch, vch = key_heads * hd, heads * hd
    matmul = d * (2 * kch + 2 * vch) + d * 2 * heads + vch * d
    # the convolution's taps over q | k | v; a_log and dt_bias a value head; the output norm's weight
    return matmul, matmul + tc.get("conv_kernel", 4) * (2 * kch + vch) + 2 * heads + hd


def attn_params(tc: Dict[str, Any]) -> Tuple[int, int]:
    """(matmul parameters, parameters held) of one gated softmax layer."""
    d, hd, heads = tc["d_model"], tc["head_dim"], tc["n_heads"]
    kv = tc.get("n_kv_heads") or heads
    wq = d * heads * hd * (2 if tc.get("attn_output_gate") else 1)
    matmul = wq + 2 * d * kv * hd + heads * hd * d
    norms = 0
    if tc.get("qk_norm"):
        norms = 2 * hd if tc.get("qk_norm_per_head") else (heads + kv) * hd
    return matmul, matmul + norms


def expert_params(tc: Dict[str, Any]) -> Dict[str, float]:
    """One expert layer: ``router``, ``shared`` (with its gate) and ``routed``
    (held) parameters, and ``per_token``: the routed parameters a token passes
    through HERE."""
    d, f, e = tc["d_model"], tc["moe_d_ff"], tc["n_experts"]
    held = tc.get("n_experts_held") or e
    one = 3 * d * f
    shared = tc.get("n_shared_experts", 0) * one
    return {
        "router": d * e,
        "shared": shared + (d if shared and tc.get("shared_expert_gate") else 0),
        "routed": held * one,
        "per_token": tc["top_k"] * held / e * one,
    }


def _experts_held(tc: Dict[str, Any]) -> float:
    ex = expert_params(tc)
    return ex["router"] + ex["shared"] + ex["routed"]


def n_params(tc: Dict[str, Any]) -> int:
    """Every parameter HELD here (``models.transformer.init_params``)."""
    d, of = tc["d_model"], _layers(tc)
    return int(
        of["gdn"] * gdn_params(tc)[1] + of["full"] * attn_params(tc)[1]
        + tc["n_layers"] * (_experts_held(tc) + 2 * d) + d + 2 * tc["vocab_size"] * d
    )


def core_flops_per_token(tc: Dict[str, Any], seq: int) -> Dict[str, float]:
    """Forward + backward operations a token of the two cores, all layers."""
    of = _layers(tc)
    heads, _, hd = _linear_heads(tc)
    gdn_fwd = heads * 3 * 2 * hd * hd
    softmax_fwd = tc["n_heads"] * 2 * ((seq + 1) / 2) * 2 * tc["head_dim"]
    return {"gdn_core": 3.0 * of["gdn"] * gdn_fwd, "global_core": 3.0 * of["full"] * softmax_fwd}


def core_bytes_per_step(tc: Dict[str, Any], batch: int, seq: int) -> Dict[str, float]:
    """The least bytes the two cores move at their own edge a step, all layers.
    Gated DeltaNet, per token: q and k over the KEY heads and v over the value
    heads in the compute dtype, the float32 log-decay and write strength a
    value head, the output over the value heads; backward the same read again
    with the output's gradient in the output's place, and the five gradients
    written. Softmax, per token, H query heads and G key/value heads of D
    lanes: forward q and the output (2H) and k, v (2G); backward those and the
    output's gradient read (3H + 2G), dq, dk and dv written (H + 2G)."""
    of, c = _layers(tc), ITEMSIZE[tc.get("dtype", "bfloat16")]
    tokens = batch * seq
    heads, key_heads, hd = _linear_heads(tc)
    operands = (2 * key_heads + heads) * hd * c + 2 * heads * 4  # q, k, v; g, beta
    gdn = tokens * (3 * operands + 2 * heads * hd * c)  # read, read again, gradients written; o written, do read
    kv = tc.get("n_kv_heads") or tc["n_heads"]
    softmax = tokens * c * tc["head_dim"] * (6 * tc["n_heads"] + 6 * kv)
    return {"gdn_core": float(of["gdn"] * gdn), "global_core": float(of["full"] * softmax)}


def ffn_scopes(tc: Dict[str, Any]) -> Tuple[str, ...]:
    return ("moe",)


def flops_per_token_by_scope(tc: Dict[str, Any], seq: int) -> Dict[str, float]:
    d, of = tc["d_model"], _layers(tc)
    ex = expert_params(tc)
    return {
        "attn": 6.0 * (of["gdn"] * gdn_params(tc)[0] + of["full"] * attn_params(tc)[0])
        + sum(core_flops_per_token(tc, seq).values()),
        "moe": 6.0 * tc["n_layers"] * (ex["router"] + ex["shared"] + ex["per_token"]),
        "head_loss": 6.0 * d * tc["vocab_size"],
    }


def flops_per_token(tc: Dict[str, Any], seq: int) -> float:
    return sum(flops_per_token_by_scope(tc, seq).values())


def bytes_per_step_by_scope(tc: Dict[str, Any], batch: int, seq: int) -> Dict[str, float]:
    d, of = tc["d_model"], _layers(tc)
    c = ITEMSIZE[tc.get("dtype", "bfloat16")]
    act = batch * seq * d * c
    return {
        "attn": 3.0 * c * (of["gdn"] * gdn_params(tc)[1] + of["full"] * attn_params(tc)[1]) + 5 * act * tc["n_layers"],
        "moe": tc["n_layers"] * (3.0 * c * _experts_held(tc) + 5 * act),
        "head_loss": 3.0 * c * d * tc["vocab_size"] + 3 * act,
        "optimizer": float((ADAMW_STATE_BYTES_PER_PARAM + c) * n_params(tc)),
    }
