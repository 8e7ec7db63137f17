"""The count of a hybrid of linear attention (KDA), latent attention without
positions (MLA) and experts held under a share — ``program.opcount``
``kimi_linear`` — with ``opcount.INTERFACE`` and, for the readers of the two
cores, :func:`core_flops_per_token` and :func:`core_bytes_per_step`.

What is read from ``program.transformer_config``: ``d_model``, ``n_layers``,
``kda_layers``, ``mla_layers``, ``n_dense_layers``, ``d_ff``, ``moe_d_ff``,
``n_experts`` (the router's width), ``n_experts_held``, ``top_k``,
``n_shared_experts``, ``n_heads``, ``kv_lora_rank``, ``qk_nope_head_dim``,
``qk_rope_head_dim``, ``v_head_dim``, ``linear_n_heads``, ``linear_head_dim``,
``conv_kernel``, ``vocab_size`` (the rows HELD) and ``dtype``.

Counted as ``opcount.py`` counts: 6 operations per matmul parameter on a
token's path (forward, and twice that backward), what the algorithm needs and
no more, recomputation and padding not counted, never clamped.

* **KDA**, a layer: the projections q, k, v, o, the two low-rank gates and
  beta; the core is the recurrence's three ``D x D`` matrix-vector products a
  head a token forward (the decayed state read by the key, the rank-one write,
  the state read by the query: 3 · 2 · D² operations), three times that with
  the backward. The short convolution (K taps a channel), the norms, the
  decay and the gates are elementwise and not counted.
* **MLA**, a layer: ``W_q``, ``W_kva``, ``W_kvb``, ``W_o``; the core is causal
  attention's two score-sized products at half the square with keys
  ``nope + rope`` and values ``dv`` wide: ``2 · (s/2) · (nope + rope + dv)`` a
  head a token forward, times three.
* **Experts**, a layer: the router (``d x E``, every token), the shared
  experts (every token), and the routed experts at the share of a token's k
  that is held here: ``k · held / E`` passes a token (8 · 8 / 256 = 0.25).
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
    """How many layers have each mixer and each feed-forward."""
    n, dense = tc["n_layers"], tc.get("n_dense_layers", 0)
    kda, mla = len(tc.get("kda_layers", ())), len(tc.get("mla_layers", ()))
    assert kda + mla == n, "this count knows kda and mla mixers only"
    return {"kda": kda, "mla": mla, "dense": dense, "experts": n - dense}


def kda_params(tc: Dict[str, Any]) -> Tuple[int, int]:
    """(matmul parameters on a token's path, parameters held) of one KDA mixer."""
    d, hd, heads = tc["d_model"], tc["linear_head_dim"], tc["linear_n_heads"]
    ch = heads * hd
    matmul = 4 * d * ch + 2 * (d * hd + hd * ch) + d * heads
    # conv filters of q, k, v; a_log, dt_bias, the output norm's weight
    return matmul, matmul + 3 * tc.get("conv_kernel", 4) * ch + heads + ch + hd


def mla_params(tc: Dict[str, Any]) -> Tuple[int, int]:
    d, heads, rank = tc["d_model"], tc["n_heads"], tc["kv_lora_rank"]
    nope, rope, dv = tc["qk_nope_head_dim"], tc["qk_rope_head_dim"], tc["v_head_dim"]
    matmul = d * heads * (nope + rope) + d * (rank + rope) + rank * heads * (nope + dv) + heads * dv * d
    return matmul, matmul + rank  # the latent's norm weight


def expert_params(tc: Dict[str, Any]) -> Dict[str, float]:
    """One expert layer: ``router``, ``shared`` and ``routed`` (held) parameters,
    and ``per_token``: the routed parameters a token passes through HERE."""
    d, f = tc["d_model"], tc.get("moe_d_ff") or tc["d_ff"]
    e = tc["n_experts"]
    held = tc.get("n_experts_held") or e
    one = 3 * d * f
    return {
        "router": d * e,
        "router_held": d * e + (e if tc.get("router_gate") == "sigmoid" else 0),  # the selection bias
        "shared": tc.get("n_shared_experts", 0) * one,
        "routed": held * one,
        "per_token": tc["top_k"] * held / e * one,
    }


def n_params(tc: Dict[str, Any]) -> int:
    """Every parameter HELD here (``models.transformer.init_params``)."""
    d, vocab, of = tc["d_model"], tc["vocab_size"], _layers(tc)
    ex = expert_params(tc)
    return int(
        of["kda"] * kda_params(tc)[1] + of["mla"] * mla_params(tc)[1]
        + of["dense"] * 3 * d * tc["d_ff"]
        + of["experts"] * (ex["router_held"] + ex["shared"] + ex["routed"])
        + tc["n_layers"] * 2 * d + d + 2 * vocab * d
    )


def core_flops_per_token(tc: Dict[str, Any], seq: int) -> Dict[str, float]:
    """Forward + backward operations a token of the two cores, all layers."""
    of = _layers(tc)
    hd = tc["linear_head_dim"]
    kda_fwd = tc["linear_n_heads"] * 3 * 2 * hd * hd
    mla_fwd = tc["n_heads"] * 2 * (seq / 2) * (tc["qk_nope_head_dim"] + tc["qk_rope_head_dim"] + tc["v_head_dim"])
    return {"kda_core": 3.0 * of["kda"] * kda_fwd, "mla_core": 3.0 * of["mla"] * mla_fwd}


def core_bytes_per_step(tc: Dict[str, Any], batch: int, seq: int) -> Dict[str, float]:
    """The least bytes the two cores move at their own edge a step, all layers.
    KDA, per token and channel: q, k, v in the compute dtype, the float32
    log-decay, the output; backward the same read again with the output's
    gradient, and four gradients written (the per-head beta and its gradient,
    1/128 of a channel's, beside them). MLA, per token and head: q and k
    ``nope + rope`` wide, v and the output ``dv`` wide; backward those and the
    output's gradient read, three gradients written."""
    of, c = _layers(tc), ITEMSIZE[tc.get("dtype", "bfloat16")]
    tokens = batch * seq
    heads, hd = tc["linear_n_heads"], tc["linear_head_dim"]
    kda_channel = (3 * c + 4 + c) + (3 * c + 4 + c) + (3 * c + 4)
    kda = tokens * (heads * hd * kda_channel + heads * 3 * 4)
    qk, dv = tc["qk_nope_head_dim"] + tc["qk_rope_head_dim"], tc["v_head_dim"]
    mla = tokens * tc["n_heads"] * c * ((2 * qk + 2 * dv) + (2 * qk + 3 * dv) + (2 * qk + dv))
    return {"kda_core": float(of["kda"] * kda), "mla_core": float(of["mla"] * mla)}


def ffn_scopes(tc: Dict[str, Any]) -> Tuple[str, ...]:
    of = _layers(tc)
    return tuple(s for s, n in (("ffn", of["dense"]), ("moe", of["experts"])) if n)


def flops_per_token_by_scope(tc: Dict[str, Any], seq: int) -> Dict[str, float]:
    d, of = tc["d_model"], _layers(tc)
    ex = expert_params(tc)
    out = {
        "attn": 6.0 * (of["kda"] * kda_params(tc)[0] + of["mla"] * mla_params(tc)[0])
        + sum(core_flops_per_token(tc, seq).values()),
        "head_loss": 6.0 * d * tc["vocab_size"],
    }
    if of["dense"]:
        out["ffn"] = 6.0 * of["dense"] * 3 * d * tc["d_ff"]
    if of["experts"]:
        out["moe"] = 6.0 * of["experts"] * (ex["router"] + ex["shared"] + ex["per_token"])
    return out


def flops_per_token(tc: Dict[str, Any], seq: int) -> float:
    return sum(flops_per_token_by_scope(tc, seq).values())


def bytes_per_step_by_scope(tc: Dict[str, Any], batch: int, seq: int) -> Dict[str, float]:
    d, of = tc["d_model"], _layers(tc)
    c = ITEMSIZE[tc.get("dtype", "bfloat16")]
    act = batch * seq * d * c
    ex = expert_params(tc)
    out = {
        "attn": 3.0 * c * (of["kda"] * kda_params(tc)[1] + of["mla"] * mla_params(tc)[1])
        + 5 * act * tc["n_layers"],
        "head_loss": 3.0 * c * d * tc["vocab_size"] + 3 * act,
        "optimizer": float((ADAMW_STATE_BYTES_PER_PARAM + c) * n_params(tc)),
    }
    if of["dense"]:
        out["ffn"] = of["dense"] * (3.0 * c * 3 * d * tc["d_ff"] + 5 * act)
    if of["experts"]:
        out["moe"] = of["experts"] * (3.0 * c * (ex["router_held"] + ex["shared"] + ex["routed"]) + 5 * act)
    return out
