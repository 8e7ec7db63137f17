"""The count of a decoder whose softmax layers come in two kinds — ``window``
(a band of ``window`` keys) and ``global`` (the whole prefix), each with its own
number of query heads over the model's key/value heads — with a leading dense
layer and experts held under a share beside a shared one: ``program.opcount``
``laguna``, with ``opcount.INTERFACE`` and, for the readers of the two cores,
:func:`core_flops_per_token` and :func:`core_bytes_per_step`.

What is read from ``program.transformer_config``: ``d_model``, ``n_layers``,
``window_layers``, ``window``, ``n_heads`` / ``n_heads_per_layer`` (entry l - 1:
layer l), ``n_kv_heads``, ``head_dim``, ``n_dense_layers``, ``d_ff``,
``moe_d_ff``, ``n_experts`` (the router's width), ``n_experts_held``, ``top_k``,
``n_shared_experts``, ``router_gate`` / ``router_selection_bias``,
``vocab_size`` (the rows HELD) and ``dtype``.

Counted as ``opcount.py`` counts: 6 operations per matmul parameter on a
token's path (forward, and twice that backward), what the algorithm needs and
no more, recomputation, padding and masked tiles not counted, never clamped.

* **Attention**, layer l with H_l query heads, G key/value heads of D: the
  projections ``d x H_l D`` (q), twice ``d x G D`` (k, v) and ``H_l D x d`` (o);
  the core is the two score-sized products over the keys a query sees,
  ``2 · keys · 2D`` a head a token forward, times three: a **global** layer
  sees ``(s + 1) / 2`` keys on average, a **window** layer the band's exact
  mean ``Σ_i min(i + 1, w) / s`` (496.03 at s8192, w512).
* **Experts**, a layer: the router (``d x E``, every token), the shared
  experts (every token), and the routed experts at the share of a token's k
  that is held here: ``k · held / E`` passes a token (8 · 32 / 256 = 1).
* the dense layers' SwiGLU; the head over the vocabulary rows held; ``embed``
  is a gather.

Bytes: a scope's weights HELD, read forward and backward and their gradient
written, and its activations at the scope's edge (``opcount.py``'s five
``[tokens, d_model]`` a layer, the head's three); the optimizer's 24 B of
float32 state and one gradient in the compute dtype a parameter held. A core's
bytes are its operands and results at ITS edge: see :func:`core_bytes_per_step`.
"""

from __future__ import annotations

from typing import Any, Dict, List, Tuple

ADAMW_STATE_BYTES_PER_PARAM = 24
ITEMSIZE = {"bfloat16": 2, "float16": 2, "float32": 4}
CORES = {"window": "window_core", "global": "global_core"}


def layer_heads(tc: Dict[str, Any]) -> List[Tuple[str, int]]:
    """(kind, query heads) of every layer, in order: ``window`` or ``global``."""
    per_layer = tc.get("n_heads_per_layer") or [tc["n_heads"]] * tc["n_layers"]
    window = set(tc.get("window_layers", ()))
    return [("window" if l in window else "global", per_layer[l - 1]) for l in range(1, tc["n_layers"] + 1)]


def _feed_forwards(tc: Dict[str, Any]) -> Dict[str, int]:
    dense = tc.get("n_dense_layers", 0) if tc.get("n_experts") else tc["n_layers"]
    return {"dense": dense, "experts": tc["n_layers"] - dense}


def attn_params(tc: Dict[str, Any], heads: int) -> int:
    """The four projections of one layer with ``heads`` query heads."""
    d, hd, kv = tc["d_model"], tc["head_dim"], tc.get("n_kv_heads") or tc["n_heads"]
    return 2 * d * heads * hd + 2 * d * kv * hd


def expert_params(tc: Dict[str, Any]) -> Dict[str, float]:
    """One expert layer: ``router``, ``shared`` and ``routed`` (held) parameters,
    and ``per_token``: the routed parameters a token passes through HERE."""
    d, f = tc["d_model"], tc.get("moe_d_ff") or tc["d_ff"]
    e = tc["n_experts"]
    held = tc.get("n_experts_held") or e
    one = 3 * d * f
    bias = e if tc.get("router_gate") == "sigmoid" and tc.get("router_selection_bias", True) else 0
    return {
        "router": d * e,
        "router_held": d * e + bias,
        "shared": tc.get("n_shared_experts", 0) * one,
        "routed": held * one,
        "per_token": tc["top_k"] * held / e * one,
    }


def n_params(tc: Dict[str, Any]) -> int:
    """Every parameter HELD here (``models.transformer.init_params``)."""
    d, vocab, of = tc["d_model"], tc["vocab_size"], _feed_forwards(tc)
    ex = expert_params(tc) if of["experts"] else {"router_held": 0, "shared": 0, "routed": 0}
    return int(
        sum(attn_params(tc, heads) for _, heads in layer_heads(tc))
        + of["dense"] * 3 * d * tc["d_ff"]
        + of["experts"] * (ex["router_held"] + ex["shared"] + ex["routed"])
        + tc["n_layers"] * 2 * d + d + 2 * vocab * d
    )


def keys_per_query(tc: Dict[str, Any], seq: int) -> Dict[str, float]:
    """Keys a query sees on average over a sequence of ``seq``."""
    w = min(int(tc.get("window") or seq), seq)
    return {"global": (seq + 1) / 2, "window": (w * (w + 1) / 2 + (seq - w) * w) / seq}


def core_flops_per_token(tc: Dict[str, Any], seq: int) -> Dict[str, float]:
    """Forward + backward operations a token of the two kinds' cores, all layers."""
    keys, out = keys_per_query(tc, seq), {core: 0.0 for core in CORES.values()}
    for kind, heads in layer_heads(tc):
        out[CORES[kind]] += 3.0 * heads * 2 * keys[kind] * 2 * tc["head_dim"]
    return out


def core_bytes_per_step(tc: Dict[str, Any], batch: int, seq: int) -> Dict[str, float]:
    """The least bytes the cores move at their own edge a step, all layers. Per
    token, H query heads and G key/value heads of D lanes in the compute dtype:
    forward q and the output (2H) and k, v (2G); backward those and the
    output's gradient read (3H + 2G), dq, dk and dv written (H + 2G)."""
    c, kv = ITEMSIZE[tc.get("dtype", "bfloat16")], tc.get("n_kv_heads") or tc["n_heads"]
    out = {core: 0.0 for core in CORES.values()}
    for kind, heads in layer_heads(tc):
        out[CORES[kind]] += float(batch * seq * c * tc["head_dim"] * (6 * heads + 6 * kv))
    return out


def ffn_scopes(tc: Dict[str, Any]) -> Tuple[str, ...]:
    of = _feed_forwards(tc)
    return tuple(s for s, n in (("ffn", of["dense"]), ("moe", of["experts"])) if n)


def flops_per_token_by_scope(tc: Dict[str, Any], seq: int) -> Dict[str, float]:
    d, of = tc["d_model"], _feed_forwards(tc)
    out = {
        "attn": 6.0 * sum(attn_params(tc, heads) for _, heads in layer_heads(tc))
        + sum(core_flops_per_token(tc, seq).values()),
        "head_loss": 6.0 * d * tc["vocab_size"],
    }
    if of["dense"]:
        out["ffn"] = 6.0 * of["dense"] * 3 * d * tc["d_ff"]
    if of["experts"]:
        ex = expert_params(tc)
        out["moe"] = 6.0 * of["experts"] * (ex["router"] + ex["shared"] + ex["per_token"])
    return out


def flops_per_token(tc: Dict[str, Any], seq: int) -> float:
    return sum(flops_per_token_by_scope(tc, seq).values())


def bytes_per_step_by_scope(tc: Dict[str, Any], batch: int, seq: int) -> Dict[str, float]:
    d, of = tc["d_model"], _feed_forwards(tc)
    c = ITEMSIZE[tc.get("dtype", "bfloat16")]
    act = batch * seq * d * c
    out = {
        "attn": 3.0 * c * sum(attn_params(tc, heads) for _, heads in layer_heads(tc)) + 5 * act * tc["n_layers"],
        "head_loss": 3.0 * c * d * tc["vocab_size"] + 3 * act,
        "optimizer": float((ADAMW_STATE_BYTES_PER_PARAM + c) * n_params(tc)),
    }
    if of["dense"]:
        out["ffn"] = of["dense"] * (3.0 * c * 3 * d * tc["d_ff"] + 5 * act)
    if of["experts"]:
        ex = expert_params(tc)
        out["moe"] = of["experts"] * (3.0 * c * (ex["router_held"] + ex["shared"] + ex["routed"]) + 5 * act)
    return out
