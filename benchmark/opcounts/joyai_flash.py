"""The count of a decoder with latent attention in EVERY layer — a low-rank
query, a key/value latent and one rotated key part shared by all heads — a
leading dense layer, experts held under a share beside a shared one, and a
multi-token-prediction module behind the stack that shares the two tables:
``program.opcount`` ``joyai_flash``, with ``opcount.INTERFACE`` and, for the
readers of the core, :func:`core_flops_per_token` and :func:`core_bytes_per_step`.

What is read from ``program.transformer_config``: ``d_model``, ``n_layers``,
``mla_layers`` (all of them), ``n_dense_layers``, ``d_ff``, ``moe_d_ff``,
``n_experts`` (the router's width), ``n_experts_held``, ``top_k``,
``n_shared_experts``, ``router_gate`` / ``router_selection_bias``, ``n_heads``,
``q_lora_rank``, ``kv_lora_rank``, ``qk_nope_head_dim``, ``qk_rope_head_dim``,
``v_head_dim``, ``n_mtp_modules`` / ``mtp_loss_weight``, ``vocab_size`` (the
rows HELD) and ``dtype``.

Counted as ``opcount.py`` counts: 6 operations per matmul parameter on a
token's path (forward, and twice that backward), what the algorithm needs and
no more, recomputation, padding (the kernel pads keys 192 → 256) and masked
tiles not counted, never clamped.

* **Latent attention**, a layer: ``W_qa`` (d x r_q), ``W_qb`` (r_q x H(nope +
  rope)) — or the one ``W_q`` at ``q_lora_rank`` 0 — ``W_kva`` (d x (rank +
  rope)), ``W_kvb`` (rank x H(nope + dv)), ``W_o`` (H dv x d); the core is causal
  attention's two score-sized products over the ``(s + 1) / 2`` keys a query
  sees on average, keys ``nope + rope`` and values ``dv`` wide:
  ``2 · (s + 1)/2 · (nope + rope + dv)`` a head a token forward, times three.
  The two norms and the rotation are elementwise and not counted.
* **Experts**, a layer: the router (``d x E``, every token), the shared experts
  (every token), and the routed experts at the share of a token's k that is
  held here: ``k · held / E`` passes a token (8 · 8 / 256 = 0.25).
* **The module**: ``eh_proj`` (2d x d, scope ``embed``), one more layer of the
  last layer's kind (``attn``, ``moe``) and a second pass of the head — counted
  while its loss has a weight; with weight 0 the program does not run it.
* the dense layers' SwiGLU; each head pass over the vocabulary rows held; the
  embedding lookups are gathers.

Bytes: a scope's weights HELD, read forward and backward and their gradient
written, and its activations at the scope's edge (``opcount.py``'s five
``[tokens, d_model]`` a layer, three a head pass); the optimizer's 24 B of
float32 state and one gradient in the compute dtype a parameter held (the
module's whether or not it runs). The core's bytes are its operands and results
at ITS edge: see :func:`core_bytes_per_step`.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

ADAMW_STATE_BYTES_PER_PARAM = 24
ITEMSIZE = {"bfloat16": 2, "float16": 2, "float32": 4}


def _layers(tc: Dict[str, Any]) -> Dict[str, int]:
    """How many layers of the MAIN stack have each feed-forward, how many
    modules are held, and how many of them run (a loss weight of 0: none)."""
    n = tc["n_layers"]
    assert len(tc.get("mla_layers", ())) == n, "this count knows a latent attention in every layer"
    dense = tc.get("n_dense_layers", 0) if tc.get("n_experts") else n
    modules = tc.get("n_mtp_modules", 0)
    return {
        "dense": dense, "experts": n - dense, "modules": modules,
        "running": modules if tc.get("mtp_loss_weight", 0.1) else 0,
    }


def _last_is_sparse(tc: Dict[str, Any]) -> bool:
    return bool(tc.get("n_experts")) and tc["n_layers"] > tc.get("n_dense_layers", 0)


def attn_params(tc: Dict[str, Any]) -> Tuple[int, int]:
    """(matmul parameters on a token's path, parameters held) of one layer's attention."""
    d, heads, rank, rq = tc["d_model"], tc["n_heads"], tc["kv_lora_rank"], tc.get("q_lora_rank", 0)
    nope, rope, dv = tc["qk_nope_head_dim"], tc["qk_rope_head_dim"], tc["v_head_dim"]
    query = d * rq + rq * heads * (nope + rope) if rq else d * heads * (nope + rope)
    matmul = query + d * (rank + rope) + rank * heads * (nope + dv) + heads * dv * d
    return matmul, matmul + rank + rq  # the two latents' norm weights


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


def _ff_held(tc: Dict[str, Any], sparse: bool) -> float:
    if not sparse:
        return 3 * tc["d_model"] * tc["d_ff"]
    ex = expert_params(tc)
    return ex["router_held"] + ex["shared"] + ex["routed"]


def module_params(tc: Dict[str, Any]) -> int:
    """One multi-token-prediction module: two input norms, ``eh_proj``, a layer
    of the last layer's kind with its two norms, and a final norm."""
    d = tc["d_model"]
    return int(3 * d + 2 * d * d + attn_params(tc)[1] + 2 * d + _ff_held(tc, _last_is_sparse(tc)))


def n_params(tc: Dict[str, Any]) -> int:
    """Every parameter HELD here (``models.transformer.init_params``)."""
    d, of = tc["d_model"], _layers(tc)
    return int(
        tc["n_layers"] * (attn_params(tc)[1] + 2 * d)
        + of["dense"] * _ff_held(tc, False) + (of["experts"] * _ff_held(tc, True) if of["experts"] else 0)
        + of["modules"] * module_params(tc)
        + d + 2 * tc["vocab_size"] * d
    )


def _blocks(tc: Dict[str, Any]) -> Dict[str, int]:
    """Layers a step RUNS, the module's among them: of attention, of dense
    feed-forwards, of experts; and passes of the head."""
    of = _layers(tc)
    sparse_module = of["running"] if _last_is_sparse(tc) else 0
    return {
        "attn": tc["n_layers"] + of["running"],
        "dense": of["dense"] + of["running"] - sparse_module,
        "experts": of["experts"] + sparse_module,
        "heads": 1 + of["running"],
        "modules": of["running"],
    }


def core_flops_per_token(tc: Dict[str, Any], seq: int) -> Dict[str, float]:
    """Forward + backward operations a token of the causal core, every layer that runs."""
    wide = tc["qk_nope_head_dim"] + tc["qk_rope_head_dim"] + tc["v_head_dim"]
    forward = tc["n_heads"] * 2 * ((seq + 1) / 2) * wide
    return {"mla_core": 3.0 * _blocks(tc)["attn"] * forward}


def core_bytes_per_step(tc: Dict[str, Any], batch: int, seq: int) -> Dict[str, float]:
    """The least bytes the core moves at its own edge a step, every layer that
    runs. Per token and head: q and k ``nope + rope`` wide, v and the output
    ``dv`` wide; backward those and the output's gradient read, three gradients
    written (the shared key part counted at every head: the kernel reads keys
    per head)."""
    c = ITEMSIZE[tc.get("dtype", "bfloat16")]
    qk, dv = tc["qk_nope_head_dim"] + tc["qk_rope_head_dim"], tc["v_head_dim"]
    one = batch * seq * tc["n_heads"] * c * ((2 * qk + 2 * dv) + (2 * qk + 3 * dv) + (2 * qk + dv))
    return {"mla_core": float(_blocks(tc)["attn"] * one)}


def ffn_scopes(tc: Dict[str, Any]) -> Tuple[str, ...]:
    blocks = _blocks(tc)
    return tuple(s for s, n in (("ffn", blocks["dense"]), ("moe", blocks["experts"])) if n)


def flops_per_token_by_scope(tc: Dict[str, Any], seq: int) -> Dict[str, float]:
    d, blocks = tc["d_model"], _blocks(tc)
    out = {
        "attn": 6.0 * blocks["attn"] * attn_params(tc)[0] + core_flops_per_token(tc, seq)["mla_core"],
        "head_loss": 6.0 * blocks["heads"] * d * tc["vocab_size"],
    }
    if blocks["modules"]:
        out["embed"] = 6.0 * blocks["modules"] * 2 * d * d  # eh_proj
    if blocks["dense"]:
        out["ffn"] = 6.0 * blocks["dense"] * 3 * d * tc["d_ff"]
    if blocks["experts"]:
        ex = expert_params(tc)
        out["moe"] = 6.0 * blocks["experts"] * (ex["router"] + ex["shared"] + ex["per_token"])
    return out


def flops_per_token(tc: Dict[str, Any], seq: int) -> float:
    return sum(flops_per_token_by_scope(tc, seq).values())


def bytes_per_step_by_scope(tc: Dict[str, Any], batch: int, seq: int) -> Dict[str, float]:
    d, blocks = tc["d_model"], _blocks(tc)
    c = ITEMSIZE[tc.get("dtype", "bfloat16")]
    act = batch * seq * d * c
    out = {
        "attn": blocks["attn"] * (3.0 * c * attn_params(tc)[1] + 5 * act),
        "head_loss": blocks["heads"] * (3.0 * c * d * tc["vocab_size"] + 3 * act),
        "optimizer": float((ADAMW_STATE_BYTES_PER_PARAM + c) * n_params(tc)),
    }
    if blocks["dense"]:
        out["ffn"] = blocks["dense"] * (3.0 * c * _ff_held(tc, False) + 5 * act)
    if blocks["experts"]:
        out["moe"] = blocks["experts"] * (3.0 * c * _ff_held(tc, True) + 5 * act)
    return out
