"""Plain reference for the decoder of JoyAI-LLM-Flash (``model_type``
``joyai_llm_flash``), as the benchmark's ``joyai-flash-*`` configurations run
it: forward pass and training loss in straightforward float32 ``jax.numpy``,
written from the layer equations of ISSUE 46 (after the published
``config.json``), independent of ``torchft_tpu.models`` and
``torchft_tpu.ops``. No flash kernel, no sort, no grouped matmul, no custom
gradient: masks are explicit comparisons of positions, the two losses are
slices.

Every layer is ``x = x + mix(RMSNorm(x; ln1)); x = x + ff(RMSNorm(x; ln2))``,
layers counted from 1 as the program counts them (the source counts from 0).

* **Latent attention**, every layer, H heads: ``c_q = RMSNorm(h·W_qa; q_a_norm)``
  [q_lora_rank], ``q = c_q·W_qb`` → H x (nope | rope) (``q_lora_rank`` 0: the
  one ``W_q``); ``(c_kv | k_r) = h·W_kva`` [rank | rope];
  ``c = RMSNorm(c_kv; kv_norm)``; ``(k_n | v) = c·W_kvb`` → H x (nope | dv);
  ``q = q_n | R_p(q_r)``, ``k = k_n | R_p(k_r)`` with the ONE rotated ``k_r``
  read by every head; scores ``q·k / √(nope + rope)``, causal softmax over v,
  ``·W_o``. ``R_p``: lane 2i turns with lane 2i + 1 by ``p · theta^(-2i/rope)``
  (``mla_rope_theta``; 0: no rotation, the positionless form). Computed in
  blocks of ``QUERY_BLOCK`` queries against every key so that a sequence of
  8 192 fits a chip: a block's scores are the full matrix's rows.
* **Feed-forward**: the first ``n_dense_layers`` layers a SwiGLU of ``d_ff``;
  the others ``s = sigmoid(h·W_r)`` over all E experts, the k largest of
  ``s + b`` chosen (``b`` = ``router_bias``: it moves the choice and not the
  weights), weights ``routed_scaling_factor · s_e / Σ_chosen s``;
  ``y = SwiGLU_shared(h) + Σ_{e chosen AND held} w_e SwiGLU_e(h)``: a loop over
  the experts held, each applied to every token and masked to its tokens.
* **Multi-token prediction**, one module behind the stack (``n_mtp_modules``
  1): with ``h`` the stack's output after ``final_norm`` and ``e = Emb(t)``,
  ``x_i = [RMSNorm(e_{i+1}; enorm) | RMSNorm(h_i; hnorm)]·W_eh``, one layer of
  the last layer's kind at positions i, ``RMSNorm(·; mtp.final_norm)``, the
  MAIN ``out`` table.
* loss: ``L = CE(h_i → t_{i+1}) + mtp_loss_weight · CE(h'_i → t_{i+2})``, the
  first a mean over positions 0..S-2, the second over 0..S-3; no balance term.
  A weight of 0 (or no module) is the first term alone.

**Departures from the published model, all stated in the configuration file.**
(1) *The share*: of the E experts the router chooses among this chip holds
``n_experts_held`` (the block ``expert_share_index``); what the absent experts
would add is left out, here as in the program; the weights are still
renormalised over all k chosen. (2) The module's loss weight, the order inside
the concatenation and that ``h`` is taken after the final norm are assumed.

``sizes`` is a configuration's ``program.transformer_config``. Parameter names
and shapes are the interface with the program (``init_params``): ``embed``,
``out``, ``final_norm``; the layers grouped by kind ``"mla.<ff>"``:
``lead[kind][name] [n, ...]`` for the leading layers, ``periods[kind][name]
[repeats, n, ...]`` for those the program scans (a model of one kind:
``layers[name] [1, L, ...]``); ``mtp``: ``enorm``, ``hnorm``, ``eh_proj``,
``layer[name]`` (one layer, no leading axis), ``final_norm``.

On a TPU a float32 matmul runs in lower precision unless
``jax.default_matmul_precision("highest")`` is set; :func:`loss` sets it.
"""

from __future__ import annotations

from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp

__all__ = ["hidden", "mtp_hidden", "forward", "losses", "loss", "per_sequence_loss", "layer_params", "inv_frequencies"]

QUERY_BLOCK = 256


def _rms_norm(x, weight, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight


def _swiglu(h, gate, up, down):
    return (jax.nn.silu(h @ gate) * (h @ up)) @ down


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32), tree)


def _kinds(sizes) -> List[Tuple[str, str]]:
    assert len(sizes["mla_layers"]) == sizes["n_layers"], "every layer of this decoder is a latent attention"
    return [
        ("mla", "experts" if sizes.get("n_experts") and i > sizes.get("n_dense_layers", 0) else "dense")
        for i in range(1, sizes["n_layers"] + 1)
    ]


def layer_params(params, sizes) -> List[Dict[str, Any]]:
    """Each layer's own parameters, in layer order, from the program's tree:
    how many leading layers and how long a period is read off the tree's own
    leading axes, the order of kinds inside them off ``sizes``."""
    kinds = [f"{m}.{f}" for m, f in _kinds(sizes)]
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


def inv_frequencies(sizes) -> List[float]:
    """The rope/2 inverse frequencies of the rotation, as plain Python floats."""
    r, theta = sizes["qk_rope_head_dim"], float(sizes["mla_rope_theta"])
    return [theta ** (-2.0 * i / r) for i in range(r // 2)]


def _rotate(x, inv_freq):
    """x [B, S, H, r]: lane 2i with lane 2i + 1 by position x inv_freq[i]."""
    angle = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * jnp.asarray(inv_freq, jnp.float32)[None, :]
    cos, sin = jnp.cos(angle)[None, :, None, :], jnp.sin(angle)[None, :, None, :]
    pairs = x.reshape(x.shape[:-1] + (len(inv_freq), 2))
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1).reshape(x.shape)


def _attention(w, h, sizes):
    b, s, _ = h.shape
    heads, rank, eps = sizes["n_heads"], sizes["kv_lora_rank"], sizes.get("norm_eps", 1e-6)
    nope, rope, dv = sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"], sizes["v_head_dim"]
    if sizes.get("q_lora_rank"):
        q = _rms_norm(h @ w["wq_a"], w["q_a_norm"], eps) @ w["wq_b"]
    else:
        q = h @ w["wq"]
    q = q.reshape(b, s, heads, nope + rope)
    down = h @ w["w_kva"]
    c, k_r = _rms_norm(down[..., :rank], w["kv_norm"], eps), down[:, :, None, rank:]
    up = (c @ w["w_kvb"]).reshape(b, s, heads, nope + dv)
    k_n, v = up[..., :nope], up[..., nope:]
    q_n, q_r = q[..., :nope], q[..., nope:]
    if sizes.get("mla_rope_theta"):
        table = inv_frequencies(sizes)
        q_r, k_r = _rotate(q_r, table), _rotate(k_r, table)  # the key once: every head reads the same k_r
    block = QUERY_BLOCK if s % QUERY_BLOCK == 0 else s
    key_pos = jnp.arange(s)

    def rows(start):  # a block of queries against every key
        qn = jax.lax.dynamic_slice_in_dim(q_n, start, block, axis=1)
        qr = jax.lax.dynamic_slice_in_dim(q_r, start, block, axis=1)
        scores = jnp.einsum("bqhd,bkhd->bhqk", qn, k_n) + jnp.einsum("bqhd,bkd->bhqk", qr, k_r[:, :, 0])
        scores = scores / jnp.sqrt(jnp.float32(nope + rope))
        seen = key_pos[None, :] <= (start + jnp.arange(block))[:, None]
        probs = jax.nn.softmax(jnp.where(seen[None, None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", probs, v)

    # a block is computed again in a backward pass (the same numbers): its probabilities are never kept
    att = jax.lax.map(jax.checkpoint(rows), jnp.arange(0, s, block))  # [blocks, B, block, H, dv]
    return jnp.moveaxis(att, 0, 1).reshape(b, s, heads * dv) @ w["wo"]


def _experts(w, h, sizes):
    n_experts, top_k = sizes["n_experts"], sizes["top_k"]
    held = sizes.get("n_experts_held") or n_experts
    first = sizes.get("expert_share_index", 0) * held
    scores = jax.nn.sigmoid(h @ w["router"])
    biased = scores + w["router_bias"] if "router_bias" in w else scores
    kth = jnp.sort(biased, axis=-1)[..., n_experts - top_k]
    chosen = biased >= kth[..., None]  # the k largest (seeded random weights: no ties)
    weight = jnp.where(chosen, scores, 0.0)
    if sizes.get("router_renormalize", False):
        weight = weight / jnp.sum(weight, axis=-1, keepdims=True)
    weight = weight * sizes.get("routed_scaling_factor", 1.0)

    def add(y, e):  # the experts held here; the absent ones' part is left out
        mine = jax.lax.dynamic_index_in_dim(weight, first + e, axis=-1, keepdims=True)
        return y + mine * _swiglu(h, w["w_gate"][e], w["w_in"][e], w["w_out"][e]), None

    y, _ = jax.lax.scan(add, jnp.zeros_like(h), jnp.arange(held))
    if sizes.get("n_shared_experts"):
        y = y + _swiglu(h, w["shared_gate"], w["shared_in"], w["shared_out"])
    return y


def _layer(w, x, sizes, ff: str):
    eps = sizes.get("norm_eps", 1e-6)
    w = _f32(w)
    x = x + _attention(w, _rms_norm(x, w["ln1"], eps), sizes)
    h = _rms_norm(x, w["ln2"], eps)
    return x + (_experts(w, h, sizes) if ff == "experts" else _swiglu(h, w["w_gate"], w["w_in"], w["w_out"]))


def hidden(params: Dict[str, Any], tokens, sizes: Dict[str, Any]):
    """tokens [B, S] -> the main stack's hidden state after ``final_norm`` [B, S, d]."""
    x = _f32(params["embed"])[tokens]
    for (_, ff), w in zip(_kinds(sizes), layer_params(params, sizes)):
        x = _layer(w, x, sizes, ff)
    return _rms_norm(x, _f32(params["final_norm"]), sizes.get("norm_eps", 1e-6))


def mtp_hidden(params: Dict[str, Any], h, tokens, sizes: Dict[str, Any]):
    """The module's hidden state after its own final norm [B, S, d], from the
    main stack's ``h``: position i joins ``h_i`` with the embedding of token
    i + 1 (the last position wraps to token 0: it has no target and, the
    layer being causal, no reader)."""
    m, eps = _f32(params["mtp"]), sizes.get("norm_eps", 1e-6)
    e = _f32(params["embed"])[jnp.roll(tokens, -1, axis=1)]
    x = jnp.concatenate([_rms_norm(e, m["enorm"], eps), _rms_norm(h, m["hnorm"], eps)], axis=-1) @ m["eh_proj"]
    x = _layer(m["layer"], x, sizes, _kinds(sizes)[-1][1])
    return _rms_norm(x, m["final_norm"], eps)


def forward(params: Dict[str, Any], tokens, sizes: Dict[str, Any]):
    """tokens [B, S] -> the main head's logits [B, S, V], float32 throughout."""
    return hidden(params, tokens, sizes) @ _f32(params["out"])


def _nll(logits, tokens, ahead: int):
    """[B, S - ahead]: position i against token i + ahead."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp[:, :-ahead], tokens[:, ahead:, None], axis=-1)[..., 0]


def losses(params, tokens, sizes):
    """(next-token cross entropy, the module's second-next-token cross entropy
    or None) of one call, each a mean over its supervised positions."""
    h, out = hidden(params, tokens, sizes), _f32(params["out"])
    main = jnp.mean(_nll(h @ out, tokens, 1))
    if not (sizes.get("n_mtp_modules") and sizes.get("mtp_loss_weight", 0.1)):
        return main, None
    return main, jnp.mean(_nll(mtp_hidden(params, h, tokens, sizes) @ out, tokens, 2))


def _total(params, tokens, sizes):
    main, mtp = losses(params, tokens, sizes)
    return main if mtp is None else main + sizes.get("mtp_loss_weight", 0.1) * mtp


def per_sequence_loss(params, tokens, sizes):
    """The training loss of each sequence by itself, [B] — what the program's
    ``loss_fn`` gives for a batch of that one sequence. One sequence at a
    time, so that a block's scores are one sequence's."""
    return jax.lax.map(lambda seq: _total(params, seq[None], sizes), tokens)


def loss(params, tokens, sizes):
    """The training loss of one call, under ``highest`` matmul precision."""
    with jax.default_matmul_precision("highest"):
        return _total(params, tokens, sizes)
