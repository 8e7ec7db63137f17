"""The count of Keye-VL-2.0-30B-A3B's language model TRAINED UNDER ITS LEARNED
KEY SELECTOR, as the ``keye-*`` configurations run it — every layer a
grouped-query softmax layer (q and k normed head by head) whose core attends
to the k keys a lightning indexer picks, the indexer trained by its own KL
term, and a router over E experts of which a share is held, k chosen a row, no
shared expert and no dense layer: ``program.opcount`` ``keye_sparse``, with
``opcount.INTERFACE`` and, for the readers of ``keye_scopes.py``, the core's,
the selector's term's and the held experts' own counts.

**The core is counted on SELECTED pairs** — ``Σ_t min(t + 1, k)`` a sequence,
``keys_per_token`` a query — and the indexer and the term on what the
equations make them compute, so ``mfu_pct`` and every share read the same work
whatever implements it: a kernel that walks dense tiles under a table reads
LOW (134 M operations a token a layer as executed against 31.5 M counted,
forward), never over 100 %. A trained token, a layer (a matmul parameter is 2
operations forward and 4 backward; nothing is counted twice for ``remat``):

* projections, router: 6 operations a matmul parameter; the indexer's three
  projections too (``d x (H_I·D_I + D_I + H_I)``);
* the core ``sparse_core``: two score-sized products of ``2·head_dim``
  operations a SELECTED pair a head, forward, and twice that backward:
  ``12 x heads x head_dim x keys_per_token``;
* the indexer's scores: ``2·D_I`` operations a CAUSAL pair a head (every causal
  key is scored before any is selected: ``(S + 1)/2`` a query), and 2 more a
  pair a head for the weighted sum over heads — FORWARD, under ``indexer``;
* the indexer's term ``indexer_loss``: its target, one score-sized product on
  the selected pairs over the ``heads`` query heads, FORWARD ONLY (the target
  is detached): ``2 x heads x head_dim x keys_per_token``; and the backward of
  the scores, which only this term asks for: ``2 x`` their forward;
* held experts: ``k x held / E`` passes x 6 x ``3 x d x f``;
* the head: ``6 x d x V`` over the rows HELD.

What is read from ``program.transformer_config``: ``d_model``, ``n_layers``,
``n_heads``, ``n_kv_heads``, ``head_dim``, ``moe_d_ff``, ``n_experts`` (the
router's width), ``n_experts_held``, ``top_k``, ``vocab_size`` (the rows
HELD), ``sparse_topk``, ``indexer_heads``, ``indexer_head_dim``, ``dtype``.

At the cell's size (``keye-vl-2_0-30b-a3b-1g`` at b1 x s16384, 6 layers; worked
by hand in ``tests/test_keye.py``):

* parameters held: a layer = attention 18 874 368, the indexer 2 260 992,
  router 262 144, two layer norms 4 096 and the q / k norms 256, 16 experts x
  4 718 592 = 75 497 472: 96 899 328; six layers, two tables of 18 992 x 2048
  and the final norm: 659 189 248.
* keys a query: (2048·2049/2 + 14 336·2048) / 16 384 = 1 920.06 of 8 192.5 causal.
* operations a trained token a layer: projections 113.25 M; the indexer's
  projections 13.57 M; core 12 x 32 x 128 x 1 920.06 = 94.38 M; the indexer's
  scores 16 x (128 + 2) x 8 192.5 = 17.04 M; the term 2 x 32 x 128 x 1 920.06 =
  15.73 M and the scores' backward 34.08 M; router 1.57 M; held experts 28.31 M:
  317.93 M; six layers and the head's 233.37 M: 2 140.9 M a token, 35.08 TFLOP a
  step of 16 384 tokens, 0.178 s at the bf16 peak.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

ADAMW_STATE_BYTES_PER_PARAM = 24
ITEMSIZE = {"bfloat16": 2, "float16": 2, "float32": 4}
CORE, TERM = "sparse_core", "indexer_loss"


def attn_params(tc: Dict[str, Any]) -> int:
    """The four projections of one layer."""
    d, hd, kv = tc["d_model"], tc["head_dim"], tc.get("n_kv_heads") or tc["n_heads"]
    return 2 * d * tc["n_heads"] * hd + 2 * d * kv * hd


def indexer_params(tc: Dict[str, Any]) -> int:
    """The selector's three projections of one layer: queries, ONE key, a weight a head."""
    hi, di = tc["indexer_heads"], tc["indexer_head_dim"]
    return tc["d_model"] * (hi * di + di + hi)


def expert_params(tc: Dict[str, Any]) -> Dict[str, float]:
    """One expert layer: ``router`` and ``routed`` (held) parameters, and
    ``per_row``: the routed parameters a row passes through here."""
    d, f, e = tc["d_model"], tc.get("moe_d_ff") or tc["d_ff"], tc["n_experts"]
    held = tc.get("n_experts_held") or e
    return {"router": d * e, "routed": held * 3 * d * f, "per_row": tc["top_k"] * held / e * 3 * d * f}


def n_params(tc: Dict[str, Any]) -> int:
    """Every parameter HELD here (``models.transformer.init_params``)."""
    d, ex = tc["d_model"], expert_params(tc)
    layer = attn_params(tc) + indexer_params(tc) + ex["router"] + ex["routed"] + 2 * d + 2 * tc["head_dim"]
    return int(tc["n_layers"] * layer + d + 2 * tc["vocab_size"] * d)


def keys_per_token(tc: Dict[str, Any], seq: int) -> float:
    """Selected pairs a query: ``Σ_t min(t + 1, k) / S``."""
    k = min(tc["sparse_topk"], seq)
    return (k * (k + 1) / 2 + (seq - k) * k) / seq


def causal_keys_per_token(seq: int) -> float:
    return (seq + 1) / 2


def core_flops_per_token(tc: Dict[str, Any], seq: int) -> Dict[str, float]:
    """Operations a trained token, all layers: the core on selected pairs,
    forward + backward; the selector's term — its target forward and the
    backward of the scores it alone asks for."""
    layers, heads, hd = tc["n_layers"], tc["n_heads"], tc["head_dim"]
    return {
        CORE: 3.0 * layers * heads * 2 * keys_per_token(tc, seq) * 2 * hd,
        TERM: layers * (heads * keys_per_token(tc, seq) * 2 * hd + 2.0 * indexer_scores_flops_per_token(tc, seq)),
    }


def indexer_scores_flops_per_token(tc: Dict[str, Any], seq: int) -> float:
    """Forward operations a token a layer of the selector's scores: every causal
    pair, ``2·D_I`` a head and 2 more for the weighted sum over heads."""
    return tc["indexer_heads"] * (2 * tc["indexer_head_dim"] + 2) * causal_keys_per_token(seq)


def core_bytes_per_step(tc: Dict[str, Any], batch: int, seq: int) -> Dict[str, float]:
    """The least bytes at each part's own edge a step, all layers. The core, a
    row of H query heads and G key/value heads of D lanes in the compute dtype:
    forward q and the output (2H) and k, v (2G); backward those and the output's
    gradient read (3H + 2G), dq, dk and dv written (H + 2G). The term: q and k
    of the core and the selector's rotated projections read, their three
    cotangents written."""
    c, kv = ITEMSIZE[tc.get("dtype", "bfloat16")], tc.get("n_kv_heads") or tc["n_heads"]
    rows, hd = batch * seq, tc["head_dim"]
    idx = tc["indexer_heads"] * tc["indexer_head_dim"] + tc["indexer_head_dim"]
    return {
        CORE: float(tc["n_layers"] * rows * c * hd * (6 * tc["n_heads"] + 6 * kv)),
        TERM: float(tc["n_layers"] * rows * (c * hd * (tc["n_heads"] + kv) + 2 * c * idx + 8 * tc["indexer_heads"])),
    }


def experts_flops_per_token(tc: Dict[str, Any]) -> float:
    """Forward + backward operations a trained token of the held experts alone, all layers."""
    return 6.0 * tc["n_layers"] * expert_params(tc)["per_row"]


def experts_bytes_per_step(tc: Dict[str, Any], batch: int, seq: int) -> float:
    """The held experts' weights read forward and backward and their gradient
    written, and the five ``[rows, d_model]`` at the scope's edge, all layers."""
    c = ITEMSIZE[tc.get("dtype", "bfloat16")]
    act = batch * seq * tc["d_model"] * c
    return float(tc["n_layers"] * (3 * c * expert_params(tc)["routed"] + 5 * act))


def ffn_scopes(tc: Dict[str, Any]) -> Tuple[str, ...]:
    return ("moe",)


def flops_per_token_by_scope(tc: Dict[str, Any], seq: int) -> Dict[str, float]:
    layers, ex = tc["n_layers"], expert_params(tc)
    parts = core_flops_per_token(tc, seq)
    return {
        "attn": 6.0 * layers * (attn_params(tc) + indexer_params(tc)) + parts[CORE] + parts[TERM]
        + layers * indexer_scores_flops_per_token(tc, seq),
        "moe": 6.0 * layers * ex["router"] + experts_flops_per_token(tc),
        "head_loss": 6.0 * tc["d_model"] * tc["vocab_size"],
    }


def flops_per_token(tc: Dict[str, Any], seq: int) -> float:
    return sum(flops_per_token_by_scope(tc, seq).values())


def bytes_per_step_by_scope(tc: Dict[str, Any], batch: int, seq: int) -> Dict[str, float]:
    d, layers, ex = tc["d_model"], tc["n_layers"], expert_params(tc)
    c = ITEMSIZE[tc.get("dtype", "bfloat16")]
    act = batch * seq * d * c
    return {
        "attn": layers * (3.0 * c * (attn_params(tc) + indexer_params(tc)) + 5 * act),
        "moe": 3.0 * c * layers * ex["router"] + experts_bytes_per_step(tc, batch, seq),
        "head_loss": 3.0 * c * d * tc["vocab_size"] + 3 * act,
        "optimizer": float((ADAMW_STATE_BYTES_PER_PARAM + c) * n_params(tc)),
    }
