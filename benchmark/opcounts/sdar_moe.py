"""The count of SDAR's decoder TRAINED BY BLOCK DIFFUSION, as the ``sdar-*``
configurations run it — every layer a grouped-query softmax layer (q and k
normed head by head) over the 2·S rows ``[noised ; clean]`` of a sequence of S
clean ids under the staircase mask, and a router over E experts of which a
share is held, k chosen a row, no shared expert and no dense layer:
``program.opcount`` ``sdar_moe``, with ``opcount.INTERFACE`` and, for the
readers of ``sdar_scopes.py``, the core's and the held experts' own counts.

**A count is per TRAINED token** — one of the ``batch x seq`` clean ids a step,
what ``tokens_per_step`` and ``mfu_pct`` count — and a trained token is TWO
rows through every layer and ONE row through the head:

* projections and router: 2 rows x 6 operations a matmul parameter;
* the core ``blockdiff_core``: the pairs the staircase lets through are
  ``S² + S·D`` of the ``(2S)²`` (a noised row D noised keys and the clean keys
  of the blocks before it, a clean row its block's and those before:
  ``S·D + D²·n(n-1)/2 + D²·n(n+1)/2`` with n = S/D), ``S + D`` a trained token
  a head; two score-sized products of ``2·head_dim`` operations a pair, forward,
  and twice that backward: ``12 x heads x head_dim x (S + D)`` a layer;
* held experts: 2 rows x ``k x held / E`` passes x 6 x ``3 x d x f``;
* the head: ONE row (the noised half's) x ``6 x d x V`` over the rows HELD.

What the algorithm needs and no more: recomputation, the tiles the kernel
visits beyond the live pairs (288 of 512 x 512 for 256.1 tiles' worth at
s8192) and padding are not counted, nothing is clamped. What is read from
``program.transformer_config``: ``d_model``, ``n_layers``, ``n_heads``,
``n_kv_heads``, ``head_dim``, ``moe_d_ff``, ``n_experts`` (the router's width),
``n_experts_held``, ``top_k``, ``vocab_size`` (the rows HELD),
``diffusion_block``, ``dtype``.

At the cell's size (``sdar-30b-a3b-1g`` at b2 x s8192, 6 layers; worked by hand
in ``tests/test_sdar.py``):

* parameters held: a layer = attention 2 x 2048 x 4096 + 2 x 2048 x 512 =
  18 874 368, router 262 144, two layer norms 4 096 and the q / k norms 256,
  16 experts x 3 x 2048 x 768 = 75 497 472: 94 638 336; six layers, two tables
  of 18 992 x 2048 and the final norm: 645 623 296.
* operations a trained token: projections 2 x 6 x 6 x 18 874 368 = 1 358.95 M;
  core 6 x 12 x 32 x 128 x 8 196 = 2 417.10 M; router 2 x 6 x 6 x 262 144 =
  18.87 M; held experts at 8 x 16 / 128 = 1 pass: 2 x 6 x 6 x 4 718 592 =
  339.74 M; head 6 x 2048 x 18 992 = 233.37 M: 4 368.04 M, 71.57 TFLOP a step
  of 16 384 trained tokens, 0.363 s at the bf16 peak. The core is 55.3 % of it
  and ``attn`` 86.4 %.
* the core's bytes at its own edge (q, k, v, o and their gradients over 32 768
  rows, 6 layers): 10.87 GB = 13.3 ms against 39.6 TFLOP = 201 ms: bound by
  operations.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

ADAMW_STATE_BYTES_PER_PARAM = 24
ITEMSIZE = {"bfloat16": 2, "float16": 2, "float32": 4}
CORE = "blockdiff_core"
ROWS_PER_TOKEN = 2  # a trained token's noised row and its clean row


def attn_params(tc: Dict[str, Any]) -> int:
    """The four projections of one layer."""
    d, hd, kv = tc["d_model"], tc["head_dim"], tc.get("n_kv_heads") or tc["n_heads"]
    return 2 * d * tc["n_heads"] * hd + 2 * d * kv * hd


def expert_params(tc: Dict[str, Any]) -> Dict[str, float]:
    """One expert layer: ``router`` and ``routed`` (held) parameters, and
    ``per_row``: the routed parameters a ROW passes through here."""
    d, f, e = tc["d_model"], tc.get("moe_d_ff") or tc["d_ff"], tc["n_experts"]
    held = tc.get("n_experts_held") or e
    return {"router": d * e, "routed": held * 3 * d * f, "per_row": tc["top_k"] * held / e * 3 * d * f}


def n_params(tc: Dict[str, Any]) -> int:
    """Every parameter HELD here (``models.transformer.init_params``): the
    layers' projections, router, held experts, two norms and the q / k norms'
    one weight a head's lanes each; the final norm and the two tables."""
    d, ex = tc["d_model"], expert_params(tc)
    layer = attn_params(tc) + ex["router"] + ex["routed"] + 2 * d + 2 * tc["head_dim"]
    return int(tc["n_layers"] * layer + d + 2 * tc["vocab_size"] * d)


def keys_per_token(tc: Dict[str, Any], seq: int) -> float:
    """Pairs the staircase lets through a trained token a head: (S² + S·D) / S."""
    return float(seq + tc["diffusion_block"])


def core_flops_per_token(tc: Dict[str, Any], seq: int) -> Dict[str, float]:
    """Forward + backward operations a trained token of the core, all layers."""
    return {CORE: 3.0 * tc["n_layers"] * tc["n_heads"] * 2 * keys_per_token(tc, seq) * 2 * tc["head_dim"]}


def core_bytes_per_step(tc: Dict[str, Any], batch: int, seq: int) -> Dict[str, float]:
    """The least bytes the core moves at its own edge a step, all layers. A row,
    H query heads and G key/value heads of D lanes in the compute dtype: forward
    q and the output (2H) and k, v (2G); backward those and the output's
    gradient read (3H + 2G), dq, dk and dv written (H + 2G) — over 2·S rows."""
    c, kv = ITEMSIZE[tc.get("dtype", "bfloat16")], tc.get("n_kv_heads") or tc["n_heads"]
    rows = ROWS_PER_TOKEN * batch * seq
    return {CORE: float(tc["n_layers"] * rows * c * tc["head_dim"] * (6 * tc["n_heads"] + 6 * kv))}


def experts_flops_per_token(tc: Dict[str, Any]) -> float:
    """Forward + backward operations a trained token of the held experts alone, all layers."""
    return 6.0 * ROWS_PER_TOKEN * tc["n_layers"] * expert_params(tc)["per_row"]


def experts_bytes_per_step(tc: Dict[str, Any], batch: int, seq: int) -> float:
    """The held experts' weights read forward and backward and their gradient
    written, and the five ``[rows, d_model]`` at the scope's edge, all layers."""
    c = ITEMSIZE[tc.get("dtype", "bfloat16")]
    act = ROWS_PER_TOKEN * batch * seq * tc["d_model"] * c
    return float(tc["n_layers"] * (3 * c * expert_params(tc)["routed"] + 5 * act))


def ffn_scopes(tc: Dict[str, Any]) -> Tuple[str, ...]:
    return ("moe",)


def flops_per_token_by_scope(tc: Dict[str, Any], seq: int) -> Dict[str, float]:
    layers, ex = tc["n_layers"], expert_params(tc)
    return {
        "attn": 6.0 * ROWS_PER_TOKEN * layers * attn_params(tc) + core_flops_per_token(tc, seq)[CORE],
        "moe": 6.0 * ROWS_PER_TOKEN * layers * ex["router"] + experts_flops_per_token(tc),
        "head_loss": 6.0 * tc["d_model"] * tc["vocab_size"],
    }


def flops_per_token(tc: Dict[str, Any], seq: int) -> float:
    return sum(flops_per_token_by_scope(tc, seq).values())


def bytes_per_step_by_scope(tc: Dict[str, Any], batch: int, seq: int) -> Dict[str, float]:
    d, layers, ex = tc["d_model"], tc["n_layers"], expert_params(tc)
    c = ITEMSIZE[tc.get("dtype", "bfloat16")]
    act = batch * seq * d * c  # one [trained tokens, d_model]; the layers see two rows a token
    return {
        "attn": layers * (3.0 * c * attn_params(tc) + 5 * ROWS_PER_TOKEN * act),
        "moe": 3.0 * c * layers * ex["router"] + experts_bytes_per_step(tc, batch, seq),
        "head_loss": 3.0 * c * d * tc["vocab_size"] + 3 * act,
        "optimizer": float((ADAMW_STATE_BYTES_PER_PARAM + c) * n_params(tc)),
    }
