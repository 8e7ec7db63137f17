"""The count of a hybrid of Mamba-2 state-space mixers (one decay a head a
position, ONE B and ONE C a position for all heads, a skip, a gated norm over
all the channels) and unrotated grouped-query softmax layers, every layer with
one dense SwiGLU, over a TIED table — ``program.opcount`` ``granite_hybrid`` —
with ``opcount.INTERFACE`` and, for the readers of the two cores,
:func:`core_flops_per_token` and :func:`core_bytes_per_step` (``ssd_core``,
``global_core``).

What is read from ``program.transformer_config``: ``d_model``, ``n_layers``,
``ssd_layers``, ``ssd_n_heads``, ``ssd_head_dim``, ``ssd_state_dim``,
``conv_kernel``, ``n_heads``, ``n_kv_heads``, ``head_dim``,
``d_ff``, ``tie_embeddings``, ``vocab_size`` (the rows HELD) and ``dtype``.

Counted as ``opcount.py`` counts: 6 operations per matmul parameter on a
token's path (forward, and twice that backward), what the algorithm needs and
no more, recomputation and padding not counted, never clamped.

* **State-space mixer**, a layer: the projections in (``d x (2 H P + 2 N + H)``:
  z, x, B, C, dt) and out (``H P x d``); the core is the CHUNKED rule's work at
  the program's chunk of L positions, whatever implements it: ``C·Bᵀ`` once a
  chunk for ALL heads (2 L N a position), then a head's apply of a chunk's
  pairs to its values (2 L P), its write into the state (2 N P) and its read of
  the state at the chunk's start (2 N P) — forward, three times that with the
  backward. The convolution, the step, the decay, the skip, the gate and the
  norm are elementwise and not counted.
* **Softmax attention**, a layer: ``wq`` (``d x H D``), ``wk``, ``wv``
  (``d x G D``), ``wo`` (``H D x d``); the core is causal attention's two
  score-sized products at ``(s + 1) / 2`` keys a query, ``2 · keys · 2D`` a head
  a token forward, times three.
* one SwiGLU of ``d_ff`` a layer; the head over the vocabulary rows held (the
  tied table is counted ONCE among the parameters, and its product is the
  head's); ``embed`` is a gather.

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
SSD_BLOCK = 1024  # positions between two states the mixer keeps (transformer._KDA_BLOCK)
SSD_CHUNK = 128  # positions of a chunk of the core (transformer._SSD_CHUNK); ISSUE 69 counted at 256: 4 847 M a token


def _layers(tc: Dict[str, Any]) -> Dict[str, int]:
    """How many layers have each mixer; every layer has a dense SwiGLU."""
    ssd = len(tc.get("ssd_layers", ()))
    return {"ssd": ssd, "full": tc["n_layers"] - ssd}


def _ssd_sizes(tc: Dict[str, Any]) -> Tuple[int, int, int, int]:
    """(heads, a head's width, the state's width, the chunk) of the state-space mixers."""
    return tc["ssd_n_heads"], tc["ssd_head_dim"], tc["ssd_state_dim"], SSD_CHUNK


def ssd_params(tc: Dict[str, Any]) -> Tuple[int, int]:
    """(matmul parameters on a token's path, parameters held) of one state-space mixer."""
    d = tc["d_model"]
    heads, hd, n_state, _ = _ssd_sizes(tc)
    inner = heads * hd
    mixed = inner + 2 * n_state
    matmul = d * (inner + mixed + heads) + inner * d
    # the convolution's taps and bias over x | B | C; dt_bias, a_log and the skip a head; the gated norm's weight
    return matmul, matmul + (tc.get("conv_kernel", 4) + 1) * mixed + 3 * heads + inner


def attn_params(tc: Dict[str, Any]) -> Tuple[int, int]:
    """(matmul parameters, parameters held) of one softmax layer: no bias, no norm of its own."""
    d, hd, heads = tc["d_model"], tc["head_dim"], tc["n_heads"]
    kv = tc.get("n_kv_heads") or heads
    matmul = 2 * d * heads * hd + 2 * d * kv * hd
    return matmul, matmul


def ffn_params(tc: Dict[str, Any]) -> int:
    return 3 * tc["d_model"] * tc["d_ff"]


def layer_params(tc: Dict[str, Any]) -> Dict[str, int]:
    """Parameters held by one layer of each kind: mixer, SwiGLU and the two norms."""
    rest = ffn_params(tc) + 2 * tc["d_model"]
    return {"ssd": ssd_params(tc)[1] + rest, "full": attn_params(tc)[1] + rest}


def n_params(tc: Dict[str, Any]) -> int:
    """Every parameter HELD here (``models.transformer.init_params``); a tied table once."""
    d, of, per = tc["d_model"], _layers(tc), layer_params(tc)
    tables = 1 if tc.get("tie_embeddings") else 2
    return int(of["ssd"] * per["ssd"] + of["full"] * per["full"] + d + tables * tc["vocab_size"] * d)


def core_flops_per_token(tc: Dict[str, Any], seq: int) -> Dict[str, float]:
    """Forward + backward operations a token of the two cores, all layers."""
    of = _layers(tc)
    heads, hd, n_state, chunk = _ssd_sizes(tc)
    ssd_fwd = 2 * chunk * n_state + heads * (2 * chunk * hd + 2 * 2 * n_state * hd)
    softmax_fwd = tc["n_heads"] * 2 * ((seq + 1) / 2) * 2 * tc["head_dim"]
    return {"ssd_core": 3.0 * of["ssd"] * ssd_fwd, "global_core": 3.0 * of["full"] * softmax_fwd}


def core_bytes_per_step(tc: Dict[str, Any], batch: int, seq: int) -> Dict[str, float]:
    """The least bytes the two cores move at their own edge a step, all layers.
    State-space, per token: the values Δ·x over the heads and B and C (one of
    each) in the compute dtype, the float32 log-decay a head, the output over
    the heads; backward the same read again with the output's gradient in the
    output's place, and the four gradients written; and a block: one float32
    state a head written forward, read backward, its gradient handed on.
    Softmax, per token, H query heads and G key/value heads of D lanes:
    forward q and the output (2H) and k, v (2G); backward those and the
    output's gradient read (3H + 2G), dq, dk and dv written (H + 2G)."""
    of, c = _layers(tc), ITEMSIZE[tc.get("dtype", "bfloat16")]
    tokens = batch * seq
    heads, hd, n_state, _ = _ssd_sizes(tc)
    operands = (heads * hd + 2 * n_state) * c + heads * 4  # Δ·x, B, C; the log-decay
    states = 3 * 4 * heads * n_state * hd * batch * -(-seq // SSD_BLOCK)
    ssd = tokens * (3 * operands + 2 * heads * hd * c) + states
    kv = tc.get("n_kv_heads") or tc["n_heads"]
    softmax = tokens * c * tc["head_dim"] * (6 * tc["n_heads"] + 6 * kv)
    return {"ssd_core": float(of["ssd"] * ssd), "global_core": float(of["full"] * softmax)}


def ffn_scopes(tc: Dict[str, Any]) -> Tuple[str, ...]:
    return ("ffn",)


def flops_per_token_by_scope(tc: Dict[str, Any], seq: int) -> Dict[str, float]:
    d, of = tc["d_model"], _layers(tc)
    return {
        "attn": 6.0 * (of["ssd"] * ssd_params(tc)[0] + of["full"] * attn_params(tc)[0])
        + sum(core_flops_per_token(tc, seq).values()),
        "ffn": 6.0 * tc["n_layers"] * ffn_params(tc),
        "head_loss": 6.0 * d * tc["vocab_size"],
    }


def flops_per_token(tc: Dict[str, Any], seq: int) -> float:
    return sum(flops_per_token_by_scope(tc, seq).values())


def bytes_per_step_by_scope(tc: Dict[str, Any], batch: int, seq: int) -> Dict[str, float]:
    d, of = tc["d_model"], _layers(tc)
    c = ITEMSIZE[tc.get("dtype", "bfloat16")]
    act = batch * seq * d * c
    return {
        "attn": 3.0 * c * (of["ssd"] * ssd_params(tc)[1] + of["full"] * attn_params(tc)[1]) + 5 * act * tc["n_layers"],
        "ffn": tc["n_layers"] * (3.0 * c * ffn_params(tc) + 5 * act),
        "head_loss": 3.0 * c * d * tc["vocab_size"] + 3 * act,
        "optimizer": float((ADAMW_STATE_BYTES_PER_PARAM + c) * n_params(tc)),
    }
