"""The count of a LOOPED dense decoder: one stack of sandwich-normed layers
(multi-head softmax attention, SwiGLU) applied ``ut_steps`` = T times a step
over the SAME weights, an exit gate a loop step, and a head that every loop
step's state passes through: ``program.opcount`` ``ouro``, with
``opcount.INTERFACE`` and, for the post-norms (``loop_post_norm_device_s``),
:func:`post_norm_flops_per_token` and :func:`post_norm_bytes_per_step`.

What is read from ``program.transformer_config``: ``d_model``, ``n_layers``,
``n_heads``, ``head_dim``, ``d_ff``, ``vocab_size``, ``ut_steps``,
``sandwich_norm``, ``exit_gate`` and ``dtype``.

Counted as ``opcount.py`` counts: 6 operations per matmul parameter on a
token's path (forward, and twice that backward), what the algorithm needs and
no more, recomputation not counted, never clamped — and a parameter that a
token passes T times is on its path T times:

* **attn**, a layer application: the four projections ``6 · 4 d·HD`` and the
  causal core's two score-sized products at half the square, ``3 · 2 · (2 s HD) / 2``;
  times ``T · n_layers`` applications.
* **ffn**, a layer application: ``6 · 3 d·d_ff``; times ``T · n_layers``.
* **head_loss**: ``6 · d · V`` a pass, T passes (the expected loss reads every
  exit). The gate is a ``[d, 1]`` projection a loop step: ``6 d T``, counted.
* ``embed`` is a gather; norms (five kinds), the rotation, softmax, the exit
  distribution and the optimizer are elementwise and not counted.

Bytes, the other bound of a scope's roofline: a layer's weights are read
forward and backward in EVERY turn (turn t + 1 needs turn t's output: nothing
of a 100 MB layer stays on the chip between turns) and their gradient, summed
over the turns, written once — ``(2T + 1)`` passes of the weights held — and
``opcount.py``'s five ``[tokens, d_model]`` arrays at the scope's edge an
application; the head's table read forward and backward and its gradient
written ONCE (the exits pass through it one after another, whatever T: 3) and
three such arrays a pass; the optimizer's 24 B of float32 state and one
gradient in the compute dtype a parameter HELD — once a step, however often
the parameter was used.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

ADAMW_STATE_BYTES_PER_PARAM = 24
ITEMSIZE = {"bfloat16": 2, "float16": 2, "float32": 4}
POST_NORM = "post_norm"


def _turns(tc: Dict[str, Any]) -> int:
    return int(tc.get("ut_steps", 1))


def layer_params(tc: Dict[str, Any]) -> Dict[str, int]:
    """One layer: ``attn`` and ``ffn`` matmul parameters, and its ``norms``."""
    d, qkv = tc["d_model"], tc["n_heads"] * tc["head_dim"]
    return {
        "attn": 3 * d * qkv + qkv * d,
        "ffn": 3 * d * tc["d_ff"],
        "norms": (4 if tc.get("sandwich_norm") else 2) * d,
    }


def n_params(tc: Dict[str, Any]) -> int:
    """Every parameter HELD (``models.transformer.init_params``): a layer ONCE,
    whatever ``ut_steps``; both tables; the final norm; the gate's d + 1."""
    d = tc["d_model"]
    gate = d + 1 if tc.get("exit_gate") else 0
    return tc["n_layers"] * sum(layer_params(tc).values()) + 2 * tc["vocab_size"] * d + d + gate


def ffn_scopes(tc: Dict[str, Any]) -> Tuple[str, ...]:
    return ("ffn",)


def flops_per_token_by_scope(tc: Dict[str, Any], seq: int) -> Dict[str, float]:
    d, qkv, per = tc["d_model"], tc["n_heads"] * tc["head_dim"], layer_params(tc)
    applications = _turns(tc) * tc["n_layers"]
    core = 3.0 * 2 * (2 * seq * qkv) / 2
    gate = 6.0 * d if tc.get("exit_gate") else 0.0
    return {
        "attn": applications * (6.0 * per["attn"] + core),
        "ffn": applications * 6.0 * per["ffn"],
        "head_loss": _turns(tc) * (6.0 * d * tc["vocab_size"] + gate),
    }


def flops_per_token(tc: Dict[str, Any], seq: int) -> float:
    return sum(flops_per_token_by_scope(tc, seq).values())


def bytes_per_step_by_scope(tc: Dict[str, Any], batch: int, seq: int) -> Dict[str, float]:
    d, per, turns = tc["d_model"], layer_params(tc), _turns(tc)
    c = ITEMSIZE[tc.get("dtype", "bfloat16")]
    act = batch * seq * d * c
    applications = turns * tc["n_layers"]
    half_norms = per["norms"] // 2  # a sublayer's own
    return {
        "attn": float(tc["n_layers"] * (2 * turns + 1) * c * (per["attn"] + half_norms) + applications * 5 * act),
        "ffn": float(tc["n_layers"] * (2 * turns + 1) * c * (per["ffn"] + half_norms) + applications * 5 * act),
        "head_loss": float(3 * c * d * tc["vocab_size"] + turns * 3 * act),
        "optimizer": float((ADAMW_STATE_BYTES_PER_PARAM + c) * n_params(tc)),
    }


def post_norm_flops_per_token(tc: Dict[str, Any]) -> Dict[str, float]:
    """Forward + backward operations a token of the layers' two OUTPUT norms,
    all applications: a norm is a square, a sum, a scale and the weight's
    product a lane — 4 d forward, times three with the backward. Elementwise,
    so outside ``attn`` / ``ffn``'s counts; here so that the metric's reader can
    say which bound is nearer: the bytes, by three orders."""
    if not tc.get("sandwich_norm"):
        return {}
    return {POST_NORM: 3.0 * 2 * _turns(tc) * tc["n_layers"] * 4 * tc["d_model"]}


def post_norm_bytes_per_step(tc: Dict[str, Any], batch: int, seq: int) -> Dict[str, float]:
    """The least bytes the two output norms move at their own edge a step, all
    applications, ``[tokens, d_model]`` arrays in the compute dtype: forward the
    sublayer's output read and the normed one written (2); backward the input
    and the output's gradient read, the input's gradient written (3). The
    forward that ``remat`` runs a second time is the program's and not counted;
    a program that fuses the norm into the product before it moves less."""
    if not tc.get("sandwich_norm"):
        return {}
    c = ITEMSIZE[tc.get("dtype", "bfloat16")]
    return {POST_NORM: float(2 * _turns(tc) * tc["n_layers"] * 5 * batch * seq * tc["d_model"] * c)}
