"""Operations a training step needs, from shapes alone — the yardstick's
count, kept here so that no PR that claims a gain can change it.

Counted: the matrix multiplications of the forward pass and twice that for
the backward pass (6 operations per matmul parameter per token), and causal
attention's two score-sized matmuls at half the square. Not counted: the
``embed`` table (a gather, no matmul), norms, RoPE, softmax and the
optimizer (elementwise), and anything rematerialisation computes a second
time — model utilisation is about the operations the algorithm needs.

Sparse experts (``n_experts`` = E > 0, ``top_k`` = k, absent: 2, what
``ops/layers.moe_dispatch`` hard-wires): a token passes through the router
(a ``d x E`` matmul on the token path) and k experts of width ``d_ff``; the
program holds all E. Padding a group of tokens to an expert's capacity or to
a tile is the implementation's, not the algorithm's, and is not counted.
With ``n_experts`` absent or 0 every function returns what it returned
before experts were counted, to the digit.

What is read from a configuration's ``program.transformer_config``:
``d_model``, ``n_heads``, ``head_dim``, ``d_ff`` (one expert's width where
there are experts), ``n_layers``, ``vocab_size``, and optionally
``n_experts``, ``top_k`` and ``dtype`` (the compute dtype, absent:
bfloat16: the width of activations and gradients in the byte counts).

Bytes are counted the same way as operations: the least the algorithm has to
move between the chip and its memory, not what a program moves.

**A block this file cannot count brings its own.** A configuration whose
``program.opcount`` names ``<name>`` is counted by ``opcounts/<name>.py``, and
every reader asks :func:`for_config` which count is the configuration's; with
no such key it is this file, as it always was. What a count has to have is
:data:`INTERFACE`, what the readers use and no more: ``n_params(tc)``;
``flops_per_token_by_scope(tc, seq)``, whose keys are top-level scopes
(``xplane_meta.SCOPES``) and whose sum is the operations a trained token needs
(``mfu_pct``); ``bytes_per_step_by_scope(tc, batch, seq)``; and
``ffn_scopes(tc)``, the scopes that hold the feed-forward blocks (``ffn`` for
dense layers, ``moe`` for expert layers, both where a model has both), which
``ffn_device_s`` and ``ffn_roofline`` read together.
"""

from __future__ import annotations

import os
import sys
from typing import Any, Dict, Tuple

from common import load_module

__all__ = [
    "matmul_params", "flops_per_token", "flops_per_step", "n_params",
    "ffn_scope", "ffn_scopes", "flops_per_token_by_scope", "bytes_per_step_optimizer",
    "bytes_per_step_by_scope", "for_config", "INTERFACE",
]

# AdamW over f32 state, as the program keeps it: a parameter and its two
# moments are read and written (6 x 4 B). The gradient, read once, is counted
# beside them in the width it is computed in (``transformer_config.dtype``).
ADAMW_STATE_BYTES_PER_PARAM = 24
ITEMSIZE = {"bfloat16": 2, "float16": 2, "float32": 4}


def _sizes(tc: Dict[str, Any]):
    d = tc["d_model"]
    qkv = tc["n_heads"] * tc["head_dim"]
    return d, qkv, tc["d_ff"], tc["n_layers"], tc["vocab_size"]


def _ffn(tc: Dict[str, Any]):
    """(parameters held, parameters a token passes through) of one layer's
    feed-forward block: three SwiGLU matrices, or E experts of three behind
    a router of which a token sees k."""
    d, _, f, _, _ = _sizes(tc)
    experts = int(tc.get("n_experts") or 0)
    if not experts:
        return 3 * d * f, 3 * d * f
    k = int(tc.get("top_k", 2))
    return experts * 3 * d * f + d * experts, k * 3 * d * f + d * experts


def ffn_scope(tc: Dict[str, Any]) -> str:
    """The scope the program names its feed-forward block by
    (``models/transformer.py``: ``moe`` with experts, else ``ffn``)."""
    return "moe" if tc.get("n_experts") else "ffn"


def ffn_scopes(tc: Dict[str, Any]) -> Tuple[str, ...]:
    """The scopes that hold this configuration's feed-forward blocks: the one
    of :func:`ffn_scope` (this file counts layers of one kind)."""
    return (ffn_scope(tc),)


def n_params(tc: Dict[str, Any]) -> int:
    """Every parameter the program holds for this configuration
    (``models.transformer.init_params``: untied embed and out, two norm
    weights a layer, one final norm; with experts all E and the router)."""
    d, qkv, f, layers, vocab = _sizes(tc)
    per_layer = 3 * d * qkv + qkv * d + _ffn(tc)[0] + 2 * d
    return layers * per_layer + 2 * vocab * d + d


def matmul_params(tc: Dict[str, Any]) -> int:
    """Parameters that sit in a matmul on the token path: q, k, v, o and
    the three SwiGLU matrices per layer (with experts: the router and k
    experts' three), plus the output head. The ``embed`` table is a gather
    and is left out."""
    d, qkv, f, layers, vocab = _sizes(tc)
    return layers * (3 * d * qkv + qkv * d + _ffn(tc)[1]) + d * vocab


def flops_per_token_by_scope(tc: Dict[str, Any], seq: int) -> Dict[str, float]:
    """Forward + backward operations per trained token at sequence length
    ``seq``, by the scope of the program that runs them; the values add up
    to :func:`flops_per_token`. ``attn``: the four projections and causal
    attention — q·kᵀ and p·v are 2·seq·qkv operations each per token over
    the full square, a causal mask needs half of it. ``ffn`` or ``moe``
    (:func:`ffn_scope`): the feed-forward block. ``head_loss``: the output
    head. ``embed`` and ``optimizer`` run no matmul."""
    d, qkv, f, layers, vocab = _sizes(tc)
    attn_fwd = layers * 2 * (2 * seq * qkv) / 2
    return {
        "attn": 6.0 * layers * (3 * d * qkv + qkv * d) + 3.0 * attn_fwd,
        ffn_scope(tc): 6.0 * layers * _ffn(tc)[1],
        "head_loss": 6.0 * d * vocab,
    }


def flops_per_token(tc: Dict[str, Any], seq: int) -> float:
    """Forward + backward operations per trained token at sequence length
    ``seq``: 6 per matmul parameter (:func:`matmul_params`) and causal
    attention's scores, the sum over :func:`flops_per_token_by_scope`."""
    return sum(flops_per_token_by_scope(tc, seq).values())


def flops_per_step(tc: Dict[str, Any], batch: int, seq: int) -> float:
    return flops_per_token(tc, seq) * batch * seq


def bytes_per_step_optimizer(tc: Dict[str, Any]) -> int:
    """The least bytes the AdamW update of one step has to move between the
    chip and its memory: :data:`ADAMW_STATE_BYTES_PER_PARAM` and one gradient
    in the compute dtype for each parameter held (bf16: 26 B). A program
    that hands its update f32 gradients, as ``tft_apply`` is handed the
    exchange's, moves 28 B: the 2 B are its own, like what rematerialisation
    computes again, and show as a lower share."""
    per_param = ADAMW_STATE_BYTES_PER_PARAM + ITEMSIZE[tc.get("dtype", "bfloat16")]
    return per_param * n_params(tc)


def bytes_per_step_by_scope(tc: Dict[str, Any], batch: int, seq: int) -> Dict[str, float]:
    """The least bytes a step of ``batch`` x ``seq`` tokens has to move
    between the chip and its memory, by scope: the other bound of a scope's
    roofline beside :func:`flops_per_token_by_scope`. A matmul scope moves
    what crosses its edge, in the compute dtype: the weights it holds, read
    forward and backward, and their gradient written (with experts all E: a
    step's tokens reach every one); per layer its ``[tokens, d_model]`` input
    and output forward, and the input, the output's gradient and the input's
    gradient backward (the head has no such output: three). What a flash
    attention or a chunked head keeps on the chip (scores, logits) is not
    counted; a program that writes them out shows it as a lower share."""
    d, qkv, f, layers, vocab = _sizes(tc)
    c = ITEMSIZE[tc.get("dtype", "bfloat16")]
    act = batch * seq * d * c
    return {
        "attn": layers * (3 * c * (3 * d * qkv + qkv * d) + 5 * act),
        ffn_scope(tc): layers * (3 * c * _ffn(tc)[0] + 5 * act),
        "head_loss": 3 * c * d * vocab + 3 * act,
        "optimizer": bytes_per_step_optimizer(tc),
    }


# -- which count is a configuration's

OPCOUNTS_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)), "opcounts")
INTERFACE = ("n_params", "flops_per_token_by_scope", "bytes_per_step_by_scope", "ffn_scopes")


def for_config(config: Dict[str, Any]):
    """The count of a configuration (the configuration file, as a reader's
    ``run.config``): the module ``opcounts/<program.opcount>.py``, or this one
    where the key is absent. A named file that is missing, or lacks a function
    of :data:`INTERFACE`, is an error that names the file."""
    name = (config.get("program") or {}).get("opcount")
    if not name:
        return sys.modules[__name__]
    path = os.path.join(OPCOUNTS_DIR, name + ".py")
    if not os.path.exists(path):
        raise FileNotFoundError(f"program.opcount names {name!r} and there is no {path}")
    counts = load_module(path, "bench_opcount_" + name)
    missing = [fn for fn in INTERFACE if not callable(getattr(counts, fn, None))]
    if missing:
        raise AttributeError(f"{path} lacks {', '.join(missing)}: a count has {', '.join(INTERFACE)}")
    return counts
