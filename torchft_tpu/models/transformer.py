"""Decoder-only transformer LM — the flagship model.

Pure-JAX pytree params with explicit ``PartitionSpec``s per leaf:

* ``tp``  — attention heads and FFN hidden dim (megatron-style; XLA/GSPMD
  inserts the all-reduces from the shardings, nothing manual here)
* ``sp``  — sequence axis via ring attention (ops/attention.py)
* ``pp``  — layer stages via the microbatched ppermute ring
  (parallel/pipeline.py); stage params carry a leading [pp, Lp] axis
* ``ep``  — MoE experts over chips (``ep`` > 1 ONLY: the top-2 capacity
  einsum dispatch, ``_ffn_moe_ep``; on ``ep`` = 1 the experts are the
  dropless top-k of ``ops/layers.moe_dropless``)
* ``dp``/``fsdp`` — batch / parameter sharding

Layers within a stage run under ``lax.scan`` (one compile per stage, not
per layer) with ``jax.checkpoint`` rematerialization — compile time and
HBM both scale O(1) in depth.

A model whose layers are not all of one kind DECLARES its pattern
(``TransformerConfig.kda_layers`` / ``gdn_layers`` / ``mla_layers`` /
``conv_layers`` / ``n_dense_layers``): per layer a sequence mixer — ``full``
softmax attention with RoPE (its output gated lane by lane under
``attn_output_gate``), ``window``
(the same over a band of ``window`` keys, ``window_layers``; each of the two
kinds with its own query heads over ``n_kv_heads`` grouped key/value heads
and its own rotation), ``kda`` (gated delta-rule linear attention,
``ops/kda.py``), ``gdn`` (the same rule with one decay a head and key heads
shared by value heads), ``ssd`` (a Mamba-2 state-space mixer, ``ssd_layers``: the same
scalar decay with no correction, one B and one C a position for all heads,
a ``D`` skip and a gated norm over all the channels) or ``mla`` (latent attention: without positions, or with
the query's and the shared key's last lanes rotated under ``mla_rope_theta``;
the query one projection or low-rank under ``q_lora_rank``) or ``conv`` (a
gated causal short convolution: no query, key, score or state) — and a
feed-forward, ``dense`` or ``experts``.
Its parameters are grouped by kind of layer and the stack runs the leading
layers one by one, then ``lax.scan`` over whole periods of the pattern with
the period unrolled inside the body (:func:`layer_pattern`). A model of one
kind is a period of one: today's tree and today's scan.

A multi-token-prediction module (``n_mtp_modules``) is a subtree
``params["mtp"]`` run after the main stack: one more layer of the last
layer's kind on ``[norm(Emb(t_{i+1})) ; norm(h_i)]·eh_proj``, its output
through the main ``out`` table against the second-next token, its loss added
at ``mtp_loss_weight`` (:func:`_mtp_hidden`).

A LOOPED stack (``ut_steps`` = T > 1, a model of one kind on ``pp`` = 1) runs
the same layers T times a step over the same leaves — a ``lax.scan`` over loop
steps around the scan over layers, the final norm at the end of each turn and
its output carried into the next; under ``exit_gate`` a learned gate a loop
step says how much probability leaves there and the loss is the expectation
of the cross entropy over the T exits less the exit distribution's entropy
(:func:`_exit_loss`), the exits through one weighted pass of the head.
``sandwich_norm`` (any kind of layer) norms a layer's two parts' outputs too.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from torchft_tpu.ops.attention import (
    attention,
    chunked_attention,
    indexer_scores,
    ring_attention,
    ring_attention_local,
    select_top,
    selected_head_probs,
    selected_softmax,
    selection_kl,
)
from torchft_tpu.ops.kda import gdn_chunked, gdn_core, kda_chunked, short_conv, ssd_chunked, ssd_core
from torchft_tpu.ops.layers import (
    moe_dispatch,
    moe_dropless,
    moe_dropless_held,
    rms_norm,
    rotary_embed,
    swiglu,
    yarn_inv_freq,
)

__all__ = [
    "TransformerConfig",
    "PRESETS",
    "layer_pattern",
    "init_params",
    "selections",
    "param_specs",
    "forward",
    "loss_fn",
    "loss_and_stats",
]


# the mixers that are causal softmax attention over projected q, k, v: rotated and global, rotated under a band,
# global without positions — one set of leaves, one function (``_mix_full``)
_SOFTMAX_MIXERS = ("full", "window", "nope")
# a gated feed-forward's activation, by ``TransformerConfig.expert_activation``
_ACTIVATIONS = {"silu": jax.nn.silu, "relu": jax.nn.relu}


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32000
    d_model: int = 512
    n_layers: int = 8
    n_heads: int = 8
    head_dim: int = 64
    d_ff: int = 1408
    n_experts: int = 0  # 0 => dense FFN
    # experts per token; either gate renormalises their weights over the chosen
    # under ``router_renormalize`` and else applies them as they are
    top_k: int = 2
    capacity_factor: float = 1.25  # ep > 1 only: the dropless path has none
    # weight of the load-balancing term E·Σ f_e·P_e (mean over layers) in
    # the training loss; 0 => the loss is the cross entropy alone
    router_aux_loss_coef: float = 0.0
    # RMSNorm with a learned weight over the whole q and k projections,
    # before the head split and RoPE (OLMoE's attention)
    qk_norm: bool = False
    # with ``qk_norm``: the norm runs over each head's ``head_dim`` lanes instead,
    # after the head split and before RoPE, under ONE head_dim-wide weight that
    # the query heads share and one that the key heads share (LFM2's attention)
    qk_norm_per_head: bool = False
    # the norms ahead of a layer's two parts, the final one, the q/k norms (and a
    # multi-token-prediction module's) scale by ``1 + w`` with ``w`` zero at init
    # (``ops/layers.rms_norm``); a linear mixer's and a latent's own norms stay plain
    norm_zero_centered: bool = False
    # ``full`` and ``window`` layers: the query projection is twice as wide, a head's
    # lanes ``[q | gate]``, and the core's output is scaled by ``sigmoid(gate)``
    # lane by lane ahead of the output projection
    attn_output_gate: bool = False
    rope_theta: float = 10000.0
    norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16  # compute dtype (MXU-native)
    remat: bool = True
    # checkpoint policy under remat: "all" recomputes the whole layer in
    # the backward (lowest memory) but for the flash kernel's forward, whose
    # output [B, S, H·Dv] and row statistics [B, H, 8, S] f32 are kept where
    # the kernel is the core (``_remat``: the kernel runs once a layer a
    # step; a path without it keeps nothing); "dots" saves matmul outputs
    # too and recomputes only elementwise/softmax (MXU work runs once — the
    # round-5 sweet spot at short S where memory isn't the constraint)
    remat_policy: str = "all"
    pp: int = 1  # pipeline stages; n_layers % pp == 0
    microbatches: int = 0  # 0 => = pp
    # "auto" | "plain" | "chunked" | "flash". auto picks from what it can
    # observe (``_attention_path``): ring attention under sp > 1; on a TPU,
    # outside a manual region, at s % 512 == 0 from s1024 on where
    # head_dim % 128 == 0 and from s2048 on where head_dim is 64, the Pallas
    # flash kernel at 512 x 512 tiles (the fastest core measured there,
    # PERF.md §6 PR 31 and, at 64 lanes, PR 52); else the tiered chunked scan
    # (ops/attention.chunked_attention, pure XLA) from s >= 1024 and plain
    # XLA attention below; the kernel again, for memory's sake, past the
    # scores-memory ceiling where chunked cannot run. A layer's window and
    # grouped key/value heads go to whichever it picks: each core takes both
    attention_impl: str = "auto"

    # -- a declared layer pattern. Layers are counted from 1, as published
    # configurations count them; a layer named in neither list mixes with
    # ``full`` attention.
    kda_layers: Tuple[int, ...] = ()  # gated delta-rule linear attention (ops/kda.py)
    mla_layers: Tuple[int, ...] = ()  # latent attention (positions: ``mla_rope_theta``)
    # a gated short convolution: [B, C, X] = h·conv_in, y = C ⊙ conv(B ⊙ X) over
    # ``conv_kernel`` causal depthwise taps, y·conv_out; d_model wide, no positions
    conv_layers: Tuple[int, ...] = ()
    # Gated DeltaNet (``ops/kda.gdn_chunked``): kda's rule and convolution with ONE
    # log-decay a head a position from ``[b | a] = h·w_ba`` (beta = sigmoid(b), g =
    # -exp(a_log)·softplus(a + dt_bias)), the output normed head by head and scaled
    # by SiLU of a projection as wide as the values; no low-rank gates
    gdn_layers: Tuple[int, ...] = ()
    # with experts: this many leading layers keep a dense SwiGLU of d_ff
    n_dense_layers: int = 0
    moe_d_ff: int = 0  # one expert's width; 0 => d_ff (a model of expert layers only)
    # -- experts under a share: the router stays n_experts wide; this layer
    # holds the contiguous block [expert_share_index * n_experts_held, ...)
    # and leaves out what the absent experts would add. 0 => all are held
    n_experts_held: int = 0
    expert_share_index: int = 0
    n_shared_experts: int = 0  # dense SwiGLUs of moe_d_ff beside the routed experts
    # the shared experts' output times ``sigmoid(h·shared_scale)``, one number a token
    shared_expert_gate: bool = False
    # "softmax": p = softmax(h·Wr), the k largest, applied as they are
    # (renormalised over the chosen if ``router_renormalize``).
    # "sigmoid": s = sigmoid(h·Wr), the k largest of s + a selection-only
    # bias, weights s (renormalised over the chosen if ``router_renormalize``)
    # times ``routed_scaling_factor``
    router_gate: str = "softmax"
    router_renormalize: bool = False
    router_norm_eps: float = 1e-20  # beside the chosen scores' sum where they are renormalised
    routed_scaling_factor: float = 1.0
    # -- mla: q is n_heads x (qk_nope + qk_rope); keys are a per-head part of
    # qk_nope from the latent (kv_lora_rank) and one part of qk_rope shared by
    # all heads (not rotated: this path has no positions); values v_head_dim
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # a low-rank query: q = RMSNorm(x·wq_a)·wq_b through this rank; 0 => the one wq
    q_lora_rank: int = 0
    # the rotation of the query's last qk_rope lanes and of the shared key (once,
    # before it is broadcast over heads), paired as ``rope_pairing`` says, at
    # this base of its own; 0 => no positions, what a latent layer had before
    mla_rope_theta: float = 0.0
    # -- kda: linear_n_heads x linear_head_dim keys and values, a causal
    # depthwise convolution of conv_kernel taps on q, k and v (a ``conv``
    # layer's own taps are as many); the two low-rank gates (decay, output)
    # are linear_head_dim wide inside
    linear_head_dim: int = 0
    linear_n_heads: int = 0
    # -- gdn: linear_n_heads VALUE heads over this many key heads (value head j
    # reads query/key head j // (heads / key heads)); 0 => as many
    linear_n_key_heads: int = 0
    conv_kernel: int = 4
    # -- grouped-query heads: ``full`` and ``window`` layers project keys and
    # values to this many heads, and query head a reads key/value head
    # a // (heads / n_kv_heads). 0 => as many as query heads
    n_kv_heads: int = 0
    # query heads layer by layer (entry i: layer i + 1; a published list may
    # run past n_layers); layers of one kind agree. () => n_heads everywhere
    n_heads_per_layer: Tuple[int, ...] = ()
    # -- a band: the layers named here are ``window`` layers, whose position i
    # attends to the keys j with i - window < j <= i; the others stay global
    window: int = 0
    window_layers: Tuple[int, ...] = ()
    window_rope_theta: float = 0.0  # the window layers' own base; 0 => rope_theta
    # -- the rotation. "interleaved": lane 2i with lane 2i + 1 (what this
    # program always did); "half": lane i with lane i + r/2 inside the first r
    # lanes. ``rotary_dim`` = r of the GLOBAL layers (0 => head_dim; window
    # layers rotate the whole head); under it needs "half"
    rope_pairing: str = "interleaved"
    rotary_dim: int = 0
    # YaRN on the global layers' table (``ops/layers.yarn_inv_freq``): 0 => none
    yarn_factor: float = 0.0
    yarn_original_max: int = 0
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_attention_factor: float = 1.0  # cos and sin are scaled by it
    # the sigmoid gate's selection-only bias leaf; False => the k largest scores
    router_selection_bias: bool = True
    # -- softmax layers WITHOUT positions: the layers named here are ``nope`` layers, global as ``full``
    # ones (every key j <= i) with q and k as they are projected, whatever rotates the other kinds
    nope_layers: Tuple[int, ...] = ()
    # where an expert layer's gate reads: "ffn" — the feed-forward's own normed input, N2 of the state
    # after the mixer, inside the feed-forward's checkpoint; "layer" — the LAYER's normed input N1(x),
    # ahead of the mixer: the chosen experts and their weights are then inputs of the feed-forward part
    router_input: str = "ffn"
    # the gate's activation in the routed and the shared experts: "silu" | "relu" (dense layers keep SiLU)
    expert_activation: str = "silu"
    # -- multi-token prediction: this many modules (0 or 1) behind the main
    # stack, each a layer of the last layer's kind that shares ``embed`` and
    # ``out``; the training loss is main + mtp_loss_weight x the module's cross
    # entropy against the second-next token. Weight 0: the module is not run
    n_mtp_modules: int = 0
    mtp_loss_weight: float = 0.1
    # -- a looped stack: the SAME layers run ``ut_steps`` times a step, the final
    # norm applied at the end of each turn and its output carried into the next
    # (one kind of layer, ``pp`` 1, no multi-token-prediction module)
    ut_steps: int = 1
    # each layer norms its two parts' OUTPUTS too: ``x + N(mix(N(x)))``, ``x + N(ffn(N(x)))``
    sandwich_norm: bool = False
    # with ``ut_steps`` > 1: lambda_t = sigmoid(h_t . w_g + b_g) (float32) says how much of
    # the probability that reached loop step t leaves there (the last step takes what is
    # left), and the training loss is the expectation of the cross entropy over the exits
    # less ``exit_entropy_coef`` times the exit distribution's entropy, a token at a time.
    # Without the gate the loss reads the last loop step alone
    exit_gate: bool = False
    exit_entropy_coef: float = 0.0
    # -- block-diffusion training (``diffusion_block`` = D > 0; 0 => next-token training): a sequence of S clean ids
    # runs as 2·S rows ``[noised ; clean]`` — in the noised half an id is replaced by ``diffusion_mask_id`` with the
    # probability t of its block of D positions — row r at position ``r mod S`` under the staircase mask of
    # ``ops.attention.block_diffusion_mask``, and the loss is the mean over the S positions of ``m/t`` times the cross
    # entropy of the NOISED row against the clean id at the same position. The noise is a function of the sequence's own
    # ids and ``diffusion_seed`` (:func:`_diffusion_noise`): t uniform on ``[diffusion_t_min, 1]`` a block
    diffusion_block: int = 0
    diffusion_t_min: float = 1e-3
    diffusion_mask_id: int = -1  # -1 => the last row of the vocabulary held
    diffusion_seed: int = 0
    # -- Mamba-2 state-space mixers (``ops/kda.ssd_chunked``): ``[z | xBC | dt] = h·ssd_in``, ``xBC`` through a causal
    # depthwise convolution of ``conv_kernel`` taps WITH a bias and SiLU, split ``x`` [heads x head_dim], ``B`` and ``C``
    # [ssd_state_dim each] — ONE of each a position for all heads (``ssd_n_groups`` 1); ``Δ = softplus(dt + dt_bias)`` a
    # head, the state of a head [ssd_state_dim, ssd_head_dim]: ``H_t = exp(-Δ_t·exp(a_log))·H_{t-1} + Δ_t·B_t ⊗ x_t``,
    # ``y_t = H_tᵀ·C_t + d_skip·x_t``; ``RMSNorm(y ⊙ SiLU(z))`` over ALL heads x head_dim channels under one plain
    # weight, then ``·ssd_out``. No positions
    ssd_layers: Tuple[int, ...] = ()
    ssd_state_dim: int = 0
    ssd_head_dim: int = 0
    ssd_n_heads: int = 0
    ssd_n_groups: int = 1
    ssd_expand: int = 0  # the channels as a multiple of d_model, where a source states it: heads x head_dim is held to it
    # -- four multipliers (Granite's), each 1 or 0 for what the program always did: the embedding's rows times
    # ``embed_scale``; each of a layer's two branches times ``residual_scale`` where it joins the residual stream; a
    # softmax layer's scores times ``attn_scale`` (0 => ``head_dim**-0.5``); the logits DIVIDED by ``logits_scale``
    embed_scale: float = 1.0
    residual_scale: float = 1.0
    attn_scale: float = 0.0
    logits_scale: float = 1.0
    # the head reads the embedding table (logits = h·embedᵀ): there is no ``out`` leaf, and the table's gradient is
    # the sum of the head's and the lookup's
    tie_embeddings: bool = False
    # -- a learned key selector in front of every ``full`` core (``sparse_topk`` = k > 0; 0 => none: the dense causal
    # core). ``indexer_heads`` heads of ``indexer_head_dim`` lanes over ONE key head score every causal pair from the
    # layer's normed input behind a ``stop_gradient``: ``I[t, s] = (heads·dim)^-1/2 · Σ_j w[t, j]·ReLU(q_j[t]·k[s])``,
    # q and k rotated over their whole width (lane i with i + dim/2 at ``rope_theta``), float32; a query attends to the
    # keys whose score is at least its k-th largest (every query head the same keys; all of them up to position k - 1),
    # and the selector's three leaves are trained by ``KL(sg(p) ‖ softmax_selected(I))`` a query, p the mean over the
    # query heads of the core's probabilities — the mean over positions, summed over layers, beside the next-token loss
    sparse_topk: int = 0
    indexer_heads: int = 0
    indexer_head_dim: int = 0

    def __post_init__(self) -> None:
        for name in (
            "kda_layers", "gdn_layers", "mla_layers", "conv_layers", "window_layers", "nope_layers", "n_heads_per_layer",
            "ssd_layers",
        ):  # a JSON file gives lists
            object.__setattr__(self, name, tuple(int(i) for i in getattr(self, name)))
        named = (
            self.kda_layers + self.gdn_layers + self.mla_layers + self.conv_layers + self.window_layers + self.nope_layers
            + self.ssd_layers
        )
        if len(set(named)) != len(named) or any(not 1 <= i <= self.n_layers for i in named):
            raise ValueError(
                f"kda_layers {self.kda_layers}, gdn_layers {self.gdn_layers}, mla_layers {self.mla_layers}, "
                f"conv_layers {self.conv_layers}, window_layers {self.window_layers}, "
                f"nope_layers {self.nope_layers} and ssd_layers {self.ssd_layers} name layers 1..{self.n_layers}, "
                "each at most once"
            )
        if self.linear_n_key_heads and (not self.gdn_layers or self.linear_n_heads % self.linear_n_key_heads):
            raise ValueError(
                f"linear_n_key_heads={self.linear_n_key_heads} under linear_n_heads={self.linear_n_heads}: the value "
                "heads of gdn_layers divide over the key heads in whole groups (a kda layer has one count)"
            )
        if bool(self.window_layers) != bool(self.window):
            raise ValueError(f"window={self.window} and window_layers={self.window_layers}: a band has both")
        if self.rope_pairing not in ("interleaved", "half"):
            raise ValueError(f"rope_pairing must be 'interleaved'|'half', got {self.rope_pairing!r}")
        if (self.rotary_dim or self.yarn_factor) and self.rope_pairing != "half":
            raise ValueError("a partial rotation (rotary_dim) and YaRN come with rope_pairing='half'")
        if self.rotary_dim % 2 or not 0 <= self.rotary_dim <= self.head_dim:
            raise ValueError(f"rotary_dim={self.rotary_dim}: an even number of a head's {self.head_dim} lanes")
        if self.n_heads_per_layer and len(self.n_heads_per_layer) < self.n_layers:
            raise ValueError(f"n_heads_per_layer has {len(self.n_heads_per_layer)} entries for {self.n_layers} layers")
        for mixer in _SOFTMAX_MIXERS:
            heads = {self.layer_heads(i) for i, (m, _) in enumerate(self.layer_kinds(), 1) if m == mixer}
            if len(heads) > 1 or any(h % self.kv_heads for h in heads):
                raise ValueError(
                    f"{mixer} layers have {sorted(heads)} query heads over {self.kv_heads} key/value heads: "
                    "layers of one kind agree, and the groups are whole"
                )
        if self.qk_norm_per_head and not self.qk_norm:
            raise ValueError("qk_norm_per_head says where qk_norm's norm runs: it comes with qk_norm")
        if self.router_gate not in ("softmax", "sigmoid"):
            raise ValueError(f"router_gate must be 'softmax'|'sigmoid', got {self.router_gate!r}")
        if self.n_experts_held and (
            self.n_experts % self.n_experts_held
            or not 0 <= self.expert_share_index < self.n_experts // self.n_experts_held
        ):
            raise ValueError(
                f"n_experts_held={self.n_experts_held}, expert_share_index={self.expert_share_index}: "
                f"the {self.n_experts} experts divide into equal blocks and the index names one"
            )
        if (self.n_dense_layers or self.n_shared_experts or self.n_experts_held) and not self.n_experts:
            raise ValueError("n_dense_layers, n_shared_experts and n_experts_held describe a model with experts")
        if self.router_input not in ("ffn", "layer"):
            raise ValueError(f"router_input must be 'ffn'|'layer', got {self.router_input!r}")
        if self.router_input == "layer" and not self.n_experts:
            raise ValueError("router_input says where an expert layer's gate reads: it comes with n_experts")
        if self.expert_activation not in _ACTIVATIONS:
            raise ValueError(f"expert_activation must be one of {sorted(_ACTIVATIONS)}, got {self.expert_activation!r}")
        if self.expert_activation != "silu" and (not self.n_experts or self.n_dense_layers):
            raise ValueError(
                f"expert_activation={self.expert_activation!r} is the experts' gate: it comes with n_experts, and a "
                "model's leading dense layers (n_dense_layers) gate with SiLU — a dense layer's own activation is missing"
            )
        if self.shared_expert_gate and not self.n_shared_experts:
            raise ValueError("shared_expert_gate scales the shared experts' output: it comes with n_shared_experts")
        if (self.q_lora_rank or self.mla_rope_theta) and not self.mla_layers:
            raise ValueError("q_lora_rank and mla_rope_theta describe a model with mla_layers")
        if self.mla_rope_theta and self.qk_rope_head_dim % 2:
            raise ValueError(f"qk_rope_head_dim={self.qk_rope_head_dim}: a rotation pairs an even number of lanes")
        if self.n_mtp_modules not in (0, 1):
            raise ValueError(
                f"n_mtp_modules={self.n_mtp_modules}: one module predicts the second-next token; the chain that "
                "hands module k's hidden state to module k + 1 for the token after is missing"
            )
        if self.ut_steps < 1:
            raise ValueError(f"ut_steps={self.ut_steps}: the stack runs at least once")
        if self.exit_gate and self.ut_steps == 1:
            raise ValueError("exit_gate says at which loop step a token leaves: it comes with ut_steps > 1")
        if self.exit_entropy_coef and not self.exit_gate:
            raise ValueError("exit_entropy_coef weighs the entropy of exit_gate's distribution: it comes with exit_gate")
        if self.diffusion_block:
            self._refuse_diffusion()
        self._refuse_ssd()
        self._refuse_multipliers()
        if self.sparse_topk or self.indexer_heads or self.indexer_head_dim:
            self._refuse_sparse()

    def _refuse_sparse(self) -> None:
        """What a learned key selector cannot run with yet, each by name."""
        k = self.sparse_topk
        if k < 1 or self.indexer_heads < 1 or self.indexer_head_dim < 2 or self.indexer_head_dim % 2:
            raise ValueError(
                f"sparse_topk={k}, indexer_heads={self.indexer_heads}, indexer_head_dim={self.indexer_head_dim}: a key "
                "selector has all three, and its rotation pairs an even number of lanes"
            )
        if self.window or self.nope_layers or self.n_heads_per_layer:
            raise ValueError(
                f"sparse_topk={k} with a window, layers without positions or heads by layer: the selector stands in front of "
                "``full`` layers of one kind; a selection inside a band (the k largest of the band's keys) and a selector "
                "a declared kind are missing"
            )
        other = sorted({m for m, _ in self.layer_kinds()} - {"full"})
        if other:
            raise ValueError(
                f"sparse_topk={k} with {other} layers: the selection enters the grouped-query core alone; a selector in front "
                "of a latent core (its shared rotated key and per-head parts under one table of pairs) and a model that "
                "mixes selected layers with linear, state-space or convolution mixers are missing"
            )
        if self.diffusion_block:
            raise ValueError(
                f"sparse_topk={k} with diffusion_block={self.diffusion_block}: the selection is causal over one run of "
                "positions; a selector over [noised ; clean] (which half's keys a noised row ranks) is missing"
            )
        if self.ut_steps > 1:
            raise ValueError(
                f"sparse_topk={k} with ut_steps={self.ut_steps}: the selector's loss is a term a layer a step; what a looped "
                "stack sums (a term a loop step over shared selector weights) is not defined"
            )
        if self.n_mtp_modules:
            raise ValueError(
                f"sparse_topk={k} with a multi-token-prediction module: the module's layer would select over the shifted "
                "stream with a selector of its own; its leaves and its term in the loss are missing"
            )
        if max(self.pp, 1) > 1:
            raise ValueError(
                f"sparse_topk={k} with pp={self.pp}: the selector's loss does not cross pipeline stages (what the layers say "
                "is dropped there, _pipeline_stage_fn) and the selection's kernels cannot run in the manual region"
            )
        if self.attention_impl == "chunked":
            raise ValueError(
                f"sparse_topk={k} with attention_impl='chunked': ops/attention.chunked_attention scans q blocks against a "
                "causal prefix; a table of selected pairs a block is missing there"
            )
        if self.attn_output_gate or self.attn_scale or self.sandwich_norm:
            raise ValueError(
                f"sparse_topk={k} with attn_output_gate, attn_scale or sandwich_norm: the selector's target reads the core's "
                "probabilities at head_dim**-0.5 from an ungated, un-normed branch; the other three are missing"
            )

    def _refuse_ssd(self) -> None:
        """What a state-space mixer cannot run with yet, each by name."""
        if not self.ssd_layers:
            return
        if self.ssd_n_groups != 1:
            raise ValueError(
                f"ssd_n_groups={self.ssd_n_groups}: one B and one C a position serve all heads and one norm runs over all "
                "channels; B and C a group of heads, and the gated norm a group (its statistics over a group's channels), "
                "are missing"
            )
        if min(self.ssd_state_dim, self.ssd_head_dim, self.ssd_n_heads) < 1:
            raise ValueError(
                f"ssd_layers with ssd_state_dim={self.ssd_state_dim}, ssd_head_dim={self.ssd_head_dim}, "
                f"ssd_n_heads={self.ssd_n_heads}: a state-space mixer has all three sizes"
            )
        if self.ssd_expand and self.ssd_n_heads * self.ssd_head_dim != self.ssd_expand * self.d_model:
            raise ValueError(
                f"ssd_n_heads={self.ssd_n_heads} x ssd_head_dim={self.ssd_head_dim} is not ssd_expand={self.ssd_expand} x "
                f"d_model={self.d_model}: the heads are the channels"
            )
        if max(self.pp, 1) > 1:
            raise ValueError(
                f"pp={self.pp} with ssd layers: a stage's scan carries no state between microbatches and the pipeline's "
                "manual region has not traced the mixer's block scan; a stage function for it is missing"
            )
        if self.ut_steps > 1:
            raise ValueError(
                f"ut_steps={self.ut_steps} with ssd layers: what a looped stack's checkpoints keep of the mixer's block "
                "scan a loop step is not settled; a looped state-space stack is missing"
            )
        if self.n_mtp_modules:
            raise ValueError(
                "a multi-token-prediction module behind ssd layers: the module would be a state-space layer that starts "
                "from an empty state at every step's position 0 of the shifted stream; what state it reads is not defined"
            )

    def _refuse_multipliers(self) -> None:
        """Where the four multipliers and the tied table are not applied yet, each by name."""
        scaled = self.embed_scale != 1.0 or self.residual_scale != 1.0 or self.logits_scale != 1.0 or self.attn_scale != 0.0
        if not (scaled or self.tie_embeddings):
            return
        if self.attn_scale < 0 or self.logits_scale <= 0:
            raise ValueError(
                f"attn_scale={self.attn_scale}, logits_scale={self.logits_scale}: a scale on the scores and a divisor of "
                "the logits are positive"
            )
        if max(self.pp, 1) > 1:
            raise ValueError(
                f"pp={self.pp} with embed_scale / residual_scale / attn_scale / logits_scale / tie_embeddings: the "
                "pipeline's head (_pipelined_loss) reads its own ``out`` table on the last stage and the embedding is "
                "looked up ahead of the first; a head that is handed the first stage's table, and the multipliers inside "
                "the manual region, are missing"
            )
        if scaled and (self.n_mtp_modules or self.ut_steps > 1):
            raise ValueError(
                "embed_scale / residual_scale / attn_scale / logits_scale with a multi-token-prediction module or a looped "
                "stack: whether the module's second embedding and each loop step's re-entry are scaled is not defined by "
                "any source this program runs; the multipliers there are missing"
            )

    def _refuse_diffusion(self) -> None:
        """What block-diffusion training cannot run with yet, each by name."""
        d = self.diffusion_block
        if d < 0 or 128 % d:
            raise ValueError(
                f"diffusion_block={d}: a block divides the sequence's tiles (128 x 128 the smallest, so a power of two up "
                "to 128); a staircase whose steps cross a tile's rows (a mask that knows each row's half and block apart) "
                "is missing"
            )
        if not 0.0 < self.diffusion_t_min <= 1.0:
            raise ValueError(f"diffusion_t_min={self.diffusion_t_min}: the least noise level lies in (0, 1], and weighs 1/t")
        if not -1 <= self.diffusion_mask_id < self.vocab_size:
            raise ValueError(f"diffusion_mask_id={self.diffusion_mask_id}: a row of the {self.vocab_size} held, or -1 for the last")
        other = sorted({m for m, _ in self.layer_kinds()} - {"full"})
        if other:
            raise ValueError(
                f"diffusion_block={d} with {other} layers: only ``full`` softmax layers know the 2·S rows — positions that "
                "repeat and the staircase mask; a band, a latent's cores, a recurrent or state-space state or a convolution over "
                "[noised ; clean] (each noised block continuing the CLEAN prefix's state) are missing"
            )
        if self.n_mtp_modules:
            raise ValueError(
                f"diffusion_block={d} with a multi-token-prediction module: the module reads the token one ahead and "
                "predicts the second-next; what it reads and predicts on a noised row is not defined"
            )
        if self.ut_steps > 1:
            raise ValueError(
                f"diffusion_block={d} with ut_steps={self.ut_steps}: the exits' heads read every row of every loop step; "
                "a looped stack whose exits read the noised half alone is missing"
            )
        if max(self.pp, 1) > 1:
            raise ValueError(
                f"diffusion_block={d} with pp={self.pp}: the pipeline's head shifts targets and weighs positions alike "
                "(_pipelined_loss), and its stages run the chunked core; a head on the noised rows with per-position "
                "weights inside the manual region is missing"
            )
        if self.attention_impl == "chunked":
            raise ValueError(
                f"diffusion_block={d} with attention_impl='chunked': ops/attention.chunked_attention scans q blocks "
                "against a causal prefix; the staircase's two runs of keys a q block are missing there"
            )

    @property
    def layers_per_stage(self) -> int:
        assert self.n_layers % max(self.pp, 1) == 0
        return self.n_layers // max(self.pp, 1)

    @property
    def kv_heads(self) -> int:
        return self.n_kv_heads or self.n_heads

    def layer_heads(self, layer: int) -> int:
        """Query heads of ``layer`` (counted from 1)."""
        return self.n_heads_per_layer[layer - 1] if self.n_heads_per_layer else self.n_heads

    def mixer_heads(self, mixer: str) -> int:
        """Query heads of the ``full``, ``window`` or ``nope`` layers (each kind's agree)."""
        for i, (m, _) in enumerate(self.layer_kinds(), 1):
            if m == mixer:
                return self.layer_heads(i)
        return self.n_heads

    @property
    def linear_key_heads(self) -> int:
        return self.linear_n_key_heads or self.linear_n_heads

    @property
    def mask_id(self) -> int:
        """The id a noised position shows under block diffusion."""
        return self.diffusion_mask_id if self.diffusion_mask_id >= 0 else self.vocab_size - 1

    @property
    def expert_d_ff(self) -> int:
        return self.moe_d_ff or self.d_ff

    @property
    def experts_held(self) -> int:
        return self.n_experts_held or self.n_experts

    def layer_kinds(self) -> Tuple[Tuple[str, str], ...]:
        """(mixer, feed-forward) of every layer, in order."""
        kinds = []
        for i in range(1, self.n_layers + 1):
            mixer = (
                "kda" if i in self.kda_layers else "gdn" if i in self.gdn_layers
                else "mla" if i in self.mla_layers
                else "conv" if i in self.conv_layers
                else "window" if i in self.window_layers
                else "nope" if i in self.nope_layers
                else "ssd" if i in self.ssd_layers else "full"
            )
            ff = "experts" if self.n_experts and i > self.n_dense_layers else "dense"
            kinds.append((mixer, ff))
        return tuple(kinds)


def layer_pattern(cfg: TransformerConfig) -> Tuple[int, Tuple[Tuple[str, str], ...]]:
    """(leading layers, the period): the stack is ``lead`` layers run one by
    one and then whole repeats of ``period`` (kinds of layer, in order) under
    ``lax.scan`` — the split with the fewest unrolled layers, and of several
    such the one with the fewest leading layers. A model of one kind gives
    (0, (kind,))."""
    kinds = cfg.layer_kinds()
    splits = [
        (lead, kinds[lead : lead + p])
        for lead in range(len(kinds))
        for p in range(1, len(kinds) - lead + 1)
        if kinds[lead:] == kinds[lead : lead + p] * ((len(kinds) - lead) // p)
    ]
    return min(splits, key=lambda split: (split[0] + len(split[1]), split[0]))


def _kind_key(kind: Tuple[str, str]) -> str:
    return f"{kind[0]}.{kind[1]}"


def _of_one_kind(cfg: TransformerConfig) -> bool:
    return len(set(cfg.layer_kinds())) == 1


def _slots(kinds) -> Tuple[Tuple[str, int], ...]:
    """For layers of these kinds in order: (kind's key, index among the
    layers of its kind) — where a layer's parameters sit in its group."""
    seen: Dict[str, int] = {}
    out = []
    for kind in kinds:
        key = _kind_key(kind)
        out.append((key, seen.get(key, 0)))
        seen[key] = seen.get(key, 0) + 1
    return tuple(out)


def _group_sizes(kinds) -> Dict[str, Tuple[Tuple[str, str], int]]:
    """By kind's key: (the kind, how many of ``kinds`` are of it)."""
    return {_kind_key(kind): (kind, n) for kind, n in collections.Counter(kinds).items()}


# Named shapes (``TransformerConfig(**PRESETS[name])``), one definition for
# examples/train_hsdp.py (MODEL=...) and chip_smoke.py.
PRESETS: Dict[str, Dict[str, Any]] = {
    # CPU-mesh testable
    "tiny": dict(
        vocab_size=64, d_model=16, n_layers=2, n_heads=2, head_dim=8,
        d_ff=32, dtype=jnp.float32,
    ),
    # the widest model this repo has run on one v5e chip: f32 params +
    # Adam + f32 gradients are ~10.4 GB of its 16 GB. remat=False: at
    # this size recompute cost more than the activations it saved
    "scale_647M": dict(
        vocab_size=32000, d_model=2048, n_layers=12, n_heads=16,
        head_dim=64, d_ff=5632, dtype=jnp.bfloat16, remat=False,
    ),
    # OLMoE-1B-7B-0125-Instruct as published (6.9B parameters: needs a
    # sharded group); the benchmark's olmoe-1g runs one of its 16 layers
    "olmoe-1b-7b": dict(
        vocab_size=50304, d_model=2048, n_layers=16, n_heads=16,
        head_dim=128, d_ff=1024, n_experts=64, top_k=8, qk_norm=True,
        router_aux_loss_coef=0.01, rope_theta=10000.0, norm_eps=1e-5,
        dtype=jnp.bfloat16,
    ),
}


# leaves that stay float32 under a narrower compute dtype: the decay's two
# parameters (a rounded log-decay is another model), the selection bias and a
# state-space mixer's skip
_F32_LEAVES = ("a_log", "dt_bias", "router_bias", "d_skip")


def _init_layers(rng, cfg: TransformerConfig, kind: Tuple[str, str], lead: Tuple[int, ...]) -> Dict[str, Any]:
    """The parameters of ``prod(lead)`` layers of one kind, each leaf with
    the leading axes ``lead``."""
    keys = jax.random.split(rng, 16)
    more = iter(jax.random.split(keys[10], 16))  # leaves the first kind of layer did not have
    mixer, ff = kind
    d = cfg.d_model

    def dense(key, *shape, fan_in):
        return (
            jax.random.normal(key, lead + shape, jnp.float32) * (fan_in**-0.5)
        )

    def ones(*shape):
        return jnp.ones(lead + shape, jnp.float32)

    def unit(*shape):  # a norm's weight that scales by one at init
        return _unit_weight(cfg, lead + shape)

    layers: Dict[str, Any] = {"ln1": unit(d), "ln2": unit(d)}
    if cfg.sandwich_norm:
        layers.update(post_ln1=unit(d), post_ln2=unit(d))
    if mixer in _SOFTMAX_MIXERS:
        qkv = cfg.mixer_heads(mixer) * cfg.head_dim
        kv = cfg.kv_heads * cfg.head_dim
        layers.update(
            wq=dense(keys[0], d, 2 * qkv if cfg.attn_output_gate else qkv, fan_in=d),  # gated: a head's [q | gate]
            wk=dense(keys[1], d, kv, fan_in=d),
            wv=dense(keys[2], d, kv, fan_in=d),
            wo=dense(keys[3], qkv, d, fan_in=qkv),
        )
        if cfg.qk_norm_per_head:
            layers.update(q_norm=unit(cfg.head_dim), k_norm=unit(cfg.head_dim))
        elif cfg.qk_norm:
            layers.update(q_norm=unit(qkv), k_norm=unit(kv))
        if cfg.sparse_topk:  # the selector's three projections: I is O(1) at normal / sqrt(fan_in)
            hi, di = cfg.indexer_heads, cfg.indexer_head_dim
            layers.update(
                idx_wq=dense(next(more), d, hi * di, fan_in=d), idx_wk=dense(next(more), d, di, fan_in=d),
                idx_ww=dense(next(more), d, hi, fan_in=d),
            )
    elif mixer == "kda":
        hd, taps = cfg.linear_head_dim, cfg.conv_kernel
        ch = cfg.linear_n_heads * hd
        layers.update(
            wq=dense(keys[0], d, ch, fan_in=d),
            wk=dense(keys[1], d, ch, fan_in=d),
            wv=dense(keys[2], d, ch, fan_in=d),
            wo=dense(keys[3], ch, d, fan_in=ch),
            conv_q=dense(next(more), taps, ch, fan_in=taps),
            conv_k=dense(next(more), taps, ch, fan_in=taps),
            conv_v=dense(next(more), taps, ch, fan_in=taps),
            w_fa=dense(next(more), d, hd, fan_in=d),
            # a tenth of the usual scale: the initial decay is dt_bias's, as in the
            # published model class (at the full scale the log-decay's input is
            # N(0, 1) and a position forgets up to 60 nats)
            w_fb=0.1 * dense(next(more), hd, ch, fan_in=hd),
            w_ga=dense(next(more), d, hd, fan_in=d),
            w_gb=dense(next(more), hd, ch, fan_in=hd),
            w_beta=dense(next(more), d, cfg.linear_n_heads, fan_in=d),
            # exp(a_log) in [1, 16] and softplus(dt_bias) in [1e-3, 1e-1], log-uniform:
            # a position forgets between a thousandth and most of a channel
            a_log=jnp.log(jax.random.uniform(next(more), lead + (cfg.linear_n_heads,), jnp.float32, 1.0, 16.0)),
            dt_bias=_inv_softplus(jnp.exp(jax.random.uniform(
                next(more), lead + (ch,), jnp.float32, np.log(1e-3), np.log(1e-1)
            ))),
            o_norm=ones(hd),
        )
    elif mixer == "gdn":
        hd, taps, heads = cfg.linear_head_dim, cfg.conv_kernel, cfg.linear_n_heads
        kch, vch = cfg.linear_key_heads * hd, heads * hd
        layers.update(
            wq=dense(keys[0], d, kch, fan_in=d),
            wk=dense(keys[1], d, kch, fan_in=d),
            wv=dense(keys[2], d, vch, fan_in=d),
            wo=dense(keys[3], vch, d, fan_in=vch),
            conv_q=dense(next(more), taps, kch, fan_in=taps),
            conv_k=dense(next(more), taps, kch, fan_in=taps),
            conv_v=dense(next(more), taps, vch, fan_in=taps),
            w_z=dense(next(more), d, vch, fan_in=d),
            w_ba=dense(next(more), d, 2 * heads, fan_in=d),  # b | a: the write strength's and the decay's inputs
            # the published class's: exp(a_log) uniform in (0, 16], dt_bias 1 — a head forgets up to ~20 nats a position
            a_log=jnp.log(jax.random.uniform(next(more), lead + (heads,), jnp.float32, 1e-6, 16.0)),
            dt_bias=ones(heads),
            o_norm=ones(hd),
        )
    elif mixer == "mla":
        h, rank = cfg.n_heads, cfg.kv_lora_rank
        nope, rope, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
        if cfg.q_lora_rank:
            layers.update(
                wq_a=dense(next(more), d, cfg.q_lora_rank, fan_in=d),
                q_a_norm=ones(cfg.q_lora_rank),
                wq_b=dense(keys[0], cfg.q_lora_rank, h * (nope + rope), fan_in=cfg.q_lora_rank),
            )
        else:
            layers.update(wq=dense(keys[0], d, h * (nope + rope), fan_in=d))
        layers.update(
            w_kva=dense(keys[1], d, rank + rope, fan_in=d),
            kv_norm=ones(rank),
            w_kvb=dense(keys[2], rank, h * (nope + dv), fan_in=rank),
            wo=dense(keys[3], h * dv, d, fan_in=h * dv),
        )
    elif mixer == "conv":
        taps = cfg.conv_kernel
        layers.update(
            conv_in=dense(next(more), d, 3 * d, fan_in=d),  # B | C | X along the features
            conv_w=dense(next(more), taps, d, fan_in=taps),
            conv_out=dense(next(more), d, d, fan_in=d),
        )
    elif mixer == "ssd":
        taps, heads = cfg.conv_kernel, cfg.ssd_n_heads
        inner = heads * cfg.ssd_head_dim
        mixed = inner + 2 * cfg.ssd_state_dim  # what the convolution runs over: x | B | C
        layers.update(
            ssd_in=dense(next(more), d, inner + mixed + heads, fan_in=d),  # z | x B C | dt along the features
            conv_w=dense(next(more), taps, mixed, fan_in=taps),
            # a depthwise convolution's bias as its published class draws it: uniform within 1/sqrt(taps)
            conv_b=jax.random.uniform(next(more), lead + (mixed,), jnp.float32, -(taps**-0.5), taps**-0.5),
            # Mamba-2's published initial values: exp(a_log) uniform in [1, 16], softplus(dt_bias) log-uniform in
            # [1e-3, 1e-1], the skip 1 — a head forgets between a thousandth of a nat and 1.6 nats a position
            a_log=jnp.log(jax.random.uniform(next(more), lead + (heads,), jnp.float32, 1.0, 16.0)),
            dt_bias=_inv_softplus(jnp.exp(jax.random.uniform(
                next(more), lead + (heads,), jnp.float32, np.log(1e-3), np.log(1e-1)
            ))),
            d_skip=ones(heads),
            y_norm=ones(inner),
            ssd_out=dense(next(more), inner, d, fan_in=inner),
        )
    else:
        raise ValueError(f"unknown mixer {mixer!r}")
    if ff == "experts":
        e, held, f = cfg.n_experts, cfg.experts_held, cfg.expert_d_ff
        layers.update(
            router=dense(keys[4], d, e, fan_in=d),
            w_gate=dense(keys[5], held, d, f, fan_in=d),
            w_in=dense(keys[6], held, d, f, fan_in=d),
            w_out=dense(keys[7], held, f, d, fan_in=f),
        )
        if cfg.router_gate == "sigmoid" and cfg.router_selection_bias:
            layers.update(router_bias=jnp.zeros(lead + (e,), jnp.float32))
        if cfg.n_shared_experts:
            fs = cfg.n_shared_experts * f
            layers.update(
                shared_gate=dense(next(more), d, fs, fan_in=d),
                shared_in=dense(next(more), d, fs, fan_in=d),
                shared_out=dense(next(more), fs, d, fan_in=fs),
            )
            if cfg.shared_expert_gate:
                layers.update(shared_scale=dense(next(more), d, 1, fan_in=d))
    else:
        f = cfg.d_ff
        layers.update(
            w_gate=dense(keys[5], d, f, fan_in=d),
            w_in=dense(keys[6], d, f, fan_in=d),
            w_out=dense(keys[7], f, d, fan_in=f),
        )
    return layers


def _inv_softplus(y: jnp.ndarray) -> jnp.ndarray:
    return y + jnp.log(-jnp.expm1(-y))


def _unit_weight(cfg: TransformerConfig, shape: Tuple[int, ...]) -> jnp.ndarray:
    """What a norm that :func:`_norm` applies starts from: ones, or zeros where it scales by ``1 + w``."""
    return (jnp.zeros if cfg.norm_zero_centered else jnp.ones)(shape, jnp.float32)


def _norm(cfg: TransformerConfig, x: jnp.ndarray, weight: jnp.ndarray) -> jnp.ndarray:
    """RMSNorm of a layer's input, of the stack's output, of q and k: ``norm_eps`` and ``norm_zero_centered``."""
    return rms_norm(x, weight, cfg.norm_eps, cfg.norm_zero_centered)


def init_params(rng, cfg: TransformerConfig) -> Dict[str, Any]:
    """Params as a pytree of float32 arrays. A model of one kind of layer:
    ``layers``, each leaf with leading [pp, Lp] axes. A declared pattern
    (:func:`layer_pattern`): ``lead`` and ``periods``, each a dict by kind of
    layer (``"<mixer>.<ff>"``) of that kind's leaves, with leading axes [n]
    (the leading layers of the kind) and [repeats, n] (those in a period).
    A multi-token-prediction module adds ``mtp``: the norms of its two inputs
    (``enorm``, ``hnorm``), ``eh_proj`` [2d, d], ``layer`` (the leaves of one
    layer of the last layer's kind, no leading axis) and its ``final_norm``.
    ``sandwich_norm`` adds ``post_ln1`` and ``post_ln2`` to every layer,
    ``exit_gate`` the float32 ``exit_gate``: ``w`` [d, 1] and ``b`` [1].
    ``tie_embeddings``: no ``out`` leaf."""
    keys = jax.random.split(rng, 16)
    d = cfg.d_model

    def dense(key, *shape, fan_in):
        return (
            jax.random.normal(key, shape, jnp.float32) * (fan_in**-0.5)
        )

    params: Dict[str, Any] = {
        # a tied table is a head's too: rows of unit length, as ``out``'s columns are — at normal(0, 1) a row the head
        # would score a position's own id at d / logits_scale (a loss of 255 at Granite's sizes, past bfloat16's steps)
        "embed": dense(keys[8], cfg.vocab_size, d, fan_in=float(d) if cfg.tie_embeddings else 1.0),
        "final_norm": _unit_weight(cfg, (d,)),
        "out": dense(keys[9], d, cfg.vocab_size, fan_in=d),
    }
    if cfg.tie_embeddings:
        del params["out"]  # the head reads ``embed``
    if cfg.n_mtp_modules:
        key = jax.random.fold_in(rng, 201)
        params["mtp"] = {
            "enorm": _unit_weight(cfg, (d,)),
            "hnorm": _unit_weight(cfg, (d,)),
            "eh_proj": dense(key, 2 * d, d, fan_in=2 * d),
            "layer": _init_layers(jax.random.fold_in(key, 1), cfg, cfg.layer_kinds()[-1], ()),
            "final_norm": _unit_weight(cfg, (d,)),
        }
    if cfg.exit_gate:  # drawn like any dense leaf, so that a seeded comparison exercises it
        key = jax.random.fold_in(rng, 301)
        params["exit_gate"] = {"w": dense(key, d, 1, fan_in=d), "b": dense(jax.random.fold_in(key, 1), 1, fan_in=d)}
    if _of_one_kind(cfg):
        lead = (max(cfg.pp, 1), cfg.layers_per_stage)
        params["layers"] = _init_layers(rng, cfg, cfg.layer_kinds()[0], lead)
        return params
    n_lead, period = layer_pattern(cfg)
    kinds = cfg.layer_kinds()
    repeats = (cfg.n_layers - n_lead) // len(period)
    params["lead"] = {
        key: _init_layers(jax.random.fold_in(rng, 1 + i), cfg, kind, (n,))
        for i, (key, (kind, n)) in enumerate(sorted(_group_sizes(kinds[:n_lead]).items()))
    }
    params["periods"] = {
        key: _init_layers(jax.random.fold_in(rng, 101 + i), cfg, kind, (repeats, n))
        for i, (key, (kind, n)) in enumerate(sorted(_group_sizes(period).items()))
    }
    return params


def _layer_specs(cfg: TransformerConfig, kind: Tuple[str, str], lead: Tuple[Any, ...]) -> Dict[str, Any]:
    """PartitionSpec per leaf of one kind of layer under the leading axes
    ``lead`` (matches :func:`_init_layers`)."""
    mixer, ff = kind

    def spec(*axes):
        return P(*lead, *axes)

    row, col = spec("fsdp", "tp"), spec("tp", "fsdp")
    layers: Dict[str, Any] = {"ln1": spec(None), "ln2": spec(None)}
    if cfg.sandwich_norm:
        layers.update(post_ln1=spec(None), post_ln2=spec(None))
    if mixer in _SOFTMAX_MIXERS:
        layers.update(wq=row, wk=row, wv=row, wo=col)
        if cfg.qk_norm_per_head:
            layers.update(q_norm=spec(None), k_norm=spec(None))  # one head wide, whole on every chip
        elif cfg.qk_norm:
            # over the tp-sharded projection: the norm's mean is one all-reduce
            layers.update(q_norm=spec("tp"), k_norm=spec("tp"))
        if cfg.sparse_topk:  # the selector's heads over tp as the core's; its one key head and the weights whole
            layers.update(idx_wq=row, idx_wk=spec("fsdp", None), idx_ww=spec("fsdp", None))
    elif mixer == "kda":
        # heads over tp, as full attention's; the narrow side of the low-rank gates whole
        layers.update(
            wq=row, wk=row, wv=row, wo=col,
            conv_q=spec(None, "tp"), conv_k=spec(None, "tp"), conv_v=spec(None, "tp"),
            w_fa=spec("fsdp", None), w_fb=spec(None, "tp"),
            w_ga=spec("fsdp", None), w_gb=spec(None, "tp"),
            w_beta=spec("fsdp", "tp"), a_log=spec("tp"), dt_bias=spec("tp"), o_norm=spec(None),
        )
    elif mixer == "gdn":
        # value and key heads over tp, the pair of narrow projections whole
        layers.update(
            wq=row, wk=row, wv=row, wo=col, w_z=row,
            conv_q=spec(None, "tp"), conv_k=spec(None, "tp"), conv_v=spec(None, "tp"),
            w_ba=spec("fsdp", None), a_log=spec("tp"), dt_bias=spec("tp"), o_norm=spec(None),
        )
    elif mixer == "conv":
        layers.update(conv_in=row, conv_w=spec(None, "tp"), conv_out=col)  # channels over tp
    elif mixer == "ssd":
        # whole over tp: one norm runs over all the channels and B and C serve all heads, so a share of the heads
        # would need the other chips' sum of squares — the partitioner keeps it correct, nothing here divides it
        layers.update(
            ssd_in=spec("fsdp", None), conv_w=spec(None, None), conv_b=spec(None), a_log=spec(None), dt_bias=spec(None),
            d_skip=spec(None), y_norm=spec(None), ssd_out=spec(None, "fsdp"),
        )
    else:
        # the narrow side of both low-rank pairs whole, heads over tp
        layers.update(w_kva=spec("fsdp", None), kv_norm=spec(None), w_kvb=spec(None, "tp"), wo=col)
        if cfg.q_lora_rank:
            layers.update(wq_a=spec("fsdp", None), q_a_norm=spec(None), wq_b=spec(None, "tp"))
        else:
            layers.update(wq=row)
    if ff == "experts":
        layers.update(
            router=spec("fsdp", None),
            w_gate=spec("ep", "fsdp", "tp"),
            w_in=spec("ep", "fsdp", "tp"),
            w_out=spec("ep", "tp", "fsdp"),
        )
        if cfg.router_gate == "sigmoid" and cfg.router_selection_bias:
            layers.update(router_bias=spec(None))
        if cfg.n_shared_experts:
            layers.update(shared_gate=row, shared_in=row, shared_out=col)
            if cfg.shared_expert_gate:
                layers.update(shared_scale=spec("fsdp", None))
    else:
        layers.update(w_gate=row, w_in=row, w_out=col)
    return layers


def param_specs(cfg: TransformerConfig) -> Dict[str, Any]:
    """PartitionSpec per leaf (matches init_params structure)."""
    specs: Dict[str, Any] = {
        # [V,D] with vocab UNSHARDED and D over (tp,fsdp): the same bytes
        # per device as the row+col P("tp","fsdp") layout, but the token
        # gather is fully local and the cotangent lands in the stored
        # layout — SPMD previously fell back to involuntary full
        # rematerialization on both (round-3 review missing #2)
        "embed": P(None, ("tp", "fsdp")),
        "final_norm": P(None),
        "out": P("fsdp", "tp"),
    }
    if cfg.tie_embeddings:
        del specs["out"]
    if cfg.n_mtp_modules:
        specs["mtp"] = {
            "enorm": P(None), "hnorm": P(None), "eh_proj": P("fsdp", "tp"),
            "layer": _layer_specs(cfg, cfg.layer_kinds()[-1], ()), "final_norm": P(None),
        }
    if cfg.exit_gate:
        specs["exit_gate"] = {"w": P(None, None), "b": P(None)}  # d + 1 numbers, whole on every chip
    if _of_one_kind(cfg):
        specs["layers"] = _layer_specs(cfg, cfg.layer_kinds()[0], ("pp", None))
        return specs
    n_lead, period = layer_pattern(cfg)
    specs["lead"] = {
        key: _layer_specs(cfg, kind, (None,))
        for key, (kind, _) in _group_sizes(cfg.layer_kinds()[:n_lead]).items()
    }
    specs["periods"] = {
        key: _layer_specs(cfg, kind, (None, None)) for key, (kind, _) in _group_sizes(period).items()
    }
    return specs


def _act_spec(sp_manual: bool = False) -> P:
    # inside a manual-sp region the sequence axis is already local; only
    # auto axes may appear in constraints
    return P(("dp", "fsdp"), None, None) if sp_manual else P(("dp", "fsdp"), "sp", None)


def _constrain(x: jnp.ndarray, spec: P) -> jnp.ndarray:
    """with_sharding_constraint that no-ops when there is no context mesh
    (single-chip / unsharded use)."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh is None or mesh.empty:
        return x
    return jax.lax.with_sharding_constraint(x, spec)


def _ffn_dense(lp: Dict[str, Any], x: jnp.ndarray) -> jnp.ndarray:
    return swiglu(x, lp["w_gate"], lp["w_in"], lp["w_out"])


def _chosen(scores: jnp.ndarray, top_idx: jnp.ndarray) -> jnp.ndarray:
    """``scores`` [T, E] at ``top_idx`` [T, k], as a compare, a select and a
    sum over E — vector work in one fusion, and its transpose the same select
    summed over k — where a gather reads T·k scalars one by one and its
    transpose scatters them. One term of each sum is not zero, so the values
    and both gradients are the gather's to the last bit."""
    chosen = top_idx[..., None] == jnp.arange(scores.shape[-1], dtype=top_idx.dtype)
    return jnp.sum(jnp.where(chosen, scores[:, None, :], 0.0), axis=-1)


def _route(lp: Dict[str, Any], tokens: jnp.ndarray, cfg: TransformerConfig):
    """(weights [T, k] as they are applied, experts [T, k], scores [T, E]
    float32 summing to one over E) of the router's gate. The sigmoid gate's
    weights are read by :func:`_chosen` and not by ``jnp.take_along_axis``:
    that gather of T·k scalars took 1.34 ms a layer a pass on a TPU v5e at
    [16384, 256] and k 8 where the select takes 0.42, and its scatter-add more."""
    logits = jnp.dot(tokens, lp["router"], preferred_element_type=jnp.float32)
    if cfg.router_gate == "softmax":
        probs = jax.nn.softmax(logits, axis=-1)
        top_w, top_idx = jax.lax.top_k(probs, cfg.top_k)
        if cfg.router_renormalize:
            top_w = top_w / (jnp.sum(top_w, axis=-1, keepdims=True) + cfg.router_norm_eps)
        return top_w, top_idx, probs
    scores = jax.nn.sigmoid(logits)
    # the bias moves which experts are chosen and not what they weigh
    biased = scores + lp["router_bias"].astype(jnp.float32) if "router_bias" in lp else scores
    _, top_idx = jax.lax.top_k(biased, cfg.top_k)
    top_w = _chosen(scores, top_idx)
    if cfg.router_renormalize:
        top_w = top_w / (jnp.sum(top_w, axis=-1, keepdims=True) + cfg.router_norm_eps)
    top_w = top_w * cfg.routed_scaling_factor
    return top_w, top_idx, scores / jnp.sum(scores, axis=-1, keepdims=True)


def _held_row_bound(cfg: TransformerConfig, rows: int) -> int:
    """Rows the grouped matmuls of a layer under a share are sized for:
    twice what the held experts draw at balance, in whole tiles of 512 —
    ``moe_dropless_held`` takes all ``rows`` in a step that exceeds it."""
    at_balance = rows * cfg.experts_held / cfg.n_experts
    return min(rows, -(-int(2 * at_balance) // 512) * 512)


def _router_load(cfg: TransformerConfig, top_idx: jnp.ndarray, probs: jnp.ndarray, counts=None):
    """(the balance term ``E·Σ_e f_e·P_e`` over the call's tokens, tokens per
    expert [E] int32 — counted here from ``top_idx`` [T, k] unless given)."""
    if counts is None:
        flat = top_idx.reshape(-1)
        counts = jnp.sum(flat[:, None] == jnp.arange(cfg.n_experts, dtype=flat.dtype), axis=0, dtype=jnp.int32)
    frac = counts.astype(jnp.float32) / top_idx.shape[0]  # sums to k; no gradient
    return cfg.n_experts * jnp.sum(frac * jnp.mean(probs, axis=0)), counts


def _gate_ahead(lp: Dict[str, Any], h: jnp.ndarray, cfg: TransformerConfig):
    """The router's gate under ``router_input`` "layer": :func:`_route` of the
    layer's normed input ``h`` [B, S, d], ahead of the mixer. Returns ((weights
    [T, k] as they are applied, experts [T, k]) — what :func:`_ffn_moe` takes as
    ``gate`` — and (balance term, tokens per expert [E])). The weights carry
    their gradient back through ``h`` into the layer's input, beside the mixer's."""
    tokens = h.reshape(-1, h.shape[-1])
    with jax.named_scope("router"):
        top_w, top_idx, probs = _route(lp, tokens, cfg)
        return (top_w, top_idx), _router_load(cfg, top_idx, probs)


def _ffn_moe(lp: Dict[str, Any], x: jnp.ndarray, cfg: TransformerConfig, gate=None):
    """Sparse experts, dropless: the router's gate (:func:`_route`) picks k
    experts a token, every chosen expert counted whatever its load; under a
    share (``n_experts_held``) the experts held here compute their part and
    what the absent ones would add is left out; a shared expert is a dense
    gated FFN beside them, scaled a token by ``sigmoid(h·shared_scale)`` under
    ``shared_expert_gate``. ``gate``: the (weights, experts) that
    :func:`_gate_ahead` chose ahead of the mixer — the router is then not run
    here, and its load is not said here. Returns (y, (balance term
    ``E·Σ_e f_e·P_e`` over the call's tokens, tokens per expert [E]) unless
    ``gate`` is given, then under a share the rows held and their share of the
    window's slots (past 1: the layer took every window), under a ReLU the share
    of the computed rows' gate lanes it zeroed, under ``shared_expert_gate``
    that gate's mean: :func:`_moe_said` names them)."""
    b, s, d = x.shape
    tokens = x.reshape(b * s, d)
    if gate is None:
        with jax.named_scope("router"):
            top_w, top_idx, probs = _route(lp, tokens, cfg)
    else:
        top_w, top_idx = gate
    # an activation that leaves lanes exactly zero says how many
    act = dict(activation=_ACTIVATIONS[cfg.expert_activation], gate_zeros=cfg.expert_activation != "silu")
    if cfg.n_experts_held:
        bound = _held_row_bound(cfg, b * s * cfg.top_k)
        y, held, *zeroed = moe_dropless_held(
            tokens, top_idx, top_w.astype(x.dtype), lp["w_gate"], lp["w_in"], lp["w_out"],
            first_expert=cfg.expert_share_index * cfg.n_experts_held,
            row_bound=bound,
            **act,
        )
        counts = None
        # beside the rows held, their share of the window the row moves and grouped matmuls are sized for
        more = (held, held.astype(jnp.float32) / bound, *zeroed)
    else:
        y, counts, *zeroed = moe_dropless(
            tokens, top_idx, top_w.astype(x.dtype), lp["w_gate"], lp["w_in"], lp["w_out"], **act
        )
        more = tuple(zeroed)
    if gate is None:
        with jax.named_scope("router"):
            more = _router_load(cfg, top_idx, probs, counts) + more
    if cfg.n_shared_experts:
        with jax.named_scope("shared"):
            shared = swiglu(
                tokens, lp["shared_gate"], lp["shared_in"], lp["shared_out"], _ACTIVATIONS[cfg.expert_activation]
            )
            if cfg.shared_expert_gate:
                gate_s = jax.nn.sigmoid(jnp.dot(tokens, lp["shared_scale"], preferred_element_type=jnp.float32))
                shared = shared * gate_s.astype(x.dtype)
                more += (jnp.mean(gate_s),)
            y = y + shared
    return y.reshape(b, s, d), more


def _moe_said(cfg: TransformerConfig, aux, gate_ahead: bool = False) -> Dict[str, jnp.ndarray]:
    """What :func:`_ffn_moe` says beside its output, by name (``gate_ahead``:
    it was handed its gate, and the router's load is :func:`_gate_ahead`'s to say)."""
    names = (
        ("balance", "counts") * (not gate_ahead) + ("held", "live_share") * bool(cfg.n_experts_held)
        + ("gate_zeros",) * (cfg.expert_activation != "silu") + ("shared_gate",) * cfg.shared_expert_gate
    )
    return dict(zip(names, aux))


def _ffn_moe_ep(lp: Dict[str, Any], x: jnp.ndarray, cfg: TransformerConfig) -> jnp.ndarray:
    """Experts over chips (``ep`` > 1) ONLY: the top-2 einsum dispatch that
    drops tokens over ``capacity_factor`` and renormalises the two gates —
    not OLMoE's mathematics, no balance term. Kept untouched until ROADMAP
    R4 gives ``moe_dropless`` a ``shard_map`` over ``ep``."""
    b, s, d = x.shape
    g = b * s
    tokens = x.reshape(g, d)
    gates = jax.nn.softmax(
        (tokens @ lp["router"]).astype(jnp.float32), axis=-1
    ).astype(x.dtype)
    capacity = max(
        1, int(np.ceil(2 * g / cfg.n_experts * cfg.capacity_factor))
    )
    dispatch, combine = moe_dispatch(gates, capacity)
    # [G,E,C] x [G,D] -> [E,C,D]: the all-to-all over `ep` falls out of the
    # expert-axis sharding on the einsum operands
    expert_in = jnp.einsum("gec,gd->ecd", dispatch, tokens)
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", expert_in, lp["w_gate"])) * jnp.einsum(
        "ecd,edf->ecf", expert_in, lp["w_in"]
    )
    expert_out = jnp.einsum("ecf,efd->ecd", h, lp["w_out"])
    out = jnp.einsum("gec,ecd->gd", combine, expert_out)
    return out.reshape(b, s, d)


def _flash_threshold_bytes() -> float:
    """Scores-memory ceiling above which auto engages the pallas kernel
    whatever its speed: when the materialized [B,H,S,S] scores exceed
    this, XLA's plain attention stops fitting HBM and the kernel's
    O(S·block) memory is the only option. Override via
    TORCHFT_TPU_FLASH_SCORES_GB for chips with a different HBM budget."""
    import os

    raw = os.environ.get("TORCHFT_TPU_FLASH_SCORES_GB", "4")
    try:
        return float(raw) * 1e9
    except ValueError:
        import logging

        logging.getLogger(__name__).warning(
            "ignoring malformed TORCHFT_TPU_FLASH_SCORES_GB=%r; using 4", raw
        )
        return 4e9


def _use_flash(
    cfg: TransformerConfig, seq_len: int, batch: int = 1, mesh=None
) -> bool:
    """The Pallas kernel as the MEMORY path: asked for by name, or (auto)
    because plain attention's scores would not fit. The speed rule is
    :func:`_flash_blocks`, which :func:`_attention_path` asks first."""
    if cfg.attention_impl in ("plain", "chunked"):
        return False
    if cfg.attention_impl == "flash":
        return True
    if cfg.attention_impl != "auto":
        raise ValueError(
            "attention_impl must be 'auto'|'plain'|'chunked'|'flash', "
            f"got {cfg.attention_impl!r}"
        )
    # The estimate divides the global shapes by the mesh's batch (dp·fsdp)
    # and head (tp) factors, and uses 4 bytes/element: plain attention's
    # softmax runs in f32 whatever the compute dtype.
    itemsize = max(jnp.dtype(cfg.dtype).itemsize, 4)
    batch_shards = heads_shards = 1
    if mesh is not None:
        batch_shards = mesh.shape.get("dp", 1) * mesh.shape.get("fsdp", 1)
        heads_shards = mesh.shape.get("tp", 1)
    scores_bytes = (
        float(itemsize)
        * max(1, batch // batch_shards)
        * max(1, cfg.n_heads // heads_shards)
        * seq_len
        * seq_len
    )
    return (
        jax.default_backend() == "tpu"
        and scores_bytes > _flash_threshold_bytes()
        and seq_len % 128 == 0
    )


def _flash_blocks(seq_len: int, head_dim: int) -> Optional[Tuple[int, int]]:
    """(block_q, block_k) at which the Pallas kernel is the fastest causal
    core measured on a v5e, or None where plain / chunked keep the shape.

    Measured, device time of the whole call (PERF.md §6 holds the tables).
    At 16 heads x 128 (my chip run, PR 31), forward + ``remat``'s forward +
    backward: b8 x s2048 8.3 ms a layer at 512 x 512 against 16.3 ms chunked
    and 22.2 plain (1024 x 1024: 8.8; 256 x 512: 11.6; 128 x 128: 24.4);
    b4 x s4096 13.8 against 25.5; b8 x s1024 3.1 against 4.9.

    At head_dim 64 (my chip run, PR 52; the kernel through ``[B, H, S, Dh]``,
    its forward once, as ``_remat`` has kept it since PR 47), 512 x 512
    against chunked: b2 x s8192 x 32 heads over 8 28.5 against 92.4 ms
    (1024 x 1024: 28.0; 256 x 512: 38.5; the lanes padded to 128 and read in
    place: 31.3; the transposes are 0.6 of the 28.5, ~2 in the cell's step);
    under a band of 512 there 10.0 against 21.1; b4 x s4096 x 32 over 8 17.2
    against 49.6; b8 x s2048 x 16 6.3 against 9.2. At b4 x s1024 x 16
    (``scale_647M``) the kernel's 1.02 beat the scan's 1.28 too — its 1.25
    with the forward run twice was PR 31's tie — but without ``remat`` its
    residuals (q, k, v, o in ``[B, H, S, 64]``, half-filled lane tiles) cost
    that preset's ``grads`` 0.40 GB more (7.38 against 6.98 of temporaries,
    compiled for a described v5e) and ``chip_smoke.py``'s steady phase no
    longer loaded on the chip: 64 lanes engage from s2048. Other widths short
    of a lane tile, and anything below s1024, were not measured: chunked /
    plain keep them.

    At head_dim 256 (my chip run, PR 54; ``qwen3-next-80b-a3b-1g``'s one softmax
    layer, b2 x s8192 x 16 heads over 2): 512 x 512 with 8 192 keys of 256
    lanes resident compiles, loads and reads 8.19 ms forward + 16.04 backward a
    step, 65.4 % of the core's roofline — the highest share of any core here
    (two lane tiles a head fill the MXU's contraction twice over). No other
    tile and not the scan were measured at this width: the rule for whole lane
    tiles stands."""
    if head_dim % 128 == 0:
        least = 1024
    elif head_dim == 64:
        least = 2048
    else:
        return None
    if seq_len % 512 or seq_len < least:
        return None
    return 512, 512


def _attn_chunk(seq_len: int) -> int:
    """Sequence-aware q-block size of :func:`chunked_attention`;
    TORCHFT_TPU_ATTN_CHUNK overrides (an unparseable value is IGNORED, not
    treated as an override). 128 everywhere but s=16k (256), from sweeps of
    a d512 / head_dim 64 model on a v5e before the benchmark existed; not
    measured at the cells' widths, where "auto" no longer takes this path
    on a TPU (PERF.md §6, PR 31)."""
    import os

    raw = os.environ.get("TORCHFT_TPU_ATTN_CHUNK")
    if raw:
        try:
            return int(raw)
        except ValueError:
            pass  # fall through to the sequence-aware default
    return 256 if seq_len == 16384 else 128


def _attn_tiers() -> Optional[int]:
    """Causal k-prefix tier count override (TORCHFT_TPU_ATTN_TIERS);
    unset/invalid -> None, i.e. chunked_attention's adaptive pick."""
    import os

    raw = os.environ.get("TORCHFT_TPU_ATTN_TIERS")
    if not raw:
        return None
    try:
        return int(raw)
    except ValueError:
        return None


def _use_chunked(cfg: TransformerConfig, seq_len: int) -> bool:
    """Route to :func:`chunked_attention`: by name, or (auto) from s = 1024
    on, where plain attention's f32 [S,S] scores round-trip HBM. Pure XLA —
    works under GSPMD sharding AND inside the pipeline's manual region,
    unlike the pallas kernel, so it is what "auto" takes there, on a CPU,
    and at the shapes :func:`_flash_blocks` declines (a head width that is
    neither 64 nor whole lane tiles, 64 lanes below s2048, a length its
    tiles do not divide).
    Override the engage point with TORCHFT_TPU_ATTN_CHUNKED_MIN_S. Sequences
    not divisible by the chunk fall back to plain (both explicit and auto)."""
    if seq_len % _attn_chunk(seq_len) != 0:
        return False
    if cfg.attention_impl == "chunked":
        return True
    if cfg.attention_impl != "auto":
        return False
    import os

    try:
        min_s = int(os.environ.get("TORCHFT_TPU_ATTN_CHUNKED_MIN_S", "1024"))
    except ValueError:
        min_s = 1024
    return seq_len >= min_s


def _attention_path(
    cfg: TransformerConfig, seq_len: int, batch: int, mesh, sp_manual: bool = False,
    widths: Optional[Tuple[int, int]] = None, window: int = 0, grouped: bool = False, diffusion: bool = False,
    sparse: bool = False,
) -> Tuple[str, str, Optional[Tuple[int, int]]]:
    """(impl, reason, (block_q, block_k) or None): which code computes the
    causal core softmax(QKᵀ)V of a layer, decided from what can be
    observed — the backend, the mesh, whether the caller is already inside
    a manual region, the shapes and the layer's ``window``. impl is "ring"
    (sp > 1), "flash" (the Pallas kernel: banded under a window, a group's
    one key/value head read in place under ``grouped`` heads), "chunked" or
    "plain", each of which takes the same two. ``widths``: a head's (key,
    value) widths where they are not ``cfg.head_dim`` (a latent attention's):
    the kernel reads heads in place when the VALUES are whole lane tiles and
    pads the keys with zero columns to the next one. ``diffusion``: the rows are
    a block-diffusion sequence ``[noised ; clean]`` (``seq_len`` counts both
    halves): the kernel where "auto" takes it at the HALF's length (its tiles
    divide a half) or it is asked for, else plain attention under the dense
    staircase mask — the ring and the chunked scan do not know the rule.
    ``sparse``: the core runs under a learned selection (``sparse_topk``): the
    kernel under the table of selected pairs where "auto" takes it or it is
    asked for, else plain attention under the dense table (:func:`_sparse_path`)."""
    sp_size = mesh.shape.get("sp", 1) if mesh is not None else 1
    if sparse and sp_size > 1:
        raise ValueError(
            f"sp={sp_size} with sparse_topk={cfg.sparse_topk}: ring attention (ops/attention.ring_attention_local) passes "
            "every K/V block to every shard; a selection under the ring — a query's k largest over ALL shards' keys (a "
            "threshold agreed across the ring) and the selector's scores against keys a shard does not hold — is missing"
        )
    if batch == 0:  # a batch of one sequence sliced past its end (benchmark/worker.py's second sequence): no kernel has a grid of none
        return "plain", "an empty batch", None
    if diffusion:
        return _diffusion_path(cfg, seq_len, batch, mesh, sp_manual, sp_size)
    if sparse:
        return _sparse_path(cfg, seq_len, batch, mesh)
    if sp_size > 1 and (window or grouped):
        raise ValueError(
            f"sp={sp_size} with a window ({window}) or grouped-query heads: ring attention "
            "(ops/attention.ring_attention_local) passes every K/V block to every shard and gives each "
            "query head its own; the band's early stop (only the shards that hold keys i - window < j <= i "
            "take part) and the group map of a shard's key/value heads are missing"
        )
    if sp_size > 1:
        if widths is not None and widths[0] != widths[1]:
            raise ValueError(
                f"sp={sp_size} with keys {widths[0]} and values {widths[1]} wide: ring attention "
                "(ops/attention.ring_attention_local) accumulates in the keys' width; it has to "
                "learn a value width before a latent attention's sequence can be sharded"
            )
        return "ring", "sp > 1: the sequence is sharded over chips", None
    # the kernel needs its own (full) manual region, which cannot nest in
    # the pipeline's partial-manual shard_map (Shardy rejects it)
    inside_manual = sp_manual or (mesh is not None and mesh.shape.get("pp", 1) > 1)
    fast = _flash_blocks(seq_len, widths[1] if widths else cfg.head_dim)
    if (
        cfg.attention_impl == "auto"
        and fast is not None
        and jax.default_backend() == "tpu"
        and not inside_manual
    ):
        return "flash", "auto on a tpu: the fastest core measured at this (seq, head_dim)", fast
    if _use_chunked(cfg, seq_len):
        why = "attention_impl" if cfg.attention_impl == "chunked" else (
            "auto: seq past the chunked engage point"
        )
        return "chunked", why, None
    if _use_flash(cfg, seq_len, batch, mesh):
        if inside_manual:
            # no fallback: flash was picked because plain attention's
            # scores cannot fit either — pp>1 long-context should shard the
            # sequence (sp), which routes to ring attention above
            raise ValueError(
                f"flash attention (attention_impl={cfg.attention_impl!r}, "
                f"b{batch} s{seq_len}) cannot run inside the pipeline's manual "
                "region (pp>1); shard the sequence (sp>1, ring "
                "attention) for long context under pp"
            )
        why = "attention_impl" if cfg.attention_impl == "flash" else (
            "auto: plain attention's scores would not fit the chip"
        )
        return "flash", why, fast or (128, 128)  # the kernel clamps a tile to S
    why = {
        "plain": "attention_impl",
        "chunked": "seq is not a multiple of the chunk",
    }.get(cfg.attention_impl, "auto: a short sequence, or no multiple of the chunk")
    return "plain", why, None


def _diffusion_path(cfg: TransformerConfig, rows: int, batch: int, mesh, sp_manual: bool, sp_size: int):
    """:func:`_attention_path` of a block-diffusion layer over ``rows`` = 2·S rows."""
    if sp_size > 1:
        raise ValueError(
            f"sp={sp_size} with diffusion_block={cfg.diffusion_block}: ring attention (ops/attention.ring_attention_local) "
            "masks by k_pos <= q_pos on one run of positions; a ring over [noised ; clean] — which shard holds which half, "
            "and the staircase between a shard's rows and the block it is passed — is missing"
        )
    inside_manual = sp_manual or (mesh is not None and mesh.shape.get("pp", 1) > 1)
    fast = _flash_blocks(rows // 2, cfg.head_dim)
    if cfg.attention_impl == "auto" and fast is not None and jax.default_backend() == "tpu" and not inside_manual:
        return "flash", "auto on a tpu: the kernel walks the staircase's live tiles", fast
    if _use_flash(cfg, rows, batch, mesh) and not inside_manual:
        why = "attention_impl" if cfg.attention_impl == "flash" else "auto: the dense staircase's scores would not fit the chip"
        return "flash", why, fast or (128, 128)
    return "plain", "the staircase as a dense mask", None


def _sparse_path(cfg: TransformerConfig, seq_len: int, batch: int, mesh):
    """:func:`_attention_path` of a core under a learned selection: the kernels
    (the selection as a table of int8 tiles in the flash kernel's walk, the
    selector's scores and its target a q block at a time) where "auto" takes
    the kernel for the dense core or it is asked for; else dense tables."""
    if mesh is not None and any(n > 1 for n in mesh.shape.values()):
        raise ValueError(
            f"sparse_topk={cfg.sparse_topk} on a mesh of {dict(mesh.shape)}: the selection's kernels run on one chip's "
            "whole batch and heads; their manual region over dp / fsdp / tp (the table of pairs split by batch, the "
            "selector's target summed over a shard's heads and then over tp) is missing"
        )
    fast = _flash_blocks(seq_len, cfg.head_dim)
    if cfg.attention_impl == "auto" and fast is not None and jax.default_backend() == "tpu":
        return "flash", "auto on a tpu: the kernel's dense walk under the table of selected pairs", fast
    if _use_flash(cfg, seq_len, batch, mesh) and seq_len % 128 == 0:
        why = "attention_impl" if cfg.attention_impl == "flash" else "auto: the dense table's scores would not fit the chip"
        return "flash", why, fast or (128, 128)
    return "plain", "the selection as a dense table", None


def _live_tiles(rows: int, blocks: Optional[Tuple[int, int]]) -> Tuple[int, int]:
    """(tiles the staircase kernel visits, tiles of the square) a head over
    ``rows`` = 2·S rows at ``blocks``: a noised q tile its own and the clean
    ones up to it, a clean q tile the clean ones up to it."""
    if not blocks:
        return 0, 0
    bq, bk = (min(b, rows // 2) for b in blocks)
    ends = [(i + 1) * bq for i in range(rows // 2 // bq)]  # a q tile's last position + 1, in either half
    upto = sum(-(-e // bk) for e in ends)  # clean key tiles that start before it
    return 2 * upto + sum(-(-e // bk) - (e - bq) // bk for e in ends), (rows // bq) * (rows // bk)


_PATHS_SAID: set = set()


def _say_once(kind: str, key, **fields) -> None:
    """One ``kind`` event and one INFO line with the same text, once a
    process for each ``key``."""
    if (kind, key) in _PATHS_SAID:
        return
    _PATHS_SAID.add((kind, key))
    import logging

    from torchft_tpu import telemetry

    telemetry.emit(kind, **fields)
    logging.getLogger(__name__).info(
        "%s %s", kind, " ".join(f"{k}={v}" for k, v in fields.items())
    )


def _say_attention_path(
    impl, reason, blocks, batch, seq_len, cfg, widths=None, kind=None, latent=None, diffusion=False, sparse=False,
) -> None:
    """One ``attention_path`` event and one INFO line per traced shape — and
    per KIND of layer where a model declares a band or grouped heads
    (``kind``: its (query heads, key/value heads, window, rotated lanes)); a
    latent attention says its query's rank and its rotated lanes (``latent``)
    — so a worker's log and event trail say which core every program took and why."""
    block_q, block_k = blocks or (0, 0)
    key_dim, value_dim = widths or (cfg.head_dim, cfg.head_dim)
    fields = dict(
        impl=impl, block_q=block_q, block_k=block_k, batch=batch, seq=seq_len,
        head_dim=key_dim, value_dim=value_dim, reason=reason,
    )
    heads = cfg.n_heads
    if kind is not None:
        heads, kv_heads, window, rotary_dim = kind
        fields.update(n_heads=heads, n_kv_heads=kv_heads, window=window, rotary_dim=rotary_dim)
    if latent is not None:
        fields.update(q_lora_rank=latent[0], rotary_dim=latent[1])
    if diffusion:  # ``seq`` counts both halves; the tiles the kernel visits of the square's, a head
        live, square = _live_tiles(seq_len, blocks if impl == "flash" else None)
        fields.update(diffusion_block=cfg.diffusion_block, live_tiles=live, tiles=square)
    if sparse:  # the selector, and how its selection reaches the core
        fields.update(
            sparse_topk=cfg.sparse_topk, indexer=f"{cfg.indexer_heads}x{cfg.indexer_head_dim}",
            selection="int8 tiles [S/block_q, B, S, block_q] in the kernel's walk, dead tiles skipped" if impl == "flash"
            else "a dense [B, S, S] table",
            threshold="bisected on the float32 bits, exact",
        )
    _say_once("attention_path", (*fields.values(), heads), **fields)


def _say_gdn_core_path(core: str, batch: int, block: int, cfg: TransformerConfig) -> None:
    """One ``gdn_core_path`` event and one INFO line per traced shape of a
    Gated DeltaNet mixer: which code ``ops/kda.gdn_chunked`` took for a block
    of it (``ops/kda.gdn_core``: the Pallas kernel pair ``gdn_kernel``, or
    ``jax.numpy``), and the heads it saw."""
    fields = dict(
        core=core, heads=cfg.linear_n_heads, key_heads=cfg.linear_key_heads, head_dim=cfg.linear_head_dim,
        chunk=64, batch=batch, block=block,  # the chunk ``gdn_chunked`` takes unasked, which is how the mixer calls it
    )
    _say_once("gdn_core_path", tuple(fields.values()), **fields)


def _say_ssd_core_path(core: str, batch: int, block: int, cfg: TransformerConfig) -> None:
    """One ``ssd_core_path`` event and one INFO line per traced shape of a
    state-space mixer: which code ``ops/kda.ssd_chunked`` took for a block of it
    (``ops/kda.ssd_core``: the Pallas kernel pair ``ssd_kernel``, or
    ``jax.numpy``), its chunk, heads, state and block."""
    fields = dict(
        core=core, heads=cfg.ssd_n_heads, head_dim=cfg.ssd_head_dim, state=cfg.ssd_state_dim,
        groups=cfg.ssd_n_groups, chunk=min(_SSD_CHUNK, block), batch=batch, block=block,
    )
    _say_once("ssd_core_path", tuple(fields.values()), **fields)


def _say_expert_path(cfg: TransformerConfig, batch: int, seq_len: int) -> None:
    """One ``expert_path`` event and INFO line per traced shape of a model whose
    expert layers take their gate from the layer's input or gate with another
    activation than SiLU: where the router reads, which activation the experts
    use, and how many of the router's experts are held."""
    fields = dict(
        router_input=cfg.router_input, activation=cfg.expert_activation, router_gate=cfg.router_gate,
        renormalize=cfg.router_renormalize, experts=cfg.n_experts, experts_held=cfg.experts_held, top_k=cfg.top_k,
        batch=batch, seq=seq_len,
    )
    _say_once("expert_path", tuple(fields.values()), **fields)


def _say_layer_pattern(cfg: TransformerConfig, batch: int, seq_len: int) -> None:
    """One ``layer_pattern`` event and INFO line per traced shape of a model
    with a declared pattern: what the stack unrolls and what it scans."""
    n_lead, period = layer_pattern(cfg)
    kinds = cfg.layer_kinds()
    fields = dict(
        layers=cfg.n_layers, lead=",".join(_kind_key(k) for k in kinds[:n_lead]) or "-",
        period=",".join(_kind_key(k) for k in period),
        repeats=(cfg.n_layers - n_lead) // len(period),
        experts_held=cfg.experts_held, experts=cfg.n_experts, batch=batch, seq=seq_len,
    )
    if cfg.n_mtp_modules:  # the module behind the stack, by the kind of its layer
        fields.update(mtp=_kind_key(kinds[-1]), mtp_weight=cfg.mtp_loss_weight)
    if cfg.conv_layers:
        fields.update(conv_kernel=cfg.conv_kernel)
    if cfg.sparse_topk:
        fields.update(sparse_topk=cfg.sparse_topk, indexer_heads=cfg.indexer_heads, indexer_head_dim=cfg.indexer_head_dim)
    if cfg.ssd_layers:
        fields.update(
            ssd_heads=cfg.ssd_n_heads, ssd_head_dim=cfg.ssd_head_dim, ssd_state=cfg.ssd_state_dim, conv_kernel=cfg.conv_kernel,
            tied=cfg.tie_embeddings,
        )
    _say_once("layer_pattern", tuple(fields.values()), **fields)


def _flash_sharded(q, k, v, mesh, blocks: Tuple[int, int], window: Optional[int] = None, block_diffusion: int = 0):
    """Flash attention under GSPMD: pallas_call has no partitioning rules,
    so without shard_map the SPMD partitioner would all-gather q/k/v onto
    every chip. Attention is independent per (batch, head), so manualize
    the batch/head axes and run the kernel per shard (grouped heads: a
    shard's query heads read the shard's own key/value heads, ``tp`` divides both)."""
    from torchft_tpu.ops.pallas.flash_attention import flash_attention

    kernel = functools.partial(
        flash_attention, causal=True, block_q=blocks[0], block_k=blocks[1], window=window, block_diffusion=block_diffusion,
    )
    if mesh is None:
        return kernel(q, k, v)
    spec = P(("dp", "fsdp"), None, "tp", None)
    return jax.shard_map(
        kernel,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        # ALL mesh axes must be manual here: any axis left auto keeps the
        # region under the SPMD partitioner, which refuses Mosaic calls
        # even at axis size 1 (tpu_custom_call "cannot be automatically
        # partitioned"). Axes beyond dp/fsdp/tp are replicated by the spec.
        axis_names=set(mesh.axis_names),
        # pallas_call's out_shape carries no varying-manual-axes type, which
        # the VMA checker would require; the kernel is per-shard local so
        # the check adds nothing here
        check_vma=False,
    )(q, k, v)


def _causal_core(cfg, mesh, sp_manual, q, k, v, widths=None, scope="core", window=0, kind=None, latent=None):
    """softmax(QKᵀ)V by the code :func:`_attention_path` picks, said once.
    ``window``: the layer's band; k and v may have fewer heads than q."""
    b, s = q.shape[:2]
    sp_size = mesh.shape.get("sp", 1) if mesh is not None else 1
    # s is the sp-local block inside a manual region; the rule
    # reads sp from the mesh, not from s
    diffusion = cfg.diffusion_block  # then the rows are [noised ; clean] and the rule is the staircase's
    impl, why, blocks = _attention_path(
        cfg, s, b, mesh, sp_manual, widths=widths, window=window, grouped=k.shape[2] != q.shape[2], diffusion=bool(diffusion)
    )
    _say_attention_path(impl, why, blocks, b, s, cfg, widths, kind, latent, bool(diffusion))
    band = window or None  # the cores' "no band"
    with jax.named_scope(scope):
        if diffusion and impl == "flash":
            return _flash_sharded(q, k, v, mesh, blocks, block_diffusion=diffusion)
        if diffusion:
            return attention(q, k, v, block_diffusion=diffusion)
        if impl == "ring" and sp_manual:
            return ring_attention_local(q, k, v, sp_size, causal=True)
        if impl == "ring":
            return ring_attention(q, k, v, mesh, causal=True)
        if impl == "flash":
            return _flash_sharded(q, k, v, mesh, blocks, band)
        if impl == "chunked":
            return chunked_attention(
                q, k, v, causal=True, chunk=_attn_chunk(s),
                tiers=_attn_tiers(), window=band,
            )
        return attention(q, k, v, causal=True, window=band)


def _declares_kinds(cfg: TransformerConfig) -> bool:
    """Whether the model declares what makes its softmax layers differ by
    kind: a band, layers without positions, grouped heads, heads by layer or a
    table of frequencies."""
    return bool(
        cfg.window_layers or cfg.nope_layers or cfg.n_kv_heads or cfg.n_heads_per_layer
        or cfg.rope_pairing != "interleaved"
    )


def _rotation(cfg: TransformerConfig, mixer: str) -> Optional[Dict[str, Any]]:
    """``rotary_embed``'s arguments for a ``full`` or ``window`` layer: the
    one ``rope_theta`` with today's lane pairing, or under ``rope_pairing``
    "half" the kind's own table — window layers the whole head at their own
    base, global layers ``rotary_dim`` lanes under YaRN. None for a ``nope``
    layer: q and k go to the core as they were projected."""
    if mixer == "nope":
        return None
    if cfg.rope_pairing != "half":
        return {"theta": cfg.rope_theta}
    if mixer == "window":
        return {"inv_freq": yarn_inv_freq(cfg.head_dim, cfg.window_rope_theta or cfg.rope_theta)}
    table = yarn_inv_freq(
        cfg.rotary_dim or cfg.head_dim, cfg.rope_theta, cfg.yarn_factor, cfg.yarn_original_max,
        cfg.yarn_beta_fast, cfg.yarn_beta_slow,
    )
    return {"inv_freq": table, "scale": cfg.yarn_attention_factor if cfg.yarn_factor else 1.0}


def _mix_full(cfg, mesh, sp_manual, lp, h, mixer="full"):
    """Softmax attention: ``full`` (global) or ``window`` (banded), each kind
    with its own query heads over the model's key/value heads and its own
    rotation, or ``nope`` (global, no positions: nothing is rotated; in the
    trace it is ``global`` too, and its ``attention_path`` line says
    ``rotary_dim`` 0); under ``attn_output_gate`` the query
    projection carries a gate a lane, ``[q | gate]`` head by head, and the
    core's output goes through ``sigmoid(gate)`` (scope ``out_gate``). Under
    a learned selection (``sparse_topk``) the core is :func:`_sparse_core` and
    the mixer says ``(output, what the selector said)``."""
    sp_size = mesh.shape.get("sp", 1) if mesh is not None else 1
    b, s, _ = h.shape  # s is the sp-local block inside a manual region
    if sp_manual and sp_size > 1:
        positions = jax.lax.axis_index("sp") * s + jnp.arange(s)
    elif cfg.diffusion_block:  # the rows are [noised ; clean]: a position occurs twice
        positions = jnp.arange(s) % (s // 2)
    else:
        positions = jnp.arange(s)
    heads, kv_heads = cfg.mixer_heads(mixer), cfg.kv_heads
    # a model that declares kinds names them in the trace: attn/window and attn/global, the core inside each; a
    # block-diffusion layer is a kind of its own, attn/blockdiff with blockdiff_core inside
    name = ("window" if mixer == "window" else "global") if _declares_kinds(cfg) else None
    if cfg.diffusion_block:
        name = "blockdiff"
    if cfg.sparse_topk:  # a kind of its own too: attn/sparse with indexer, select, sparse_core and indexer_loss inside
        name = "sparse"
    with jax.named_scope(name) if name else contextlib.nullcontext():
        q, k = h @ lp["wq"], h @ lp["wk"]
        if cfg.attn_output_gate:
            q, gate = (
                x.reshape(b, s, heads * cfg.head_dim)
                for x in jnp.split(q.reshape(b, s, heads, 2 * cfg.head_dim), 2, axis=-1)
            )
        if cfg.qk_norm and not cfg.qk_norm_per_head:
            q = _norm(cfg, q, lp["q_norm"])
            k = _norm(cfg, k, lp["k_norm"])
        q = q.reshape(b, s, heads, cfg.head_dim)
        k = k.reshape(b, s, kv_heads, cfg.head_dim)
        if cfg.qk_norm_per_head:  # each head's lanes by themselves, the heads under one weight
            q = _norm(cfg, q, lp["q_norm"])
            k = _norm(cfg, k, lp["k_norm"])
        v = (h @ lp["wv"]).reshape(b, s, kv_heads, cfg.head_dim)
        rotation = _rotation(cfg, mixer)
        if rotation is not None:
            q = rotary_embed(q, positions, **rotation)
            k = rotary_embed(k, positions, **rotation)
        if cfg.attn_scale:  # every core scales by head_dim**-0.5: the query carries the rest (a power of two here: exact)
            q = q * jnp.asarray(cfg.attn_scale * cfg.head_dim**0.5, q.dtype)
        if cfg.sparse_topk:
            att, said = _sparse_core(cfg, mesh, sp_manual, lp, h, q, k, v, positions)
            return att.reshape(b, s, heads * cfg.head_dim) @ lp["wo"], said
        said = {}
        if name:
            rotated = (
                0 if rotation is None else 2 * len(rotation["inv_freq"]) if "inv_freq" in rotation else cfg.head_dim
            )
            window = cfg.window if mixer == "window" else 0
            said = dict(scope=name + "_core", window=window, kind=(heads, kv_heads, window, rotated))
        att = _causal_core(cfg, mesh, sp_manual, q, k, v, **said).reshape(b, s, heads * cfg.head_dim)
        if cfg.attn_output_gate:
            with jax.named_scope("out_gate"):
                att = att * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(att.dtype)
        return att @ lp["wo"]


def _sparse_core(cfg, mesh, sp_manual, lp, h, q, k, v, positions):
    """(softmax(QKᵀ)V over the keys a learned selector picks, what the selector
    said): ``q`` [B, S, H, D], ``k``, ``v`` [B, S, Hkv, D] rotated, ``h`` the
    layer's normed input. Four scopes inside ``attn/sparse``:

    * ``indexer`` — the selector's three projections from ``stop_gradient(h)``,
      its rotation, and the scores ``I`` (float32, 512 queries at a time on the
      kernel path: ``ops/pallas/indexer``);
    * ``select`` — a query's threshold, the k-th largest causal score, exact
      (``ops.attention.select_top``), and the table of selected pairs;
    * ``sparse_core`` — the core under the table (the flash kernel's walk, or
      plain attention under the dense table), forward and backward;
    * ``indexer_loss`` — the target ``p`` (the mean over the query heads of the
      core's probabilities on the selected pairs, detached) and the mean over
      positions of ``KL(p ‖ softmax_selected(I))``, whose gradient reaches the
      selector's three leaves and nothing else.

    Says ``indexer_kl`` (the term, differentiated), ``selected_mean`` (keys a
    query), ``selected_over`` (rows a tie gave more than k) and ``tiles_live``
    (of the causal key tiles of the kernel's walk, the share that hold a
    selected pair)."""
    b, s = q.shape[:2]
    hi, di, top = cfg.indexer_heads, cfg.indexer_head_dim, cfg.sparse_topk
    impl, why, blocks = _attention_path(cfg, s, b, mesh, sp_manual, sparse=True)
    _say_attention_path(impl, why, blocks, b, s, cfg, kind=(q.shape[2], k.shape[2], 0, cfg.head_dim), sparse=True)
    hs = jax.lax.stop_gradient(h)
    with jax.named_scope("indexer"):
        table = {"inv_freq": yarn_inv_freq(di, cfg.rope_theta)}  # lane i with lane i + di/2, the whole width
        q_i = rotary_embed((hs @ lp["idx_wq"]).reshape(b, s, hi, di), positions, **table)
        k_i = rotary_embed((hs @ lp["idx_wk"]).reshape(b, s, 1, di), positions, **table)[:, :, 0]
        w = jnp.einsum("bsd,dj->bsj", hs, lp["idx_ww"], preferred_element_type=jnp.float32) * (hi * di) ** -0.5
    if impl == "flash":
        att, said = _sparse_tiles(cfg, blocks, q, k, v, q_i, k_i, w)
        return att, {**said, "selector_input": h} if _SAY_SELECTION else said
    tile = min(512, s)  # the statistics' tile where no kernel walks one
    with jax.named_scope("indexer"):
        scores = indexer_scores(q_i, k_i, w)
    with jax.named_scope("select"):
        seen, _, over = select_top(scores, jnp.tril(jnp.ones((s, s), bool)), top, -1)
        live = jnp.any(seen.reshape(b, s // tile, tile, s // tile, tile), axis=(2, 4)) if s % tile == 0 and b else None
    with jax.named_scope("sparse_core"):
        att = attention(q, k, v, selected=seen)
    with jax.named_scope("indexer_loss"):
        p = selected_head_probs(jax.lax.stop_gradient(q), jax.lax.stop_gradient(k), seen)
        kl = jnp.mean(selection_kl(scores, p, seen))
    said = _selector_said(kl, jnp.sum(seen, axis=-1), over, live, b * (s // tile))
    return att, {**said, "selected": seen, "selector_input": h} if _SAY_SELECTION else said


# set by :func:`selections` while it traces: the layers then also say their table of selected pairs and what the selector read
_SAY_SELECTION = False


def _selector_said(kl, taken, over, live, q_tiles: int):
    """What :func:`_sparse_core` says, from the term, the keys each query took,
    the rows over k and which tiles are live (None: no whole tiles) of
    ``q_tiles`` q tiles over all sequences — tile i of a sequence has i + 1
    causal key tiles, so the square's causal tiles are ``q_tiles·(n + 1)/2``
    at n tiles a side."""
    share = jnp.float32(0.0)
    if live is not None and q_tiles:
        share = jnp.sum(live.astype(jnp.float32)) / (q_tiles * (live.shape[-1] + 1) / 2)
    return dict(
        indexer_kl=kl, selected_mean=jnp.mean(taken.astype(jnp.float32)),
        selected_over=jnp.sum(over.astype(jnp.float32)), tiles_live=share,
    )


def _sparse_tiles(cfg, blocks, q, k, v, q_i, k_i, w):
    """:func:`_sparse_core` on the kernel path, ``block_q`` queries at a time:
    one scan gives each q block's scores (``indexer_scores_t``), threshold and
    int8 tiles of the table; the flash kernel runs under the table and hands on
    its row statistics; a second scan (:func:`_indexer_kl`) gives the term."""
    from torchft_tpu.ops.pallas.flash_attention import flash_attention, tiles_live
    from torchft_tpu.ops.pallas.indexer import indexer_scores_t

    b, s = q.shape[:2]
    bq, bk = (min(x, s) for x in blocks)
    top = cfg.sparse_topk
    with jax.named_scope("indexer"):
        q_t, w_t = q_i.transpose(0, 2, 1, 3), w.transpose(0, 2, 1)  # head-major: a head's rows are a kernel's block

    detached = jax.lax.stop_gradient((q_t, k_i, w_t))  # nothing of the selection is differentiated

    def one(i):
        with jax.named_scope("indexer"):
            scores = indexer_scores_t(*detached, i, bq, bk)  # [B, S, bq]
        with jax.named_scope("select"):
            causal = jnp.arange(s)[:, None] <= i * bq + jnp.arange(bq)[None, :]
            seen, _, over = select_top(scores, causal, top, 1)
            return seen.astype(jnp.int8), jnp.sum(seen, axis=1, dtype=jnp.int32), over

    selected, taken, over = jax.lax.map(one, jnp.arange(s // bq))  # [nq, B, S, bq] int8, [nq, B, bq] twice
    with jax.named_scope("select"):
        live = tiles_live(selected, bk)
    with jax.named_scope("sparse_core"):
        att, lse = flash_attention(q, k, v, block_q=bq, block_k=bk, selected=selected, live=live)
    with jax.named_scope("indexer_loss"):
        kl = _indexer_kl(q_t, k_i, w_t, q, k, lse, selected, live, bk)
    said = _selector_said(kl, taken, over, live, b * (s // bq))
    if _SAY_SELECTION:  # the tiles back as a dense table [B, Sq, Sk]
        said["selected"] = selected.transpose(1, 0, 3, 2).reshape(b, s, s) != 0
    return att, said


@functools.partial(jax.custom_vjp, nondiff_argnums=(8,))
def _indexer_kl(q_t, k_i, w_t, q, k, lse, selected, live, bk):
    """The mean over sequences and positions of ``KL(p ‖ softmax_selected(I))``,
    a q block at a time: ``I`` again from the selector's rotated projections
    (``q_t`` [B, Hi, S, Di], ``k_i`` [B, S, Di], ``w_t`` [B, Hi, S]), ``p`` from
    the core's q, k and row statistics (``head_probs``), on the pairs of
    ``selected``. Differentiable in the selector's three alone: the forward
    pass emits their closed-form cotangents (``∂/∂I = (softmax(I) - p)/(B·S)``
    on the selected pairs, through ``indexer_scores_bwd_t``), so no block's
    scores or probabilities are kept for a backward pass."""
    return _indexer_kl_blocks(q_t, k_i, w_t, q, k, lse, selected, live, bk, False)[0]


def _indexer_kl_blocks(q_t, k_i, w_t, q, k, lse, selected, live, bk, with_grads: bool):
    from torchft_tpu.ops.pallas.flash_attention import head_probs, scaled_head_major
    from torchft_tpu.ops.pallas.indexer import indexer_scores_bwd_t, indexer_scores_t

    nq, b, s, bq = selected.shape
    q_m, k_m = scaled_head_major(q), k.transpose(0, 2, 1, 3)

    def one(carry, i):
        total, dk = carry
        seen = selected[i] != 0
        scores = indexer_scores_t(q_t, k_i, w_t, i, bq, bk)
        p = head_probs(q_m, k_m, lse, selected, live, i, bk)
        soft, log_soft = selected_softmax(scores, seen, 1)
        total = total + jnp.sum(jnp.where(seen & (p > 0), p * (jnp.log(jnp.maximum(p, 1e-38)) - log_soft), 0.0))
        if not with_grads:
            return (total, dk), None
        g = (soft - p) / (b * s)  # both are zero off the selection
        dq_blk, dk_blk, dw_blk = indexer_scores_bwd_t(q_t, k_i, w_t, g, i, bk)
        return (total, dk + dk_blk), (dq_blk, dw_blk)

    (total, dk), blocks = jax.lax.scan(one, (jnp.float32(0.0), jnp.zeros(k_i.shape, jnp.float32)), jnp.arange(nq))
    if not with_grads:
        return total / (b * s), None
    dq, dw = blocks  # [nq, B, Hi, bq, Di], [nq, B, Hi, bq]: block-major back to [B, Hi, S, ...]
    dq = dq.transpose(1, 2, 0, 3, 4).reshape(q_t.shape)
    dw = dw.transpose(1, 2, 0, 3).reshape(w_t.shape)
    return total / (b * s), (dq.astype(q_t.dtype), dk.astype(k_i.dtype), dw.astype(w_t.dtype))


def _indexer_kl_fwd(q_t, k_i, w_t, q, k, lse, selected, live, bk):
    return _indexer_kl_blocks(q_t, k_i, w_t, q, k, lse, selected, live, bk, True)


def _indexer_kl_bwd(bk, grads, g):
    # the target is detached and the table is no number: the core's q, k and row statistics and the table get nothing
    return (*((g * d.astype(jnp.float32)).astype(d.dtype) for d in grads), None, None, None, None, None)


_indexer_kl.defvjp(_indexer_kl_fwd, _indexer_kl_bwd)


def _mix_mla(cfg, mesh, sp_manual, lp, h):
    """Latent attention: per-head keys and values come from one normalised
    latent, a second key part is shared by all heads; keys are wider than
    values. The query is one projection, or low-rank (``q_lora_rank``: down,
    RMSNorm, up). Without positions the shared part is NOT rotated; under
    ``mla_rope_theta`` it and the query's last ``qk_rope_head_dim`` lanes are,
    the key once, before it is broadcast over heads."""
    b, s, _ = h.shape
    heads, rank = cfg.n_heads, cfg.kv_lora_rank
    nope, rope, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    sp_size = mesh.shape.get("sp", 1) if mesh is not None else 1
    if cfg.mla_rope_theta and sp_size > 1:
        raise ValueError(
            f"sp={sp_size} with a rotated shared key: a sequence shard rotates by its own positions "
            "(axis_index('sp') * s, as _mix_full has them) and the ring (ops/attention.ring_attention_local) "
            "accumulates in the keys' width; the shard's position offset for the latent layer's two rotations "
            "and a value width in the ring are missing"
        )
    with jax.named_scope("mla"):
        if cfg.q_lora_rank:
            with jax.named_scope("q_lora"):
                q = rms_norm(h @ lp["wq_a"], lp["q_a_norm"], cfg.norm_eps) @ lp["wq_b"]
        else:
            q = h @ lp["wq"]
        q = q.reshape(b, s, heads, nope + rope)
        with jax.named_scope("kv_lora"):
            latent = h @ lp["w_kva"]
            c = rms_norm(latent[..., :rank], lp["kv_norm"], cfg.norm_eps)
            kv = (c @ lp["w_kvb"]).reshape(b, s, heads, nope + dv)
        shared = latent[:, :, None, rank:]
        if cfg.mla_rope_theta:
            with jax.named_scope("rope"):
                positions = jnp.arange(s)
                rotation = (
                    {"inv_freq": yarn_inv_freq(rope, cfg.mla_rope_theta)} if cfg.rope_pairing == "half"
                    else {"theta": cfg.mla_rope_theta}
                )
                q = jnp.concatenate([q[..., :nope], rotary_embed(q[..., nope:], positions, **rotation)], axis=-1)
                shared = rotary_embed(shared, positions, **rotation)
        shared = jnp.broadcast_to(shared, (b, s, heads, rope))
        k = jnp.concatenate([kv[..., :nope], shared], axis=-1)
        att = _causal_core(
            cfg, mesh, sp_manual, q, k, kv[..., nope:], widths=(nope + rope, dv), scope="mla_core",
            latent=(cfg.q_lora_rank, rope if cfg.mla_rope_theta else 0),
        )
        return att.reshape(b, s, heads * dv) @ lp["wo"]


def _unit_l2(x: jnp.ndarray) -> jnp.ndarray:
    """x / sqrt(Σx² + 1e-6) over the last axis, in float32: a linear mixer's q and k, head by head."""
    xf = x.astype(jnp.float32)
    return xf * jax.lax.rsqrt(jnp.sum(xf * xf, axis=-1, keepdims=True) + 1e-6)


# Positions a KDA mixer takes at a time. Everything in it but the recurrent
# state and the convolution's K-1 taps of history is local to a position, so
# the mixer runs as a ``lax.scan`` over blocks of the sequence that carries
# those two, each block under its own ``jax.checkpoint``: the float32
# [batch, block, heads x head_dim] temporaries of the gates and of the chunked
# core (two dozen of them live in a block's backward) are then a block's and
# not the sequence's — at b2 x s8192 x 32 x 128 the difference between 15.7 GB
# of temporaries in the backward, which no chip holds, and 4.5 (PERF.md §6, PR 35).
# The checkpoint's price is that a block's forward runs twice a step. What is
# outside it, taken once for the whole sequence ahead of the scan and read by the
# blocks as ``xs``: the full-rank products of the layer's input (q, k, v; Gated
# DeltaNet's z) — a product of all the rows is the blocks' products, so the
# backward does not run them a second time, for their outputs kept (bfloat16
# 402 MB a layer at Kimi-Linear's widths; 268 MB and z's float32 268 at
# Qwen3-Next's: PERF.md §6, PR 57). The convolution, the activations, the
# low-rank gates, the norms and the core stay a block's.
_KDA_BLOCK = 1024


def _mix_kda(cfg, lp, h):
    """Gated delta-rule linear attention (``ops/kda.py``): q, k, v through a
    causal short convolution and SiLU, q and k L2-normalised per head, a
    per-channel log-decay and a per-head write strength from the layer's
    input, the output normalised per head and gated. No positions. The three
    input projections are taken once, for the whole sequence, AHEAD of the
    block scan and outside its checkpoint (the note above ``_KDA_BLOCK``);
    the scan reads them block by block beside the layer's input."""
    b, s, d = h.shape
    heads, hd, taps = cfg.linear_n_heads, cfg.linear_head_dim, cfg.conv_kernel
    ch = heads * hd
    f32 = jnp.float32
    blk = _KDA_BLOCK if s % _KDA_BLOCK == 0 else s

    def block(carry, xs):
        state, before = carry  # [B, H, D, D] float32; three of [B, K-1, ch]: q, k, v ahead of the convolution
        hb, qkv = xs  # [B, blk, d]; three of [B, blk, ch]: h·wq, h·wk, h·wv of these positions
        with jax.named_scope("conv"):
            q, k, v = (
                jax.nn.silu(short_conv(x, lp[w], x0)).reshape(b, blk, heads, hd)
                for x, w, x0 in zip(qkv, ("conv_q", "conv_k", "conv_v"), before)
            )
        with jax.named_scope("gates"):
            raw = jnp.dot(hb @ lp["w_fa"], lp["w_fb"], preferred_element_type=f32)
            g = -jnp.exp(lp["a_log"].astype(f32))[:, None] * jax.nn.softplus(
                raw + lp["dt_bias"].astype(f32)
            ).reshape(b, blk, heads, hd)
            beta = jax.nn.sigmoid(jnp.dot(hb, lp["w_beta"], preferred_element_type=f32))
            out_gate = jax.nn.sigmoid(
                jnp.dot(hb @ lp["w_ga"], lp["w_gb"], preferred_element_type=f32)
            ).astype(hb.dtype).reshape(b, blk, heads, hd)
        q = (_unit_l2(q) * hd**-0.5).astype(v.dtype)
        k = _unit_l2(k).astype(v.dtype)
        with jax.named_scope("kda_core"):
            o, state = kda_chunked(q, k, v, g, beta, initial_state=state)
        o = rms_norm(o, lp["o_norm"], cfg.norm_eps) * out_gate
        return (state, tuple(x[:, blk - (taps - 1) :] for x in qkv)), o.reshape(b, blk, ch) @ lp["wo"]

    with jax.named_scope("kda"):
        start = (jnp.zeros((b, heads, hd, hd), f32), (jnp.zeros((b, taps - 1, ch), h.dtype),) * 3)
        blocks = jnp.moveaxis(h.reshape(b, s // blk, blk, d), 1, 0)
        # on the block-major view: the scan's ``xs`` ARE the arrays the backward keeps, with no second copy of them
        qkv = tuple(blocks @ lp[w] for w in ("wq", "wk", "wv"))
        _, out = jax.lax.scan(jax.checkpoint(block), start, (blocks, qkv))
        return jnp.moveaxis(out, 0, 1).reshape(b, s, d)


def _mix_gdn(cfg, lp, h):
    """Gated DeltaNet: q, k, v through a causal short convolution and SiLU, q
    and k L2-normalised per head, ONE log-decay and one write strength a value
    head a position from ``[b | a] = h·w_ba``, ``linear_n_heads`` value heads
    over ``linear_n_key_heads`` key heads (``ops/kda.gdn_chunked``), the output
    normalised per head under a plain weight and scaled by ``SiLU(h·w_z)``. No
    positions. Blocks of the sequence under a ``lax.scan`` that carries the
    state and the convolution's taps, as :func:`_mix_kda`, and as there the
    full-rank products of the layer's input — q, k, v, and ``h·w_z`` in
    float32 — are taken once ahead of the scan and outside the blocks'
    checkpoint. Returns (y, by name: ``decay_min`` the least log-decay of a
    position, ``beta_mean`` the mean write strength, over the call)."""
    b, s, d = h.shape
    heads, key_heads, hd, taps = cfg.linear_n_heads, cfg.linear_key_heads, cfg.linear_head_dim, cfg.conv_kernel
    kch, vch = key_heads * hd, heads * hd
    f32 = jnp.float32
    blk = _KDA_BLOCK if s % _KDA_BLOCK == 0 else s

    def block(carry, xs):
        state, before = carry  # [B, H, D, D] float32; [B, K-1, kch] twice and [B, K-1, vch]: q, k, v ahead of the convolution
        hb, qkv, z = xs  # [B, blk, d]; h·wq, h·wk [B, blk, kch] and h·wv [B, blk, vch]; h·w_z [B, blk, vch] float32
        with jax.named_scope("conv"):
            q, k, v = (
                jax.nn.silu(short_conv(x, lp[w], x0)).reshape(b, blk, -1, hd)
                for x, w, x0 in zip(qkv, ("conv_q", "conv_k", "conv_v"), before)
            )
        with jax.named_scope("gates"):
            ba = jnp.dot(hb, lp["w_ba"], preferred_element_type=f32)
            beta = jax.nn.sigmoid(ba[..., :heads])
            g = -jnp.exp(lp["a_log"].astype(f32)) * jax.nn.softplus(ba[..., heads:] + lp["dt_bias"].astype(f32))
            out_gate = jax.nn.silu(z).astype(hb.dtype).reshape(b, blk, heads, hd)
        q = (_unit_l2(q) * hd**-0.5).astype(v.dtype)
        k = _unit_l2(k).astype(v.dtype)
        _say_gdn_core_path(gdn_core(q, k, v), b, blk, cfg)
        with jax.named_scope("gdn_core"):
            o, state = gdn_chunked(q, k, v, g, beta, initial_state=state)
        o = rms_norm(o, lp["o_norm"], cfg.norm_eps) * out_gate
        before = tuple(x[:, blk - (taps - 1) :] for x in qkv)
        return (state, before), (o.reshape(b, blk, vch) @ lp["wo"], jnp.min(g), jnp.mean(beta))

    with jax.named_scope("gdn"):
        start = (
            jnp.zeros((b, heads, hd, hd), f32),
            tuple(jnp.zeros((b, taps - 1, c), h.dtype) for c in (kch, kch, vch)),
        )
        blocks = jnp.moveaxis(h.reshape(b, s // blk, blk, d), 1, 0)
        # on the block-major view, as in _mix_kda: what the scan takes is what the backward keeps
        qkv = tuple(blocks @ lp[w] for w in ("wq", "wk", "wv"))
        z = jnp.dot(blocks, lp["w_z"], preferred_element_type=f32)
        _, (out, decay_min, beta_mean) = jax.lax.scan(jax.checkpoint(block), start, (blocks, qkv, z))
        stats = {"decay_min": jnp.min(decay_min), "beta_mean": jnp.mean(beta_mean)}
        return jnp.moveaxis(out, 0, 1).reshape(b, s, d), stats


# Positions of a chunk of a state-space mixer's core (``ops/kda.ssd_chunked``); the result does not depend on it. A
# chunk's pairs cost chunk x head_dim operations a position a head, its two products with the state 2 x state x
# head_dim whatever the chunk. 128 is the one chunk the Pallas kernel pair is written for (``ops/pallas/kda.SSD_CHUNK``:
# a head's pairs are one whole [128, 128] MXU tile; ``ops/kda.ssd_core`` says which calls are the kernels'), and in the
# ``jax.numpy`` form, whose time is the pairs' elementwise passes over [heads, chunk, chunk] float32, it was the fastest
# of three: 0.570 s a device step at Granite's widths on a v5e, 64 0.606, 256 0.710 (PERF.md §6, PR 69).
# ``benchmark/opcounts/granite_hybrid.py`` counts at the same number
_SSD_CHUNK = 128


def _mix_ssd(cfg, lp, h):
    """A Mamba-2 state-space mixer (``TransformerConfig.ssd_layers`` has the
    equations). Blocks of the sequence under a ``lax.scan`` that carries the
    state and the convolution's taps, each under its own ``jax.checkpoint``, as
    :func:`_mix_gdn` — but EVERYTHING of the mixer is a block's, the wide
    product of the layer's input ``[z | x B C]`` too: kept outside the
    checkpoint, as ``_mix_gdn`` keeps its q, k, v, it is 138 MB a layer at
    8 192 positions, 1.25 GB over nine mixers that a 772 M-parameter state does
    not leave (PERF.md §6, PR 69), and computed again in the backward it is
    what every checkpointed layer does with its projections. The 64 columns of
    ``dt`` are a float32 product of their own (a decay's input rounded to
    bfloat16 is another decay). Scopes inside ``ssd``: ``ssd_in``, ``conv``,
    ``gates``, ``ssd_core``, ``gated_norm`` (the skip and the gate with it),
    ``ssd_out``. Returns (y, by name: ``ssd_decay_min`` the least log-decay of a
    position, ``ssd_dt_mean`` the mean step, ``ssd_state_rms`` of the final state)."""
    b, s, d = h.shape
    heads, hd, n_state, taps = cfg.ssd_n_heads, cfg.ssd_head_dim, cfg.ssd_state_dim, cfg.conv_kernel
    inner = heads * hd
    mixed = inner + 2 * n_state
    f32 = jnp.float32
    blk = _KDA_BLOCK if s % _KDA_BLOCK == 0 else s

    def block(carry, hb):
        state, before = carry  # [B, H, N, P] float32; [B, K-1, mixed]: x | B | C ahead of the convolution
        with jax.named_scope("ssd_in"):
            zx = hb @ lp["ssd_in"][:, : inner + mixed]  # [B, blk, inner + mixed]: z | x B C of these positions
        z, mix = zx[..., :inner], zx[..., inner:]
        with jax.named_scope("conv"):
            xbc = jax.nn.silu(short_conv(mix, lp["conv_w"], before) + lp["conv_b"])
            x = xbc[..., :inner].reshape(b, blk, heads, hd)
            b_in, c_out = xbc[..., inner : inner + n_state], xbc[..., inner + n_state :]
        with jax.named_scope("gates"):
            dt = jnp.dot(hb, lp["ssd_in"][:, inner + mixed :], preferred_element_type=f32)
            delta = jax.nn.softplus(dt + lp["dt_bias"].astype(f32))  # [B, blk, H]
            g = -jnp.exp(lp["a_log"].astype(f32)) * delta
            v = (x * delta[..., None]).astype(x.dtype)
        chunk = min(_SSD_CHUNK, blk)
        _say_ssd_core_path(ssd_core(c_out, b_in, v, chunk), b, blk, cfg)
        with jax.named_scope("ssd_core"):
            y, state = ssd_chunked(c_out, b_in, v, g, chunk=chunk, initial_state=state)
        with jax.named_scope("gated_norm"):
            y = y.astype(f32) + lp["d_skip"].astype(f32)[:, None] * x
            # the gate goes in BEFORE the norm, and the norm's input stays float32: rounded once, after the weight
            y = rms_norm(y.reshape(b, blk, inner) * jax.nn.silu(z.astype(f32)), lp["y_norm"], cfg.norm_eps).astype(hb.dtype)
        with jax.named_scope("ssd_out"):
            out = y @ lp["ssd_out"]
        return (state, mix[:, blk - (taps - 1) :]), (out, jnp.min(g, initial=0.0), jnp.mean(delta))  # a log-decay is <= 0

    with jax.named_scope("ssd"):
        start = (jnp.zeros((b, heads, n_state, hd), f32), jnp.zeros((b, taps - 1, mixed), h.dtype))
        blocks = jnp.moveaxis(h.reshape(b, s // blk, blk, d), 1, 0)
        (state, _), (out, decay_min, dt_mean) = jax.lax.scan(jax.checkpoint(block), start, blocks)
        stats = {
            "ssd_decay_min": jnp.min(decay_min), "ssd_dt_mean": jnp.mean(dt_mean),
            "ssd_state_rms": jnp.sqrt(jnp.mean(jax.lax.stop_gradient(state) ** 2)),
        }
        return jnp.moveaxis(out, 0, 1).reshape(b, s, d), stats


def _mix_conv(lp, h):
    """A gated short convolution: ``[B, C, X] = h·conv_in`` (in this order
    along the features), ``y = C ⊙ conv(B ⊙ X)`` with ``conv`` the causal
    depthwise convolution of ``conv_kernel`` taps (zeros ahead of position 0),
    ``y·conv_out``; the taps are as many as ``conv_w`` has rows. No query, key,
    score or state, no positions and no activation function. The elementwise
    part is the scope ``gated_conv_core``: plain ``jax.numpy`` between the two
    products, for XLA to fuse."""
    d = h.shape[-1]
    with jax.named_scope("gated_conv"):
        bcx = h @ lp["conv_in"]
        with jax.named_scope("gated_conv_core"):
            gate_in, gate_out, x = bcx[..., :d], bcx[..., d : 2 * d], bcx[..., 2 * d :]
            y = gate_out * short_conv(gate_in * x, lp["conv_w"])
        return y @ lp["conv_out"]


@contextlib.contextmanager
def _scopes(*names: Optional[str]):
    """``jax.named_scope``s nested in order; a None is skipped."""
    with contextlib.ExitStack() as stack:
        for name in names:
            if name:
                stack.enter_context(jax.named_scope(name))
        yield


def _make_layer_fn(
    cfg: TransformerConfig, mesh, sp_manual: bool = False, kind: Optional[Tuple[str, str]] = None,
    remat_parts: bool = False, nested: Optional[str] = None, from_input: bool = False,
):
    """The function of one layer of ``kind`` (mixer, feed-forward); absent:
    the one kind a model of one kind has. ``remat_parts``: ``jax.checkpoint``
    (``cfg.remat``) around the mixer and around the feed-forward, each by
    itself, where the caller puts none around the layer — but a ``kda``, ``gdn`` or ``ssd`` mixer,
    which checkpoints itself block by block (:data:`_KDA_BLOCK`): a second one
    around it would run its forward a third time. ``nested``: a name every op
    of the layer carries INSIDE its top-level scope (``attn/<nested>/...``: the
    multi-token-prediction module's), so the six scopes stay the whole.
    ``from_input``: the layer is handed its leaves as they are stored and each
    part is a function of the layer's own input — the cast to the compute
    dtype and the part's norm happen INSIDE its checkpoint. What a checkpoint
    keeps for the backward is then the layer's input and the stored leaves
    themselves, not a float32 copy of the norm's input, its output and a
    second set of weights: under a ``lax.scan`` those are kept once a repeat
    (0.55 GB a repeat at b2 x s8192 x 2048, PERF.md §6, PR 46)."""
    mixer, ff = kind or cfg.layer_kinds()[0]
    part = (lambda fn: _remat(cfg, fn)) if remat_parts else (lambda fn: fn)
    sp_size = mesh.shape.get("sp", 1) if mesh is not None else 1
    ep_size = mesh.shape.get("ep", 1) if mesh is not None else 1
    experts_over_chips = ff == "experts" and ep_size > 1
    if experts_over_chips and (cfg.top_k != 2 or cfg.router_aux_loss_coef):
        raise ValueError(
            f"ep={mesh.shape['ep']}: experts over chips still run the top-2 "
            "capacity dispatch (_ffn_moe_ep), which has no other top_k and no "
            f"balance term; got top_k={cfg.top_k}, "
            f"router_aux_loss_coef={cfg.router_aux_loss_coef}"
        )
    if experts_over_chips and (cfg.n_experts_held or cfg.n_shared_experts or cfg.router_gate != "softmax"):
        raise ValueError(
            f"ep={ep_size} with a held share, a shared expert or a sigmoid gate: experts over chips "
            "still run the top-2 capacity dispatch (_ffn_moe_ep); the all-to-all that would carry "
            "a token's rows to the chips holding its experts, and their outputs back, is missing, "
            "and a share held on one chip does not stand in for it"
        )
    gate_ahead = ff == "experts" and cfg.router_input == "layer"
    if experts_over_chips and (gate_ahead or cfg.expert_activation != "silu"):
        raise ValueError(
            f"ep={ep_size} with router_input={cfg.router_input!r}, expert_activation={cfg.expert_activation!r}: experts over "
            "chips still run the top-2 capacity dispatch (_ffn_moe_ep), which routes on its own input inside the "
            "feed-forward and gates with SiLU; a dispatch over ep that takes a gate it is given (the chosen experts "
            "and weights as inputs of the all-to-all) and an activation argument are missing"
        )
    pp_size = max(cfg.pp, mesh.shape.get("pp", 1) if mesh is not None else 1)
    if pp_size > 1 and ff == "experts" and (gate_ahead or cfg.expert_activation != "silu"):
        raise ValueError(
            f"pp={pp_size} with router_input={cfg.router_input!r}, expert_activation={cfg.expert_activation!r}: the "
            "dropless experts' grouped matmul (ops/layers._grouped_matmul) is a Pallas call, which the pipeline's "
            "manual region cannot trace; a grouped matmul typed for that region (its outputs' varying mesh axes) is missing"
        )
    if mixer in ("kda", "gdn", "ssd") and sp_size > 1:
        raise ValueError(
            f"sp={sp_size} with a {mixer} layer: the recurrent state at a sequence shard's start is the "
            "state at the end of the shard before it; the hand-over of that state (and of the "
            "short convolution's last taps) from one sp shard to the next is missing"
        )
    if mixer == "conv" and sp_size > 1:
        raise ValueError(
            f"sp={sp_size} with a conv layer: a sequence shard's first positions convolve over the last "
            f"{cfg.conv_kernel - 1} positions of the shard before it; the hand-over of those gated inputs "
            "from one sp shard to the next is missing"
        )

    def of_input(fn, norm: str):
        """``fn(lp, h)`` itself, or under ``from_input`` as a function of the
        stored leaves and the layer's input (and of what else it takes by name)."""
        if not from_input:
            return fn

        def whole(lp, x, **more):
            lp = _compute_dtype(lp, cfg.dtype)
            return fn(lp, _norm(cfg, x, lp[norm]), **more)

        return whole

    def normed(fn, norm: str):
        """``fn`` itself, or under ``sandwich_norm`` with its output (the first
        of them, where it says more) through the norm ``norm`` of its own,
        inside whatever checkpoint holds ``fn``."""
        if not cfg.sandwich_norm:
            return fn

        def whole(lp, h, **more):
            out = fn(lp, h, **more)
            y, rest = (out[0], out[1:]) if isinstance(out, tuple) else (out, ())
            with jax.named_scope("post_norm"):
                y = _norm(cfg, y, lp[norm])
            return (y, *rest) if rest else y

        return whole

    def mix(fn):
        return of_input(normed(fn, "post_ln1"), "ln1")

    def feed(fn):
        return of_input(normed(fn, "post_ln2"), "ln2")

    def join(x, y):
        """The residual stream plus a branch's output, under ``residual_scale`` the branch times it: the product and
        the sum in float32, rounded once."""
        if cfg.residual_scale == 1.0:
            return x + y
        return (x.astype(jnp.float32) + cfg.residual_scale * y.astype(jnp.float32)).astype(x.dtype)

    def layer_fn(x: jnp.ndarray, lp: Dict[str, Any]):
        """(x, aux): aux by name — what :func:`_ffn_moe` says of a dropless
        expert layer (:func:`_moe_said`), what :func:`_mix_gdn` says of its
        or :func:`_mix_ssd` of its mixer — {} otherwise."""
        aux, gate = {}, {}
        x = _constrain(x, _act_spec(sp_manual))
        if ff == "experts" and (gate_ahead or cfg.expert_activation != "silu"):
            _say_expert_path(cfg, *x.shape[:2])
        with _scopes("attn", nested):
            h = x if from_input else _norm(cfg, x, lp["ln1"])
        if gate_ahead:
            # the gate is a function of the layer's input, as the mixer is: a part of its own ahead of the mixer, its
            # [T, k] weights and experts kept for the feed-forward part (inputs of its checkpoint, not recomputed in it)
            with _scopes("moe", nested):
                chosen, load = part(of_input(functools.partial(_gate_ahead, cfg=cfg), "ln1"))(lp, h)
                gate, aux = {"gate": chosen}, dict(zip(("balance", "counts"), load))
        with _scopes("attn", nested):
            if mixer in _SOFTMAX_MIXERS:
                y = part(mix(functools.partial(_mix_full, cfg, mesh, sp_manual, mixer=mixer)))(lp, h)
                if cfg.sparse_topk:  # the mixer says what its selector said beside its output
                    y, said = y
                    aux = {**aux, **said}
                x = join(x, y)
            elif mixer == "kda":
                x = join(x, mix(functools.partial(_mix_kda, cfg))(lp, h))
            elif mixer in ("gdn", "ssd"):
                y, said = mix(functools.partial(_mix_gdn if mixer == "gdn" else _mix_ssd, cfg))(lp, h)
                x, aux = join(x, y), {**aux, **said}
            elif mixer == "conv":
                x = join(x, part(mix(_mix_conv))(lp, h))
            else:
                x = join(x, part(mix(functools.partial(_mix_mla, cfg, mesh, sp_manual)))(lp, h))

        with _scopes("moe" if ff == "experts" else "ffn", nested):
            h = x if from_input else _norm(cfg, x, lp["ln2"])
            if experts_over_chips:
                x = join(x, feed(functools.partial(_ffn_moe_ep, cfg=cfg))(lp, h))
            elif ff == "experts":
                y, said = part(feed(functools.partial(_ffn_moe, cfg=cfg)))(lp, h, **gate)
                x, aux = join(x, y), {**aux, **_moe_said(cfg, said, gate_ahead)}
            else:
                x = join(x, part(feed(_ffn_dense))(lp, h))
        return _constrain(x, _act_spec(sp_manual)), aux

    return layer_fn


@functools.lru_cache(maxsize=None)
def _remat_policy(name: str):
    """What ``jax.checkpoint`` may keep under ``remat_policy`` ``name``. What only the flash kernel's forward can give
    its backward, output and row statistics, is kept under either policy: the kernel's forward then runs once a layer
    a step (ops/pallas/flash_attention.py, "Remat"). The names do not occur on a path without the kernel, whose
    program is what policy None traced. ONE object a name, as None was: JAX keys what it caches of a checkpoint on its
    policy, and a fresh closure a layer has every unrolled layer of a pattern traced and lowered anew."""
    from torchft_tpu.ops.pallas.flash_attention import CORE_LSE, CORE_OUT

    core = jax.checkpoint_policies.save_only_these_names(CORE_OUT, CORE_LSE)
    if name == "all":
        return core
    if name == "dots":
        return jax.checkpoint_policies.save_from_both_policies(
            jax.checkpoint_policies.dots_with_no_batch_dims_saveable, core
        )
    raise ValueError(
        f"remat_policy={name!r}: expected 'all' or "
        "'dots' (a typo here would silently pay full recompute)"
    )


def _remat(cfg: TransformerConfig, layer_fn):
    if not cfg.remat:
        return layer_fn
    return jax.checkpoint(layer_fn, policy=_remat_policy(cfg.remat_policy))


def _make_stage_fn(cfg: TransformerConfig, mesh, sp_manual: bool = False):
    layer_fn = _remat(cfg, _make_layer_fn(cfg, mesh, sp_manual))

    def stage_fn(stage_params: Dict[str, Any], x: jnp.ndarray):
        """(x, aux stacked over the stage's layers); see ``layer_fn``."""
        # stage_params leaves: [Lp, ...]; scan over the layer axis
        return jax.lax.scan(layer_fn, x, stage_params)

    return stage_fn


def _scans_layers(cfg: TransformerConfig) -> bool:
    """Whether the declared pattern's period is ONE layer: the scan over
    periods is then a scan over layers with more than one repeat, and the
    stack takes its leaves as they are stored (``_make_layer_fn``'s
    ``from_input``)."""
    return len(layer_pattern(cfg)[1]) == 1


def _make_pattern_fn(cfg: TransformerConfig, mesh):
    """The stack of a declared pattern: ``(lead, periods, x) -> (x, aux)``.
    The leading layers run one by one, then ``lax.scan`` over the repeats of
    the period with its layers unrolled in the body (a period of one layer:
    over the layers), each layer's mixer and
    feed-forward under a ``jax.checkpoint`` of their own (``remat_parts``).
    ``aux`` is the layers' (see ``layer_fn``) by name, each name stacked over the
    layers that say it, in layer order."""
    n_lead, period = layer_pattern(cfg)
    kinds = cfg.layer_kinds()
    scans_layers = _scans_layers(cfg)
    fns = {
        _kind_key(kind): _make_layer_fn(cfg, mesh, kind=kind, remat_parts=True, from_input=scans_layers)
        for kind in set(kinds)
    }

    def run(group, slots, x):
        auxes = []
        for key, i in slots:
            lp = jax.tree_util.tree_map(lambda a: a[i], group[key])
            x, aux = fns[key](x, lp)
            if aux:
                auxes.append(aux)
        return x, auxes

    def one(x, lp):
        x, aux = fns[_kind_key(period[0])](x, lp)
        return x, [aux] if aux else []

    def rows_of(lead_aux, period_aux, name):
        """[layers that say ``name``, ...]: the leading ones, then repeat by repeat."""
        rows = [a[name][None] for a in lead_aux if name in a]
        inside = [a[name] for a in period_aux if name in a]
        if inside:
            inner = jnp.stack(inside, axis=1)  # [repeats, in a period, ...]
            rows.append(inner.reshape((-1,) + inner.shape[2:]))
        return jnp.concatenate(rows, axis=0)

    def pattern_fn(lead, periods, x):
        x, lead_aux = run(lead, _slots(kinds[:n_lead]), x)
        if scans_layers:
            # a period of ONE layer: the scan is over the layers themselves. The leaves' unit axis goes out here, so
            # that what the layer's checkpoints keep for the backward are the scan's own slices (``from_input``): a
            # slice taken inside the body is a new array, kept once a repeat
            layers = jax.tree_util.tree_map(lambda a: a[:, 0], periods[_kind_key(period[0])])
            x, period_aux = jax.lax.scan(one, x, layers)
        else:
            x, period_aux = jax.lax.scan(lambda x, group: run(group, _slots(period), x), x, periods)
        names = dict.fromkeys(name for aux in lead_aux + period_aux for name in aux)
        return x, {name: rows_of(lead_aux, period_aux, name) for name in names}

    return pattern_fn


def _pipeline_stage_fn(cfg: TransformerConfig, mesh, sp_manual: bool):
    """The stage as ``pipeline_forward`` calls it: hidden state in, hidden
    state out. The experts' balance term does not cross stages
    (``loss_and_stats`` refuses a non-zero coefficient under ``pp`` > 1)."""
    stage_fn = _make_stage_fn(cfg, mesh, sp_manual=sp_manual)
    return lambda stage_params, x: stage_fn(stage_params, x)[0]


def _embed_lookup(
    params: Dict[str, Any], tokens: jnp.ndarray, dt, nested: Optional[str] = None, scale: float = 1.0
) -> jnp.ndarray:
    """Embedding gather with EXPLICIT gather partitioning (round-3 review
    missing #2): the table is stored P(None, ("tp","fsdp")) — vocab
    unsharded, D over (tp,fsdp) — so the token gather is fully LOCAL
    (SPMD cannot partition a vocab-sharded gather and previously fell
    back to "involuntary full rematerialization", replicating [V,D] on
    every device each step). Only the (much smaller) [B,S,D] activation
    is resharded to the standard spec afterwards. ``scale``
    (``embed_scale``) multiplies the float32 table ahead of its cast, so a row
    is rounded once."""
    with _scopes("embed", nested):
        table = params["embed"] if scale == 1.0 else params["embed"] * scale
        embed = _constrain(table.astype(dt), P(None, ("tp", "fsdp")))
        tok = _constrain(tokens, P("dp", "sp"))
        x = jnp.take(embed, tok, axis=0)
        # reshard to the activation spec ONE axis move per step — GSPMD
        # falls back to a full-remat copy on the combined move (fsdp D→B
        # while dropping tp) but handles each single-axis hop efficiently
        x = _constrain(x, P("dp", "sp", ("tp", "fsdp")))
        x = _constrain(x, P(("dp", "fsdp"), "sp", "tp"))
        return _constrain(x, _act_spec())


def _compute_dtype(layers: Dict[str, Any], dt) -> Dict[str, Any]:
    """The layers' leaves in the compute dtype, but :data:`_F32_LEAVES`."""
    return {
        name: _compute_dtype(leaf, dt) if isinstance(leaf, dict)
        else leaf if name in _F32_LEAVES else leaf.astype(dt)
        for name, leaf in sorted(layers.items())  # tree_map's order
    }


@jax.custom_vjp
def _gradients_apart(tree):
    """The identity, whose gradients pass an ``optimization_barrier``: an aid
    to MEASUREMENT, with its cost in the step measured (PERF.md §6, PR 35).
    The layers of a declared pattern are unrolled, so XLA is free to run a
    leaf's optimizer update as the tail of the matrix product that forms its
    gradient; the update's seconds then count to that product's scope
    (``attn`` / ``ffn``), and what is left under ``optimizer`` moves its bytes
    faster than the memory can (105 % of its roofline there). Behind a
    ``lax.scan`` over identical layers no such fusion exists. It goes when the
    benchmark's readers can give a fused update's seconds to the optimizer."""
    return tree


_gradients_apart.defvjp(lambda tree: (tree, None), lambda _, g: (jax.lax.optimization_barrier(g),))


def _refuse_pattern_under_pp(cfg: TransformerConfig) -> None:
    if max(cfg.pp, 1) > 1:
        raise ValueError(
            f"pp={cfg.pp} with a declared layer pattern: a pipeline stage is a scan over identical "
            "layers under one [pp, Lp] parameter layout; stages that each hold their own kinds of "
            "layer (a per-stage tree and stage function in parallel/pipeline.py) are missing"
        )


def _refuse_mtp_under_pp(cfg: TransformerConfig) -> None:
    if cfg.n_mtp_modules and max(cfg.pp, 1) > 1:
        raise ValueError(
            f"pp={cfg.pp} with a multi-token-prediction module: the module reads the last stage's hidden state "
            "beside the first stage's embedding table and runs a layer of its own behind the head; a pipeline "
            "exit that carries both into the manual region (a head_fn with a layer in parallel/pipeline.py) is missing"
        )


def _refuse_loop(cfg: TransformerConfig) -> None:
    """What a looped stack (``ut_steps`` > 1) cannot run with yet, each by name."""
    if cfg.ut_steps == 1:
        return
    if max(cfg.pp, 1) > 1:
        raise ValueError(
            f"ut_steps={cfg.ut_steps} with pp={cfg.pp}: every loop step crosses every pipeline stage, so the last "
            "stage's normed output goes back to the first ut_steps - 1 times a microbatch; a pipeline schedule with "
            "that return edge (and the exits' heads on the last stage, parallel/pipeline.py) is missing"
        )
    if not _of_one_kind(cfg):
        raise ValueError(
            f"ut_steps={cfg.ut_steps} with a declared layer pattern: the loop scans ONE stage function over the loop "
            "steps with the same leaves; a pattern's leading layers and periods under that scan (and what their "
            "checkpoints keep a loop step) are missing"
        )
    if cfg.n_mtp_modules:
        raise ValueError(
            f"ut_steps={cfg.ut_steps} with a multi-token-prediction module: which loop step's hidden state the module "
            "reads, and how its loss joins the expectation over the exits, is not defined"
        )


def _say_loop(cfg: TransformerConfig, batch: int, seq_len: int) -> None:
    """One ``loop_shape`` event and INFO line per traced shape of a looped
    stack: how often which layers run and what the loss reads of the exits."""
    fields = dict(
        ut_steps=cfg.ut_steps, layers=cfg.n_layers, applications=cfg.ut_steps * cfg.n_layers,
        sandwich_norm=cfg.sandwich_norm, exit_gate=cfg.exit_gate, exit_entropy_coef=cfg.exit_entropy_coef,
        batch=batch, seq=seq_len,
    )
    _say_once("loop_shape", tuple(fields.values()), **fields)


def _diffusion_noise(tokens: jnp.ndarray, cfg: TransformerConfig):
    """(t [B, S/D] float32, m [B, S] bool) of block-diffusion training: each
    block's noise level and which positions show the mask id — a pure function
    of a sequence's OWN ids and ``diffusion_seed``, so that ``loss_fn(params,
    tokens, cfg, mesh)`` takes no further argument and anyone can rebuild it:

    * ``c = Σ_ℓ x_ℓ·(2ℓ + 1) mod 2³¹`` (uint32 arithmetic, which wraps at 2³²);
    * ``key = fold_in(PRNGKey(diffusion_seed), c)``, ``(key_t, key_m) = split(key)``;
    * ``t_b = t_min + (1 - t_min)·U_b``, ``U = uniform(key_t, [S/D])`` float32,
      computed as ``(U_b + t_min / (1 - t_min))·(1 - t_min)``;
    * ``m_ℓ = V_ℓ < t_⌊ℓ/D⌋``, ``V = uniform(key_m, [S])`` float32.

    A sequence met again draws the same noise; a trainer that wants otherwise
    changes the seed by epoch. The sum comes FIRST and the one product after
    it: written as a product and then a sum, XLA:CPU contracts the two into one
    rounding under ``jit`` (and through an ``optimization_barrier``) and not
    eagerly, and 27 % of the levels differ by an ulp; as written (t, m) are
    the same bits on a CPU and on the chip, under ``jit`` or not."""
    s, d = tokens.shape[1], cfg.diffusion_block
    if s % d:
        raise ValueError(f"diffusion_block={d} does not divide the sequence's {s} positions")
    base = jax.random.PRNGKey(cfg.diffusion_seed)
    odd = 2 * jnp.arange(s, dtype=jnp.uint32) + 1

    def one(ids):
        c = jnp.sum(ids.astype(jnp.uint32) * odd, dtype=jnp.uint32) & jnp.uint32(0x7FFFFFFF)
        key_t, key_m = jax.random.split(jax.random.fold_in(base, c))
        least = cfg.diffusion_t_min
        t = (jax.random.uniform(key_t, (s // d,), jnp.float32) + jnp.float32(least / (1.0 - least))) * jnp.float32(1.0 - least)
        return t, jax.random.uniform(key_m, (s,), jnp.float32) < jnp.repeat(t, d)

    with _scopes("embed", "noise"):
        return jax.vmap(one)(tokens)


def _diffusion_rows(tokens: jnp.ndarray, cfg: TransformerConfig) -> jnp.ndarray:
    """The ids a step embeds: ``tokens`` [B, S], or under block diffusion the
    2·S rows ``[noised ; clean]`` a sequence, [B, 2·S]."""
    if not cfg.diffusion_block:
        return tokens
    _, m = _diffusion_noise(tokens, cfg)
    with _scopes("embed", "noise"):
        return jnp.concatenate([jnp.where(m, jnp.asarray(cfg.mask_id, tokens.dtype), tokens), tokens], axis=1)


def _final_norm(cfg: TransformerConfig, x: jnp.ndarray, weight: jnp.ndarray) -> jnp.ndarray:
    """The final norm of the rows the head reads: under block diffusion the
    noised half alone (the clean half has no loss and needs no logits)."""
    if cfg.diffusion_block:
        x = x[:, : x.shape[1] // 2]
    return _norm(cfg, x, weight.astype(cfg.dtype))


def _hidden_states(
    params: Dict[str, Any],
    tokens: jnp.ndarray,
    cfg: TransformerConfig,
    mesh=None,
):
    """tokens [B, S] -> (final-norm hidden states [B, S, D] in cfg.dtype, aux)
    (everything except the unembed — the chunked loss head consumes this
    without ever materializing [S, V] logits). ``aux`` is what the layers
    say beside the hidden state, by name (``layer_fn``), each name stacked
    over the layers that say it: ``balance`` [L] and ``counts`` [L, E] of
    dropless expert layers, ``decay_min`` of gdn mixers, …; else {}.
    A looped stack (``ut_steps`` = T > 1) gives every loop step's normed
    state, [T, B, S, D], and its aux stacked over T·L layer applications.
    Under block diffusion the layers run the 2·S rows ``[noised ; clean]``
    (their aux counts both halves) and the state returned is the NOISED half's."""
    from torchft_tpu.parallel.pipeline import pipeline_forward

    b, s = tokens.shape
    dt = cfg.dtype
    _refuse_loop(cfg)
    x = _embed_lookup(params, _diffusion_rows(tokens, cfg), dt, scale=cfg.embed_scale)

    pp = max(cfg.pp, 1)
    aux = {}
    if not _of_one_kind(cfg):
        _refuse_pattern_under_pp(cfg)
        _say_layer_pattern(cfg, b, s)
        lead, periods = _gradients_apart((params["lead"], params["periods"]))
        if not _scans_layers(cfg):
            lead, periods = _compute_dtype(lead, dt), _compute_dtype(periods, dt)
        x, aux = _make_pattern_fn(cfg, mesh)(lead, periods, x)
        return _final_norm(cfg, x, params["final_norm"]), aux

    layers = _compute_dtype(params["layers"], dt)
    if cfg.ut_steps > 1:
        _say_loop(cfg, b, s)
        stage_fn = _make_stage_fn(cfg, mesh, sp_manual=False)
        stage = jax.tree_util.tree_map(lambda a: a[0], layers)
        final_norm = params["final_norm"].astype(dt)

        def turn(x, _):
            # the same leaves every turn: the scan's transpose sums a leaf's ut_steps gradients
            x, aux = stage_fn(stage, x)
            # under its own checkpoint, as a layer is: the backward keeps the turn's last layer output, not the
            # norm's float32 copy of it, a turn
            h = _remat(cfg, lambda x: _norm(cfg, x, final_norm))(x)
            return h, (h, aux)

        _, (hs, aux) = jax.lax.scan(turn, x, None, length=cfg.ut_steps)
        return hs, jax.tree_util.tree_map(lambda a: a.reshape((-1,) + a.shape[2:]), aux)
    if pp == 1:
        stage_fn = _make_stage_fn(cfg, mesh, sp_manual=False)
        x, aux = stage_fn(jax.tree_util.tree_map(lambda a: a[0], layers), x)
    else:
        # inside the pipeline's manual region the sp axis is manual too
        # (Shardy forbids nested manual regions)
        sp_manual = mesh is not None and mesh.shape.get("sp", 1) > 1
        stage_fn = _pipeline_stage_fn(cfg, mesh, sp_manual)
        m = cfg.microbatches or pp
        assert b % m == 0, f"batch {b} must divide into {m} microbatches"
        x_mb = x.reshape(m, b // m, s, -1)
        x_mb = pipeline_forward(layers, x_mb, stage_fn, mesh)
        x = x_mb.reshape(b, s, -1)

    return _final_norm(cfg, x, params["final_norm"]), aux


def forward(
    params: Dict[str, Any],
    tokens: jnp.ndarray,
    cfg: TransformerConfig,
    mesh=None,
) -> jnp.ndarray:
    """tokens [B, S] int32 -> logits [B, S, V] (compute in cfg.dtype,
    logits in float32); of a looped stack, the last loop step's; under block
    diffusion the NOISED rows' (the step's own draw of the noise)."""
    x, _ = _hidden_states(params, tokens, cfg, mesh)
    if cfg.ut_steps > 1:
        x = x[-1]
    return _scaled_logits(cfg, (x @ _out_table(params, cfg).astype(cfg.dtype)).astype(jnp.float32))


def selections(params: Dict[str, Any], tokens: jnp.ndarray, cfg: TransformerConfig, mesh=None, with_inputs: bool = False):
    """[L, B, S, S] bool: the pairs each layer's learned selector picked in a
    forward pass over ``tokens`` (``sparse_topk`` > 0) — row t of a layer is the
    set of keys query t attended to — by the path the step itself takes (the
    kernel path's tiles, turned back into a dense table). ``with_inputs``: and
    what each layer's selector read, the layer's normed input [L, B, S, d] in
    the compute dtype. For the checks that compare the program's sets with a
    reference's (from the same input: what differs is the selector's own
    rounding) and hand them to it."""
    global _SAY_SELECTION
    _SAY_SELECTION = True
    try:
        said = _hidden_states(params, tokens, cfg, mesh)[1]
    finally:
        _SAY_SELECTION = False
    return (said["selected"], said["selector_input"]) if with_inputs else said["selected"]


def _out_table(params: Dict[str, Any], cfg: TransformerConfig) -> jnp.ndarray:
    """The head's table [d, V]: the ``out`` leaf, or under ``tie_embeddings``
    the embedding's transpose — the head's cotangent then arrives as one
    float32 [d, V] array, and autodiff adds its transpose to the lookup's."""
    return params["embed"].T if cfg.tie_embeddings else params["out"]


def _scaled_logits(cfg: TransformerConfig, logits: jnp.ndarray) -> jnp.ndarray:
    """Float32 logits divided by ``logits_scale``; at 1 the array itself."""
    return logits if cfg.logits_scale == 1.0 else logits / cfg.logits_scale


def loss_fn(
    params: Dict[str, Any],
    tokens: jnp.ndarray,
    cfg: TransformerConfig,
    mesh=None,
) -> jnp.ndarray:
    """The training loss: next-token cross entropy; position S-1 is
    unsupervised (targets are tokens shifted left; same [B, S] shape keeps
    sp sharding aligned). With dropless experts and a non-zero
    ``router_aux_loss_coef``, plus that times the load-balancing term. Of a
    looped stack with an exit gate: the expectation of the cross entropy over
    the exits less the exit distribution's entropy (:func:`_exit_loss`). Under
    block diffusion (``diffusion_block``): the mean over the S positions of
    ``m/t`` times the noised row's cross entropy against the clean id at the
    same position, no shift (:func:`_diffusion_loss`)."""
    return loss_and_stats(params, tokens, cfg, mesh)[0]


def loss_and_stats(
    params: Dict[str, Any],
    tokens: jnp.ndarray,
    cfg: TransformerConfig,
    mesh=None,
) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """(:func:`loss_fn`'s loss, router statistics of the call). The
    statistics are ``{}`` for a model without dropless experts, else
    ``tokens_per_expert`` [L, E] int32 and ``balance_loss`` (the mean over
    layers of E·Σ_e f_e·P_e, before the coefficient), under a share
    ``rows_held`` and ``window_live_share`` [L], under ReLU-gated experts ``gate_zero_share`` [L], under
    ``shared_expert_gate`` ``shared_gate_mean`` [L];
    of gdn mixers ``gdn_decay_min`` and ``gdn_beta_mean`` [their layers]; of
    state-space mixers ``ssd_decay_min`` (the least log-decay of a position)
    and ``ssd_dt_mean`` [their layers] and ``ssd_state_rms`` (of the last
    mixer's final state): which numerical regime a run is in;
    of a looped stack with an exit gate ``exit_probs`` [T] (each exit's
    probability, mean over the supervised tokens), ``exit_entropy`` and
    ``loss_by_step`` [T] (each exit's own cross entropy); under block
    diffusion ``masked_share``, ``noise_weight_mean`` and
    ``loss_masked_unweighted``, and the router's statistics then count 2·S
    rows a sequence — what ``TrainStep`` keeps of its last step."""
    if max(cfg.pp, 1) > 1 and cfg.n_experts and cfg.router_aux_loss_coef:
        raise ValueError(
            f"pp={cfg.pp} with router_aux_loss_coef={cfg.router_aux_loss_coef}: "
            "the load-balancing term is not carried across pipeline stages, "
            "and is refused rather than dropped; set the coefficient to 0 or pp=1"
        )
    if not _of_one_kind(cfg):
        _refuse_pattern_under_pp(cfg)
    _refuse_mtp_under_pp(cfg)
    _refuse_loop(cfg)
    if max(cfg.pp, 1) > 1 and mesh is not None:
        # pipelined training path: the head (final norm + unembed + NLL)
        # runs inside the pipeline's manual region on the last stage and
        # only SCALAR reductions cross the pp axis — the replicate-the-
        # activations psum the plain forward() pays is for logits
        # consumers, not the training loop
        return _pipelined_loss(params, tokens, cfg, mesh), {}
    return _loss_of_hidden(params, *_hidden_states(params, tokens, cfg, mesh), tokens, cfg, mesh)


def _loss_of_hidden(params: Dict[str, Any], x: jnp.ndarray, aux, tokens: jnp.ndarray, cfg: TransformerConfig, mesh=None):
    """:func:`loss_and_stats` of what :func:`_hidden_states` gave: the head
    (``params["out"]``; an exit gate's and a multi-token-prediction module's
    leaves where the model has them) and what the layers said (``aux``)."""
    stats = {}
    if cfg.diffusion_block:
        ce, stats = _diffusion_loss(params, x, tokens, cfg, mesh)
    elif cfg.exit_gate:
        ce, stats = _exit_loss(params, x, tokens, cfg, mesh)
    else:
        ce = _cross_entropy(params, x[-1] if cfg.ut_steps > 1 else x, tokens, cfg, mesh)
    if cfg.n_mtp_modules and cfg.mtp_loss_weight:
        second, mtp_aux = _mtp_hidden(params, x, tokens, cfg, mesh)
        mtp_ce = _cross_entropy(params, second, tokens, cfg, mesh, ahead=2)
        # the module's layer is the statistics' last row (it is of the last layer's kind: what it says, that layer said)
        aux = {**aux, **{k: jnp.concatenate([aux[k], m[None]]) for k, m in mtp_aux.items()}} if aux else mtp_aux
        stats = {"main_loss": ce, "mtp_loss": mtp_ce}
        ce = ce + cfg.mtp_loss_weight * mtp_ce
    for name, said in (
        ("decay_min", "gdn_decay_min"), ("beta_mean", "gdn_beta_mean"), ("shared_gate", "shared_gate_mean"),
        ("ssd_decay_min", "ssd_decay_min"), ("ssd_dt_mean", "ssd_dt_mean"),
    ):
        if name in aux:
            stats[said] = aux[name]
    if "ssd_state_rms" in aux:  # of the LAST state-space mixer's final state
        stats["ssd_state_rms"] = aux["ssd_state_rms"][-1]
    if "indexer_kl" in aux:  # a learned selector: its term a layer, summed beside the next-token loss at weight one
        stats.update(
            lm_loss=ce, indexer_loss=aux["indexer_kl"], selected_mean=jnp.mean(aux["selected_mean"]),
            selected_over_k=jnp.sum(aux["selected_over"]), sparse_tiles_live_share=aux["tiles_live"],
        )
        ce = ce + jnp.sum(aux["indexer_kl"])
    if "balance" not in aux:
        return ce, stats
    balance = jnp.mean(aux["balance"])
    stats.update(tokens_per_expert=aux["counts"], balance_loss=balance)
    if "held" in aux:  # under a share: the token-expert rows whose expert is held, a layer
        stats["rows_held"] = aux["held"]
        stats["window_live_share"] = aux["live_share"]  # of the window's slots; past 1, the layer took every window
    if "gate_zeros" in aux:  # of the computed rows' gate lanes, the share a ReLU left zero, a layer
        stats["gate_zero_share"] = aux["gate_zeros"]
    if cfg.router_aux_loss_coef:
        ce = ce + cfg.router_aux_loss_coef * balance
    return ce, stats


def _diffusion_loss(params: Dict[str, Any], x: jnp.ndarray, tokens: jnp.ndarray, cfg: TransformerConfig, mesh=None):
    """(loss, statistics) of block-diffusion training from the NOISED half's
    normed state ``x`` [B, S, D]: ``mean_ℓ (m_ℓ / t_⌊ℓ/D⌋)·CE(x_ℓ, tokens_ℓ)``
    — the target is the row's own clean id, no shift, every position counted in
    the mean and the unmasked ones weighing nothing. Through the one head as
    weights a position (:func:`_cross_entropy` under ``probs``, ``ahead`` 0).
    Says ``masked_share`` (mean of m), ``noise_weight_mean`` (mean of m/t,
    about 1) and ``loss_masked_unweighted`` (the masked positions' mean cross entropy)."""
    t, m = _diffusion_noise(tokens, cfg)
    with jax.named_scope("head_loss"):
        shown = m.astype(jnp.float32)
        weight = shown / jnp.repeat(t, cfg.diffusion_block, axis=1)
    loss, nll = _cross_entropy(params, x, tokens, cfg, mesh, ahead=0, probs=weight)
    with jax.named_scope("head_loss"):
        stats = dict(
            masked_share=jnp.mean(shown), noise_weight_mean=jnp.mean(weight),
            loss_masked_unweighted=jnp.sum(nll * shown) / jnp.maximum(jnp.sum(shown), 1.0),
        )
    return loss, stats


def cuts_by_layer(cfg: TransformerConfig) -> bool:
    """Whether the backward can be cut where the stack's scan iterates
    (:func:`grads_chain`): layers of one kind scanned once under one stage,
    and nothing beside the head that reads the stack's last state. A declared
    pattern, a looped stack, ``pp`` > 1, a multi-token-prediction module and a
    tied table (the head's piece and the tail's would be two gradients of ONE
    leaf, and the chain hands each leaf's on when ITS call ends) stay ONE
    program. Layers of one kind that are ``ssd`` cut like any other."""
    return (
        _of_one_kind(cfg) and max(cfg.pp, 1) == 1 and cfg.ut_steps == 1 and not cfg.n_mtp_modules
        and not cfg.tie_embeddings
    )


def _closure_own(vjp_fn, inputs):
    """(the leaves of a ``jax.vjp`` closure, their treedef, the positions of
    the leaves that are neither an input of the differentiated call nor a
    constant of its trace): what a forward hands its backward beside the
    inputs — under :func:`_remat` the values its policy keeps, without it
    every residual. Two traces of one call at equal shapes give one answer."""
    leaves, treedef = jax.tree_util.tree_flatten(vjp_fn)
    given = {id(a) for a in jax.tree_util.tree_leaves(inputs)}
    own = [i for i, leaf in enumerate(leaves) if isinstance(leaf, jax.core.Tracer) and id(leaf) not in given]
    return leaves, treedef, own


def grads_chain(cfg: TransformerConfig, mesh=None):
    """``jax.value_and_grad`` of :func:`loss_and_stats` as a chain of L + 2
    calls of three functions, for a stack that :func:`cuts_by_layer`: the same
    operations a step, cut where the scan's backward iterates, so that a piece
    of the gradient exists when ITS call ends and not when the last one does
    (``TrainStep.grads``; ``ddp`` has a piece landing on the host while the next
    is computed).

    * ``head(params, tokens)`` -> ``(loss, stats, top, (dx, kept))``: embedding,
      the stack's forward keeping what ``_remat`` keeps (each layer's input and
      what the policy names, stacked over the layers), final norm, the head's
      forward and backward. ``top`` is the gradient of ``final_norm`` and
      ``out``, ``dx`` the cotangent at the top of the stack.
    * ``layer(layers, l, kept, dx)`` -> ``(layer l's gradients, dx below)``, from
      the last layer to the first: the scan's backward body — the checkpoint's
      second forward, then the backward — on the stored leaves' row ``l``. Each
      gradient leaf keeps the stacked leaf's leading axes, as [1, 1, ...].
    * ``tail(embed, tokens, dx)`` -> the embedding's gradient.

    The layer's ``jax.vjp`` closure is traced in ``head`` and in ``layer``
    alike; ``head`` hands on the leaves of it that are its own
    (:func:`_closure_own`) and ``layer`` puts row ``l`` of them in the place of
    its own trace's, whose forward is then dead code: the kernel's output and
    row statistics are computed once a layer a step, as under the scan.
    :func:`grads_of_pieces` stacks the pieces into the parameters' tree.

    Returns ``(head, layer, tail, spec)``; ``spec`` is the partition of the
    cotangent the calls hand on — the activations' own, as the layers
    constrain them."""
    if not cuts_by_layer(cfg):
        raise ValueError(
            "grads_chain: this stack does not cut by layer (cuts_by_layer) — a declared pattern, a loop, pp > 1, a "
            "multi-token-prediction module or tie_embeddings (two pieces of one leaf's gradient) stays one program"
        )
    layer_fn = _remat(cfg, _make_layer_fn(cfg, mesh))
    dt = cfg.dtype
    row = lambda a, l: jax.lax.dynamic_index_in_dim(a, l, keepdims=False)

    def head(params, tokens):
        x = _embed_lookup(params, _diffusion_rows(tokens, cfg), dt, scale=cfg.embed_scale)
        stage = jax.tree_util.tree_map(lambda a: a[0], _compute_dtype(params["layers"], dt))

        def body(x, lp):
            (y, aux), vjp_fn = jax.vjp(layer_fn, x, lp)
            leaves, _, own = _closure_own(vjp_fn, (x, lp))
            return y, (x, aux, [leaves[i] for i in own])

        x, (xs, aux, own) = jax.lax.scan(body, x, stage)

        def top(x, aux, final_norm, out):
            h = _final_norm(cfg, x, final_norm)
            return _loss_of_hidden({"out": out}, h, aux, tokens, cfg, mesh)

        loss, vjp_fn, stats = jax.vjp(top, x, aux, params["final_norm"], params["out"], has_aux=True)
        dx, daux, d_norm, d_out = vjp_fn(jnp.ones_like(loss))
        # what the layers say in whole numbers (an expert's count of tokens) carries no cotangent
        daux = {name: d for name, d in daux.items() if d.dtype != jax.dtypes.float0}
        return loss, stats, {"final_norm": d_norm, "out": d_out}, (dx, (xs, own, daux))

    def layer(layers, l, kept, dx):
        xs, own_rows, daux = kept
        stored = jax.tree_util.tree_map(lambda a: row(a[0], l), layers)
        x, lp = row(xs, l), _compute_dtype(stored, dt)
        (_, aux), vjp_fn = jax.vjp(layer_fn, x, lp)
        leaves, treedef, own = _closure_own(vjp_fn, (x, lp))
        if [(leaves[i].shape, leaves[i].dtype) for i in own] != [(a.shape[1:], a.dtype) for a in own_rows]:
            raise AssertionError("grads_chain: the layer's closure traced differently in head and in layer")
        for i, rows in zip(own, own_rows):
            leaves[i] = row(rows, l)
        d_aux = {name: row(daux[name], l) if name in daux else np.zeros(a.shape, jax.dtypes.float0) for name, a in aux.items()}
        dx, d_lp = jax.tree_util.tree_unflatten(treedef, leaves)((dx, d_aux))
        # the cast to the compute dtype, transposed: the gradient arrives in the stored leaf's dtype
        return jax.tree_util.tree_map(lambda g, p: g.astype(p.dtype)[None, None], d_lp, stored), dx

    def tail(embed, tokens, dx):
        _, vjp_fn = jax.vjp(lambda e: _embed_lookup({"embed": e}, _diffusion_rows(tokens, cfg), dt, scale=cfg.embed_scale), embed)
        return {"embed": vjp_fn(dx)[0]}

    return head, layer, tail, _act_spec()


def grads_of_pieces(pieces):
    """The parameters' tree of the gradient :func:`grads_chain` gave in pieces
    (``top``, layer L-1's ... layer 0's, the embedding's, in that order): the
    layers' rows concatenated along the stacked leaves' second axis."""
    top, *layers, bottom = pieces
    stacked = jax.tree_util.tree_map(lambda *rows: jnp.concatenate(rows, axis=1), *reversed(layers))
    return {**top, "layers": stacked, **bottom}


_MTP = "mtp"  # the name the module's ops carry inside their top-level scopes


def _mtp_hidden(params: Dict[str, Any], h: jnp.ndarray, tokens: jnp.ndarray, cfg: TransformerConfig, mesh=None):
    """(hidden states [B, S, D] after the module's own final norm, the layer's
    aux) of the multi-token-prediction module: ``x_i = [RMSNorm(Emb(t_{i+1});
    enorm) ; RMSNorm(h_i; hnorm)]·eh_proj`` with ``h`` the main stack's output
    after its final norm, one layer of the last layer's kind at positions i,
    the module's ``final_norm``. ``loss_and_stats`` scores them through the
    MAIN ``out`` table against the SECOND-next token (the last two positions
    have no target); ``embed`` and ``out`` are the main model's, so their
    gradients are the sums over both uses. Same [B, S] shapes throughout:
    position S-1 reads the wrapped-around token 0, sees no loss and, the
    layer being causal, is seen by no one."""
    dt = cfg.dtype
    # the module's layer is unrolled like a pattern's: see ``_gradients_apart``
    mtp = _gradients_apart(params[_MTP])
    e = _embed_lookup(params, jnp.roll(tokens, -1, axis=1), dt, nested=_MTP)
    with _scopes("embed", _MTP):
        x = jnp.concatenate(
            [_norm(cfg, e, mtp["enorm"].astype(dt)), _norm(cfg, h, mtp["hnorm"].astype(dt))],
            axis=-1,
        ) @ mtp["eh_proj"].astype(dt)
    layer_fn = _make_layer_fn(cfg, mesh, kind=cfg.layer_kinds()[-1], remat_parts=True, nested=_MTP, from_input=True)
    x, aux = layer_fn(x, mtp["layer"])
    with _scopes("head_loss", _MTP):
        return _norm(cfg, x, mtp["final_norm"].astype(dt)), aux


# Logit-element budget, per device, above which the loss head chunks the
# sequence: 2^27 elements = 512 MB of f32 logits a live buffer; tests
# monkeypatch it to force the chunked head on tiny shapes.
_LOSS_CHUNK_ELEMS = 1 << 27


def _cross_entropy(
    params: Dict[str, Any],
    x: jnp.ndarray,
    tokens: jnp.ndarray,
    cfg: TransformerConfig,
    mesh=None,
    ahead: int = 1,
    probs: Optional[jnp.ndarray] = None,
):
    """Mean cross entropy of final-norm hidden states ``x`` against the token
    ``ahead`` positions on (1: the next token; 2: the multi-token-prediction
    module's, whose ops carry its name inside ``head_loss``; 0: the position's
    own id, block diffusion's target on a noised row); the last
    ``ahead`` positions have no target. With ``probs`` [B, S] (float32, and
    differentiated): (the mean over the supervised positions of ``probs`` times
    the position's cross entropy, each position's cross entropy [B, S] — a
    statistic, which carries no gradient)."""
    nested = _MTP if ahead > 1 else None
    b, s = tokens.shape
    sp = mesh.shape.get("sp", 1) if mesh is not None else 1
    # Long-context memory wall: at s=32k vocab=32k the [B,S,V] f32 logits
    # alone are >4 GB and softmax doubles it — the attention ceiling
    # (flash) was solved but the HEAD would still OOM the chip. Chunk the
    # sequence through the unembed instead. Budget is PER DEVICE (logits
    # shard b over dp·fsdp and V over tp). Under sp>1 the s axis is
    # already sharded and a global-s scan would fight that sharding: the
    # dense path stays (its per-device logits are S/sp smaller), so scale
    # very long context under sp by adding sp shards, not chunking.
    # (an empty batch — benchmark/worker.py's second slice of a batch of one — has no logits to chunk: the dense path)
    if sp == 1 and b and _per_device_logit_elems(cfg, b, s, mesh) > _LOSS_CHUNK_ELEMS:
        return _chunked_loss(params, x, tokens, cfg, mesh, ahead, probs)
    with _scopes("head_loss", nested):
        logits = _scaled_logits(cfg, (x @ _out_table(params, cfg).astype(cfg.dtype)).astype(jnp.float32))
        targets = jnp.roll(tokens, -ahead, axis=1)
        logprobs = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logprobs, targets[..., None], axis=-1)[..., 0]
        mask = _no_target(jnp.ones_like(nll), ahead)
        if probs is not None:
            return jnp.sum(nll * mask * probs) / jnp.sum(mask), jax.lax.stop_gradient(nll)
        return jnp.sum(nll * mask) / jnp.sum(mask)


def _exit_loss(params: Dict[str, Any], hs: jnp.ndarray, tokens: jnp.ndarray, cfg: TransformerConfig, mesh=None):
    """(loss, statistics) of a looped stack's normed states ``hs`` [T, B, S, D]
    under the exit gate, a token at a time and in float32: ``lambda_t =
    sigmoid(h_t . w_g + b_g)``, ``p_t = lambda_t prod_{j<t}(1 - lambda_j)`` and
    the last step takes what is left (``sum_t p_t = 1``); the loss is the mean
    over the supervised tokens of ``sum_t p_t CE_t - exit_entropy_coef H(p)``.
    The T exits go through the one head as T batches (:func:`_cross_entropy`
    under ``probs``: one pass of the unembed a loop step, one accumulator for
    its gradient), so the mean there is over T times the tokens."""
    t, b, s, _ = hs.shape
    with _scopes("head_loss", "exit"):
        gate = params["exit_gate"]
        # h . w_g as a float32 sum over lanes: a [d, 1] product has nothing for the MXU
        logit = jnp.sum(hs.astype(jnp.float32) * gate["w"][:, 0], axis=-1) + gate["b"]
        stay = jax.nn.log_sigmoid(-logit)  # log(1 - lambda_t)
        leave = jax.nn.log_sigmoid(logit).at[-1].set(0.0)
        log_p = leave + jnp.cumsum(stay, axis=0) - stay  # ... + sum_{j<t} log(1 - lambda_j)
        p = jnp.exp(log_p)
    expected, nll = _cross_entropy(
        params, hs.reshape(t * b, s, -1), jnp.tile(tokens, (t, 1)), cfg, mesh, probs=p.reshape(t * b, s)
    )
    with _scopes("head_loss", "exit"):
        mask = _no_target(jnp.ones((b, s), jnp.float32), 1)

        def mean(a):  # over the supervised tokens
            return jnp.sum(a * mask, axis=(-2, -1)) / jnp.sum(mask)

        entropy = mean(-jnp.sum(p * log_p, axis=0))
        stats = dict(exit_probs=mean(p), exit_entropy=entropy, loss_by_step=mean(nll.reshape(t, b, s)))
        return t * expected - cfg.exit_entropy_coef * entropy, stats


def _no_target(mask: jnp.ndarray, ahead: int) -> jnp.ndarray:
    """``mask`` [B, S] with the last ``ahead`` positions zeroed, a position an
    update: at ``ahead`` 1 the one update every model's program had."""
    for i in range(1, ahead + 1):
        mask = mask.at[:, -i].set(0.0)
    return mask


def _per_device_logit_elems(
    cfg: TransformerConfig, batch: int, seq_len: int, mesh
) -> int:
    """Per-device element count of the dense [B, S, V] logits: b shards
    over dp·fsdp, V over tp (the out matrix's tp sharding carries into
    the logits)."""
    batch_shards = vocab_shards = 1
    if mesh is not None:
        batch_shards = mesh.shape.get("dp", 1) * mesh.shape.get("fsdp", 1)
        vocab_shards = mesh.shape.get("tp", 1)
    return (
        max(1, batch // batch_shards)
        * seq_len
        * max(1, cfg.vocab_size // vocab_shards)
    )


def _chunked_loss(
    params: Dict[str, Any],
    h: jnp.ndarray,
    tokens: jnp.ndarray,
    cfg: TransformerConfig,
    mesh=None,
    ahead: int = 1,
    probs: Optional[jnp.ndarray] = None,
):
    """Cross entropy without materializing [B, S, V]: scan the unembed +
    softmax over sequence chunks (:func:`_chunked_nll`). Same numbers as
    the dense path (f32 log-sum-exp per position; accumulation order
    differs only in the final f32 sums), and under ``probs`` the same pair."""
    b, s = tokens.shape
    targets = jnp.roll(tokens, -ahead, axis=1)
    mask = _no_target(jnp.ones((b, s), jnp.float32), ahead)

    # chunk size straight from the per-device budget; s needn't divide —
    # the tail chunk is padded and masked out (any s, prime or odd, gets
    # full chunking)
    per_pos = _per_device_logit_elems(cfg, b, 1, mesh)
    chunk = max(1, min(s, _LOSS_CHUNK_ELEMS // max(1, per_pos)))
    if chunk >= 128:
        chunk -= chunk % 128  # lane-aligned chunks
    n_chunks = -(-s // chunk)
    pad = n_chunks * chunk - s
    if pad:
        h = jnp.pad(h, ((0, 0), (0, pad), (0, 0)))
        targets = jnp.pad(targets, ((0, 0), (0, pad)))
        mask = jnp.pad(mask, ((0, 0), (0, pad)))  # zeros: padded positions

    hs = jnp.moveaxis(h.reshape(b, n_chunks, chunk, -1), 1, 0)
    ts = jnp.moveaxis(targets.reshape(b, n_chunks, chunk), 1, 0)
    ms = jnp.moveaxis(mask.reshape(b, n_chunks, chunk), 1, 0)
    out = _out_table(params, cfg)
    # a divisor of the logits other than 1 goes into the chunks as one more (undifferentiated) argument; at 1 it is
    # absent, and the call and its program are what they were
    scale = None if cfg.logits_scale == 1.0 else jnp.float32(cfg.logits_scale)
    if probs is not None:
        ps = jnp.moveaxis(jnp.pad(probs, ((0, 0), (0, pad))).reshape(b, n_chunks, chunk), 1, 0)
        with jax.named_scope("head_loss"):
            loss, nll = _chunked_nll(hs, out, ts, ms, ps, scale)
        return loss, jnp.moveaxis(nll, 0, 1).reshape(b, n_chunks * chunk)[:, :s]
    if ahead > 1:
        with _scopes("head_loss", _MTP):
            return _chunked_nll_mtp(hs, out, ts, ms)
    with jax.named_scope("head_loss"):
        return _chunked_nll(hs, out, ts, ms, None, scale)


def _chunk_nll(h_c, out_w, t_c, w_c, scale=None):
    """One chunk's f32 logits (divided by ``scale`` where given), their
    log-sum-exp, each position's NLL, and the sum of those under the weights ``w_c``."""
    logits = h_c @ out_w
    # the target's logit is picked BEFORE the cast (the same number): picked
    # after it, XLA keeps an f32 copy of the chunk's logits for the gather
    target = jnp.take_along_axis(logits, t_c[..., None], axis=-1).astype(jnp.float32)
    logits = logits.astype(jnp.float32)
    if scale is not None:
        target, logits = target / scale, logits / scale
    mx = jnp.max(logits, axis=-1, keepdims=True)
    lse = jnp.log(jnp.sum(jnp.exp(logits - mx), axis=-1, keepdims=True)) + mx
    nll = (lse - target)[..., 0]
    return logits, lse, nll, jnp.sum(nll * w_c)


def _chunk_weights(m_c, p_c):
    """A chunk's mask, times the weight a position where the call has them."""
    return m_c if p_c is None else m_c * p_c


@jax.custom_vjp
def _chunked_nll(hs, out, ts, ms, ps=None, scale=None):
    """Mean masked NLL of hidden-state chunks ``hs`` [n, B, c, d] through
    the unembed ``out`` [d, V] (cast to ``hs.dtype`` here, so its gradient
    arrives in ``out``'s own dtype). Called plainly it is the forward scan
    and nothing more. Differentiated (:func:`_chunked_nll_fwd`) the same
    scan also forms each chunk's ``softmax - onehot`` while its logits are
    on the chip and multiplies it into both gradients there: three matrix
    products a chunk, not the four of a backward that computes the logits
    again, and no logits kept.

    With ``ps`` [n, B, c] (float32), a weight a position beside the mask: the
    result is ``(sum(nll * ms * ps) / sum(ms), nll [n, B, c])``. ``ps`` carries
    a cotangent — ``nll * ms / sum(ms)``, which the forward scan already has —
    and the second result, a statistic, carries none. Without ``ps`` the
    program is what it was before weights existed, to the letter. ``scale``
    (a float32 scalar, ``logits_scale``): the chunks' float32 logits are divided
    by it, and their cotangent with them; it carries no cotangent itself."""
    out_w = out.astype(hs.dtype)

    def body(nll_sum, xt):
        h_c, t_c, m_c, p_c = xt
        _, _, nll_c, total = _chunk_nll(h_c, out_w, t_c, _chunk_weights(m_c, p_c), scale)
        return nll_sum + total, None if p_c is None else nll_c

    nll_sum, nll = jax.lax.scan(body, jnp.float32(0.0), (hs, ts, ms, ps))
    loss = nll_sum / jnp.sum(ms)
    return loss if ps is None else (loss, nll)


def _chunked_nll_fwd(hs, out, ts, ms, ps=None, scale=None):
    out_w = out.astype(hs.dtype)
    cnt = jnp.sum(ms)

    def body(carry, xt):
        h_c, t_c, m_c, p_c = xt
        w_c = _chunk_weights(m_c, p_c)
        nll_sum, d_out = carry
        logits, lse, nll_c, total = _chunk_nll(h_c, out_w, t_c, w_c, scale)
        onehot = jax.nn.one_hot(t_c, logits.shape[-1], dtype=logits.dtype)
        # in the dtype the transposed products of `(h_c @ out_w).astype(f32)`
        # read: that cast's cotangent is cast back to the compute dtype
        # (the probabilities first and the weight after them: the order the program's text always had)
        off = jnp.exp(logits - lse) - onehot
        dlogits = (off * (w_c / cnt if scale is None else w_c / (cnt * scale))[..., None]).astype(h_c.dtype)
        dh_c = jnp.einsum("bcv,dv->bcd", dlogits, out_w)
        # summed over the chunks in f32, so `d out` is rounded once and not
        # once a chunk: 1.2 ms of a 67 ms head at b8 x s2048 on a v5e
        d_out = d_out + jnp.einsum(
            "bcd,bcv->dv", h_c, dlogits, preferred_element_type=jnp.float32
        )
        return (nll_sum + total, d_out), dh_c if p_c is None else (dh_c, nll_c)

    (nll_sum, d_out), kept = jax.lax.scan(
        body, (jnp.float32(0.0), jnp.zeros(out.shape, jnp.float32)), (hs, ts, ms, ps)
    )
    if ps is None:
        return nll_sum / cnt, (kept, d_out.astype(out.dtype), None)
    dhs, nll = kept
    return (nll_sum / cnt, nll), (dhs, d_out.astype(out.dtype), nll * ms / cnt)


def _head_cotangents(res, g):
    """(hs', out', None, None, ps', None) of :func:`_chunked_nll` from what
    its forward scan kept; under weights ``g`` is the pair's and the
    statistic's part of it is dropped."""
    dhs, d_out, d_ps = res
    if d_ps is not None:
        g = g[0]
    return (g * dhs).astype(dhs.dtype), (g * d_out).astype(d_out.dtype), None, None, None if d_ps is None else g * d_ps, None


def _chunked_nll_bwd(res, g):
    with jax.named_scope("head_loss"):
        return _head_cotangents(res, g)


_chunked_nll.defvjp(_chunked_nll_fwd, _chunked_nll_bwd)


@jax.custom_vjp
def _chunked_nll_mtp(hs, out, ts, ms):
    """:func:`_chunked_nll` as the multi-token-prediction module calls it: the
    same forward rule, the backward's two products under the module's name."""
    return _chunked_nll.__wrapped__(hs, out, ts, ms)


def _chunked_nll_mtp_bwd(res, g):
    with _scopes("head_loss", _MTP):
        return _head_cotangents(res, g)[:4]


_chunked_nll_mtp.defvjp(_chunked_nll_fwd, _chunked_nll_mtp_bwd)


def _pipelined_loss(
    params: Dict[str, Any],
    tokens: jnp.ndarray,
    cfg: TransformerConfig,
    mesh,
) -> jnp.ndarray:
    """pp>1 loss with the cheap pipeline exit (pipeline.py head_fn): same
    numbers as the forward()+loss composition, minus the O(activations)
    psum that existed only to replicate the last stage's outputs."""
    from torchft_tpu.parallel.pipeline import pipeline_forward

    b, s = tokens.shape
    dt = cfg.dtype
    pp = cfg.pp
    x = _embed_lookup(params, tokens, dt)
    layers = _compute_dtype(params["layers"], dt)

    sp_size = mesh.shape.get("sp", 1)
    sp_manual = sp_size > 1
    stage_fn = _pipeline_stage_fn(cfg, mesh, sp_manual)
    m = cfg.microbatches or pp
    assert b % m == 0, f"batch {b} must divide into {m} microbatches"
    x_mb = x.reshape(m, b // m, s, -1)
    # the shifted targets are built OUTSIDE the manual region so GSPMD
    # handles the cross-sp-block halo of the roll
    t_mb = jnp.roll(tokens, -1, axis=1).reshape(m, b // m, s)
    head_params = {
        "final_norm": params["final_norm"].astype(dt),
        "out": params["out"].astype(dt),
    }

    @jax.named_scope("head_loss")
    def head_fn(hp, outs, t):
        h = _norm(cfg, outs, hp["final_norm"])
        logits = (h @ hp["out"]).astype(jnp.float32)
        logprobs = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logprobs, t[..., None], axis=-1)[..., 0]
        mask = jnp.ones_like(nll)
        if sp_manual:
            # global position S-1 lives in the LAST sp block only
            last_block = jax.lax.axis_index("sp") == sp_size - 1
            mask = mask.at[..., -1].set(jnp.where(last_block, 0.0, 1.0))
        else:
            mask = mask.at[..., -1].set(0.0)
        return {"nll": jnp.sum(nll * mask), "cnt": jnp.sum(mask)}

    res = pipeline_forward(
        layers,
        x_mb,
        stage_fn,
        mesh,
        head_fn=head_fn,
        head_params=head_params,
        head_extras=(t_mb,),
    )
    return res["nll"] / res["cnt"]
