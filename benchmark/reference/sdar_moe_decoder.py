"""Plain reference for the decoder of SDAR-30B-A3B-Chat (``model_type``
``sdar_moe``) TRAINED BY BLOCK DIFFUSION, as the benchmark's ``sdar-*``
configurations run it: the noise, the 2·S rows, the mask from its definition,
the layers and the weighted loss in straightforward float32 ``jax.numpy``,
written from the equations of ISSUE 65 (after the published ``config.json`` and
the objective of arXiv:2503.09573 as SDAR, arXiv:2510.06303, adapts an
autoregressive model to it), independent of ``torchft_tpu.models`` and
``torchft_tpu.ops``. No flash kernel, no tile that is skipped, no sort, no
grouped matmul, no remat: the mask is a comparison of halves and blocks, an
expert is applied to every row and weighted.

**The objective.** A sequence of S clean ids x, block length D
(``diffusion_block``), one noise level ``t_b`` a block of D positions and
``m_ℓ ~ Bernoulli(t_⌊ℓ/D⌋)``: the input is the 2·S rows ``u = [x̃ ; x]``,
``x̃_ℓ`` = the mask id where ``m_ℓ``, else ``x_ℓ``. Row r has position
``p(r) = r mod S`` and block ``β(r) = ⌊p(r)/D⌋`` and is NOISED iff ``r < S``.
Row r sees key c iff

* r and c are both noised and ``β(c) = β(r)`` (in both directions), or
* r is noised, c is clean and ``β(c) < β(r)``, or
* r and c are both clean and ``β(c) <= β(r)``;

a clean row never sees a noised key. The loss of the sequence is
``(1/S)·Σ_{ℓ<S} (m_ℓ / t_⌊ℓ/D⌋)·(−log softmax(W_out·N(h_ℓ))[x_ℓ])`` over the
NOISED rows' final states: the target is the row's own clean id (no shift),
every position counts in the mean, an unmasked one weighs nothing, and the
clean half has no loss.

**The noise** (:func:`noise`), a function of the sequence's own ids and
``diffusion_seed`` alone, as the configuration's ``assumed`` writes it down:
``c = Σ_ℓ x_ℓ·(2ℓ + 1) mod 2³¹``; ``key = fold_in(PRNGKey(seed), c)``;
``(key_t, key_m) = split(key)``; ``t_b = t_min + (1 − t_min)·U_b`` with
``U = uniform(key_t, [S/D])``, computed as ``(U_b + t_min/(1 − t_min))·(1 −
t_min)`` (the sum first: a product followed by a sum is contracted into one
rounding by some compilers and not by others); ``m_ℓ = V_ℓ < t_⌊ℓ/D⌋`` with
``V = uniform(key_m, [S])``; float32, JAX's default (threefry) generator.

**A layer**, x ``[2S, d]``: ``h1 = RMSNorm(x; ln1)`` (eps ``norm_eps``);
``q = h1·W_q`` → ``n_heads x D_h``, ``k, v = h1·W_k, h1·W_v`` → ``n_kv_heads x
D_h``, no bias; q and k normed head by head over their D_h lanes under ONE
weight the heads share (``q_norm``, ``k_norm``); both rotated over the whole
head, lane i with lane i + D_h/2 at ``rope_theta^(-2i/D_h)``, by the ROW'S
POSITION ``r mod S`` (positions repeat); ``x' = x + softmax(q·kᵀ/√D_h +
mask)·v·W_o``, query head a reading key/value head ``a // (n_heads /
n_kv_heads)``; ``h2 = RMSNorm(x'; ln2)``; ``p = softmax(h2·W_r)`` over all E
experts, the k largest renormalised over themselves (``router_renormalize``);
``out = x' + Σ_{e chosen AND held} w_e·W_down,e(silu(h2·W_gate,e) ⊙ h2·W_up,e)``.
Every layer has experts, no shared one; final RMSNorm; a separate output table.
Queries go in blocks of :data:`QUERY_BLOCK` against every key, so that one
sequence's 16 384 rows under 32 heads fit a chip beside the training state (a
block's scores: 32 x 256 x 16 384 float32 = 0.54 GB).

**Departures from the published model, all stated in the configuration
file.** (1) *The share*: of the E experts the router chooses among, this chip
holds ``n_experts_held``; what the absent ones would add is left out, here as
in the program. (2) Block length, schedule, mask id and the noise's derivation
are not in the published config (``assumed``).

``slip`` names ONE thing done wrong, for the checks that have to tell the
program from a near miss (``check_sdar.py``, ``tests/test_block_diffusion.py``):
:data:`SLIPS`. ``sizes`` is a configuration's ``program.transformer_config``;
parameter names and shapes are the interface with the program
(``init_params``): ``embed``, ``out``, ``final_norm`` and ``layers[name]
[1, L, ...]``.

On a TPU a float32 matmul runs in lower precision unless
``jax.default_matmul_precision("highest")`` is set; :func:`loss` sets it.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

__all__ = ["noise", "rows", "seen", "hidden", "layer", "loss_of_hidden", "sequence_loss", "per_sequence_loss", "loss", "SLIPS"]

QUERY_BLOCK = 256
SLIPS = (
    "own_clean_block_seen",  # a noised row also sees the CLEAN keys of its own block: the answer leaks
    "noised_rows_at_s_plus_p",  # the noised rows rotated at S + p, as if the 2·S rows were one run of positions
    "weight_one",  # the masked positions weigh 1 and not 1/t
    "causal_inside_the_block",  # a noised row sees the noised keys of its block up to itself only
    "targets_shifted",  # the target is the NEXT clean id
    "not_renormalised",  # the chosen experts' weights as the softmax over all E gives them
)


def _rms_norm(x, weight, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight


def noise(seq, sizes):
    """(t [S/D], m [S] bool) of ONE sequence's clean ids ``seq`` [S]."""
    s, d = seq.shape[0], sizes["diffusion_block"]
    least = sizes.get("diffusion_t_min", 1e-3)
    odd = 2 * jnp.arange(s, dtype=jnp.uint32) + 1
    c = jnp.sum(seq.astype(jnp.uint32) * odd, dtype=jnp.uint32) & jnp.uint32(0x7FFFFFFF)
    key_t, key_m = jax.random.split(jax.random.fold_in(jax.random.PRNGKey(sizes.get("diffusion_seed", 0)), c))
    t = (jax.random.uniform(key_t, (s // d,), jnp.float32) + jnp.float32(least / (1.0 - least))) * jnp.float32(1.0 - least)
    return t, jax.random.uniform(key_m, (s,), jnp.float32) < jnp.repeat(t, d)


def rows(seq, m, sizes):
    """The 2·S ids ``[x̃ ; x]`` of one sequence."""
    mask_id = sizes.get("diffusion_mask_id", -1)
    mask_id = mask_id if mask_id >= 0 else sizes["vocab_size"] - 1
    return jnp.concatenate([jnp.where(m, mask_id, seq), seq])


def seen(q_row, k_row, s: int, d: int, slip: Optional[str] = None):
    """Whether row ``q_row`` sees key ``k_row`` (arrays that broadcast)."""
    q_noised, k_noised = q_row < s, k_row < s
    q_pos, k_pos = q_row % s, k_row % s
    q_blk, k_blk = q_pos // d, k_pos // d
    own = k_blk == q_blk
    if slip == "causal_inside_the_block":
        own = own & (k_pos <= q_pos)
    before = k_blk <= q_blk if slip == "own_clean_block_seen" else k_blk < q_blk
    return jnp.where(k_noised, q_noised & own, jnp.where(q_noised, before, k_blk <= q_blk))


def _rotate(x, positions, theta):
    """x [R, H, D]: lane i with lane i + D/2 at ``theta^(-2i/D)``, the whole head."""
    d = x.shape[-1]
    inv_freq = jnp.asarray([theta ** (-2.0 * i / d) for i in range(d // 2)], jnp.float32)
    angle = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    a, b = x[..., : d // 2], x[..., d // 2 :]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _attention(w, h, sizes, slip):
    r = h.shape[0]
    s, d = r // 2, sizes["diffusion_block"]
    heads, hd = sizes["n_heads"], sizes["head_dim"]
    kv_heads = sizes.get("n_kv_heads") or heads
    group = heads // kv_heads
    eps = sizes.get("norm_eps", 1e-6)
    q = _rms_norm((h @ w["wq"]).reshape(r, heads, hd), w["q_norm"], eps)
    k = _rms_norm((h @ w["wk"]).reshape(r, kv_heads, hd), w["k_norm"], eps)
    v = (h @ w["wv"]).reshape(r, kv_heads, hd)
    row = jnp.arange(r)
    # the slip puts the noised half behind the clean one: clean at p, noised at S + p
    positions = jnp.where(row < s, s + row, row - s) if slip == "noised_rows_at_s_plus_p" else row % s
    q, k = _rotate(q, positions, sizes["rope_theta"]), _rotate(k, positions, sizes["rope_theta"])
    q = q.reshape(r, kv_heads, group, hd)  # query head a reads key/value head a // group
    block = QUERY_BLOCK if r % QUERY_BLOCK == 0 else r

    def some(start):  # a block of queries against every key
        q_blk = jax.lax.dynamic_slice_in_dim(q, start, block, axis=0)
        scores = jnp.einsum("qngd,knd->ngqk", q_blk, k) / jnp.sqrt(jnp.float32(hd))
        mask = seen((start + jnp.arange(block))[:, None], row[None, :], s, d, slip)
        probs = jax.nn.softmax(jnp.where(mask[None, None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("ngqk,knd->qngd", probs, v)

    att = jax.lax.map(some, jnp.arange(0, r, block)).reshape(r, heads * hd)
    return att @ w["wo"]


def gate(w, h, sizes, slip=None):
    """[R, E]: the weight of every expert for every row, zero where the expert is not among the row's k."""
    n_experts, top_k = sizes["n_experts"], sizes["top_k"]
    probs = jax.nn.softmax(h @ w["router"], axis=-1)
    kth = jnp.sort(probs, axis=-1)[..., n_experts - top_k]
    weight = jnp.where(probs >= kth[..., None], probs, 0.0)  # the k largest (seeded random weights: no ties)
    if sizes.get("router_renormalize", False) and slip != "not_renormalised":
        weight = weight / jnp.sum(weight, axis=-1, keepdims=True)
    return weight


def _experts(w, h, weight, sizes):
    held = sizes.get("n_experts_held") or sizes["n_experts"]
    first = sizes.get("expert_share_index", 0) * held

    def add(y, e):  # the experts held here; the absent ones' part is left out
        mine = jax.lax.dynamic_index_in_dim(weight, first + e, axis=-1, keepdims=True)
        return y + mine * ((jax.nn.silu(h @ w["w_gate"][e]) * (h @ w["w_in"][e])) @ w["w_out"][e]), None

    return jax.lax.scan(add, jnp.zeros_like(h), jnp.arange(held))[0]


def layer(w, x, sizes, slip=None):
    """One layer on the rows ``x`` [2S, d] of one sequence with its own float32 parameters ``w``."""
    eps = sizes.get("norm_eps", 1e-6)
    x = x + _attention(w, _rms_norm(x, w["ln1"], eps), sizes, slip)
    h2 = _rms_norm(x, w["ln2"], eps)
    return x + _experts(w, h2, gate(w, h2, sizes, slip), sizes)


def hidden(params: Dict[str, Any], seq, sizes: Dict[str, Any], slip: Optional[str] = None):
    """One sequence's clean ids [S] -> (the NOISED rows' state after ``final_norm`` [S, d], t [S/D], m [S])."""
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    t, m = noise(seq, sizes)
    x = f32(params["embed"])[rows(seq, m, sizes)]
    for i in range(sizes["n_layers"]):
        x = layer({k: f32(v[0, i]) for k, v in params["layers"].items()}, x, sizes, slip)
    return _rms_norm(x[: seq.shape[0]], f32(params["final_norm"]), sizes.get("norm_eps", 1e-6)), t, m


def loss_of_hidden(params, h, t, m, seq, sizes, slip: Optional[str] = None):
    """The loss of one sequence from its noised rows' final state ``h`` [S, d]
    and its noise: the mean over the S positions of ``m/t`` times the row's
    cross entropy against the clean id there."""
    logp = jax.nn.log_softmax(h @ jnp.asarray(params["out"], jnp.float32), axis=-1)
    target = jnp.roll(seq, -1) if slip == "targets_shifted" else seq
    nll = -jnp.take_along_axis(logp, target[:, None], axis=-1)[:, 0]
    weight = m / (1.0 if slip == "weight_one" else jnp.repeat(t, sizes["diffusion_block"]))
    return jnp.mean(weight * nll)


def sequence_loss(params, seq, sizes, slip: Optional[str] = None):
    """The training loss of one sequence [S]."""
    h, t, m = hidden(params, seq, sizes, slip)
    return loss_of_hidden(params, h, t, m, seq, sizes, slip)


def per_sequence_loss(params, tokens, sizes, slip: Optional[str] = None):
    """The training loss of each sequence by itself, [B] — what the program's
    ``loss_fn`` gives for a batch of that one sequence."""
    return jax.lax.map(lambda seq: sequence_loss(params, seq, sizes, slip), tokens)


def loss(params, tokens, sizes):
    """The training loss of one call, under ``highest`` matmul precision."""
    with jax.default_matmul_precision("highest"):
        return jnp.mean(per_sequence_loss(params, tokens, sizes))
