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
"""

from __future__ import annotations

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
    ring_attention,
    ring_attention_local,
)
from torchft_tpu.ops.layers import (
    moe_dispatch,
    moe_dropless,
    rms_norm,
    rotary_embed,
    swiglu,
)

__all__ = [
    "TransformerConfig",
    "PRESETS",
    "init_params",
    "param_specs",
    "forward",
    "loss_fn",
    "loss_and_stats",
]


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32000
    d_model: int = 512
    n_layers: int = 8
    n_heads: int = 8
    head_dim: int = 64
    d_ff: int = 1408
    n_experts: int = 0  # 0 => dense FFN
    top_k: int = 2  # experts per token (router weights NOT renormalised)
    capacity_factor: float = 1.25  # ep > 1 only: the dropless path has none
    # weight of the load-balancing term E·Σ f_e·P_e (mean over layers) in
    # the training loss; 0 => the loss is the cross entropy alone
    router_aux_loss_coef: float = 0.0
    # RMSNorm with a learned weight over the whole q and k projections,
    # before the head split and RoPE (OLMoE's attention)
    qk_norm: bool = False
    rope_theta: float = 10000.0
    norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16  # compute dtype (MXU-native)
    remat: bool = True
    # checkpoint policy under remat: "all" recomputes the whole layer in
    # the backward (lowest memory); "dots" saves matmul outputs and
    # recomputes only elementwise/softmax (MXU work runs once — the
    # round-5 sweet spot at short S where memory isn't the constraint)
    remat_policy: str = "all"
    pp: int = 1  # pipeline stages; n_layers % pp == 0
    microbatches: int = 0  # 0 => = pp
    # "auto" | "plain" | "chunked" | "flash". auto picks from what it can
    # observe (``_attention_path``): ring attention under sp > 1; on a TPU,
    # outside a manual region, at head_dim % 128 == 0 and s % 512 == 0 from
    # s1024 on the Pallas flash kernel at 512 x 512 tiles (the fastest core
    # measured there, PERF.md §6 PR 31); else the tiered chunked scan
    # (ops/attention.chunked_attention, pure XLA) from s >= 1024 and plain
    # XLA attention below; the kernel again, for memory's sake, past the
    # scores-memory ceiling where chunked cannot run
    attention_impl: str = "auto"

    @property
    def layers_per_stage(self) -> int:
        assert self.n_layers % max(self.pp, 1) == 0
        return self.n_layers // max(self.pp, 1)

    @property
    def qkv_dim(self) -> int:
        return self.n_heads * self.head_dim


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


def init_params(rng, cfg: TransformerConfig) -> Dict[str, Any]:
    """Params as a pytree of float32 numpy-backed arrays; leading [pp, Lp]
    axes on per-layer tensors."""
    keys = jax.random.split(rng, 16)
    d, qkv, f = cfg.d_model, cfg.qkv_dim, cfg.d_ff
    lp, pp = cfg.layers_per_stage, max(cfg.pp, 1)

    def dense(key, *shape, fan_in):
        return (
            jax.random.normal(key, shape, jnp.float32) * (fan_in**-0.5)
        )

    layers: Dict[str, Any] = {
        "ln1": jnp.ones((pp, lp, d), jnp.float32),
        "ln2": jnp.ones((pp, lp, d), jnp.float32),
        "wq": dense(keys[0], pp, lp, d, qkv, fan_in=d),
        "wk": dense(keys[1], pp, lp, d, qkv, fan_in=d),
        "wv": dense(keys[2], pp, lp, d, qkv, fan_in=d),
        "wo": dense(keys[3], pp, lp, qkv, d, fan_in=qkv),
    }
    if cfg.qk_norm:
        layers.update(
            q_norm=jnp.ones((pp, lp, qkv), jnp.float32),
            k_norm=jnp.ones((pp, lp, qkv), jnp.float32),
        )
    if cfg.n_experts:
        e = cfg.n_experts
        layers.update(
            router=dense(keys[4], pp, lp, d, e, fan_in=d),
            w_gate=dense(keys[5], pp, lp, e, d, f, fan_in=d),
            w_in=dense(keys[6], pp, lp, e, d, f, fan_in=d),
            w_out=dense(keys[7], pp, lp, e, f, d, fan_in=f),
        )
    else:
        layers.update(
            w_gate=dense(keys[5], pp, lp, d, f, fan_in=d),
            w_in=dense(keys[6], pp, lp, d, f, fan_in=d),
            w_out=dense(keys[7], pp, lp, f, d, fan_in=f),
        )
    return {
        "embed": dense(keys[8], cfg.vocab_size, d, fan_in=1.0),
        "layers": layers,
        "final_norm": jnp.ones((d,), jnp.float32),
        "out": dense(keys[9], d, cfg.vocab_size, fan_in=d),
    }


def param_specs(cfg: TransformerConfig) -> Dict[str, Any]:
    """PartitionSpec per leaf (matches init_params structure)."""
    row, col = P("pp", None, "fsdp", "tp"), P("pp", None, "tp", "fsdp")
    layers: Dict[str, Any] = {
        "ln1": P("pp", None, None),
        "ln2": P("pp", None, None),
        "wq": row,
        "wk": row,
        "wv": row,
        "wo": col,
    }
    if cfg.qk_norm:
        # over the tp-sharded projection: the norm's mean is one all-reduce
        layers.update(q_norm=P("pp", None, "tp"), k_norm=P("pp", None, "tp"))
    if cfg.n_experts:
        layers.update(
            router=P("pp", None, "fsdp", None),
            w_gate=P("pp", None, "ep", "fsdp", "tp"),
            w_in=P("pp", None, "ep", "fsdp", "tp"),
            w_out=P("pp", None, "ep", "tp", "fsdp"),
        )
    else:
        layers.update(w_gate=row, w_in=row, w_out=col)
    return {
        # [V,D] with vocab UNSHARDED and D over (tp,fsdp): the same bytes
        # per device as the row+col P("tp","fsdp") layout, but the token
        # gather is fully local and the cotangent lands in the stored
        # layout — SPMD previously fell back to involuntary full
        # rematerialization on both (round-3 review missing #2)
        "embed": P(None, ("tp", "fsdp")),
        "layers": layers,
        "final_norm": P(None),
        "out": P("fsdp", "tp"),
    }


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


def _ffn_moe(lp: Dict[str, Any], x: jnp.ndarray, cfg: TransformerConfig):
    """Sparse experts, dropless: ``p = softmax(h·Wr)`` in float32, the k
    largest chosen, their ``p`` applied as they are (not renormalised), every
    chosen expert counted whatever its load. Returns (y, (balance term
    ``E·Σ_e f_e·P_e`` over the call's tokens, tokens per expert [E]))."""
    b, s, d = x.shape
    tokens = x.reshape(b * s, d)
    with jax.named_scope("router"):
        logits = jnp.dot(tokens, lp["router"], preferred_element_type=jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        top_w, top_idx = jax.lax.top_k(probs, cfg.top_k)
    y, counts = moe_dropless(
        tokens, top_idx, top_w.astype(x.dtype), lp["w_gate"], lp["w_in"], lp["w_out"]
    )
    with jax.named_scope("router"):
        frac = counts.astype(jnp.float32) / (b * s)  # sums to k; no gradient
        balance = cfg.n_experts * jnp.sum(frac * jnp.mean(probs, axis=0))
    return y.reshape(b, s, d), (balance, counts)


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

    Measured (my chip run, PR 31; PERF.md §6 holds the table) at 16 heads x
    128, forward + ``remat``'s forward + backward, device time of the whole
    call: b8 x s2048 8.3 ms a layer at 512 x 512 against 16.3 ms chunked and
    22.2 plain (1024 x 1024: 8.8; 256 x 512: 11.6; 128 x 128: 24.4);
    b4 x s4096 13.8 against 25.5; b8 x s1024 3.1 against 4.9. At head_dim
    64 (b4 x s1024 x 16: ``scale_647M``) the kernel ties chunked (1.25
    against 1.28 ms) and a head is then no whole lane tile (a transpose a
    side): declined. Below s1024 nothing was measured: plain keeps it."""
    if head_dim % 128 or seq_len % 512 or seq_len < 1024:
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
    and at the shapes :func:`_flash_blocks` declines. Override the engage
    point with TORCHFT_TPU_ATTN_CHUNKED_MIN_S. Sequences not divisible by
    the chunk fall back to plain (both explicit and auto)."""
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
    cfg: TransformerConfig, seq_len: int, batch: int, mesh, sp_manual: bool = False
) -> Tuple[str, str, Optional[Tuple[int, int]]]:
    """(impl, reason, (block_q, block_k) or None): which code computes the
    causal core softmax(QKᵀ)V of a layer, decided from what can be
    observed — the backend, the mesh, whether the caller is already inside
    a manual region, and the shapes. impl is "ring" (sp > 1), "flash" (the
    Pallas kernel), "chunked" or "plain"."""
    sp_size = mesh.shape.get("sp", 1) if mesh is not None else 1
    if sp_size > 1:
        return "ring", "sp > 1: the sequence is sharded over chips", None
    # the kernel needs its own (full) manual region, which cannot nest in
    # the pipeline's partial-manual shard_map (Shardy rejects it)
    inside_manual = sp_manual or (mesh is not None and mesh.shape.get("pp", 1) > 1)
    fast = _flash_blocks(seq_len, cfg.head_dim)
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


_PATHS_SAID: set = set()


def _say_attention_path(impl, reason, blocks, batch, seq_len, cfg) -> None:
    """One ``attention_path`` event and one INFO line per traced shape, so a
    worker's log and event trail say which core every program took and why."""
    block_q, block_k = blocks or (0, 0)
    fields = dict(
        impl=impl, block_q=block_q, block_k=block_k, batch=batch, seq=seq_len,
        head_dim=cfg.head_dim, reason=reason,
    )
    key = (*fields.values(), cfg.n_heads)
    if key in _PATHS_SAID:
        return
    _PATHS_SAID.add(key)
    import logging

    from torchft_tpu import telemetry

    telemetry.emit("attention_path", **fields)
    logging.getLogger(__name__).info(
        "attention_path %s", " ".join(f"{k}={v}" for k, v in fields.items())
    )


def _flash_sharded(q, k, v, mesh, blocks: Tuple[int, int]):
    """Flash attention under GSPMD: pallas_call has no partitioning rules,
    so without shard_map the SPMD partitioner would all-gather q/k/v onto
    every chip. Attention is independent per (batch, head), so manualize
    the batch/head axes and run the kernel per shard."""
    from torchft_tpu.ops.pallas.flash_attention import flash_attention

    kernel = functools.partial(
        flash_attention, causal=True, block_q=blocks[0], block_k=blocks[1]
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


def _make_layer_fn(cfg: TransformerConfig, mesh, sp_manual: bool = False):
    sp_size = mesh.shape.get("sp", 1) if mesh is not None else 1
    experts_over_chips = (
        bool(cfg.n_experts) and mesh is not None and mesh.shape.get("ep", 1) > 1
    )
    if experts_over_chips and (cfg.top_k != 2 or cfg.router_aux_loss_coef):
        raise ValueError(
            f"ep={mesh.shape['ep']}: experts over chips still run the top-2 "
            "capacity dispatch (_ffn_moe_ep), which has no other top_k and no "
            f"balance term; got top_k={cfg.top_k}, "
            f"router_aux_loss_coef={cfg.router_aux_loss_coef}"
        )

    def layer_fn(x: jnp.ndarray, lp: Dict[str, Any]):
        """(x, aux): aux is (balance term, tokens per expert) of a dropless
        expert layer and () otherwise."""
        aux = ()
        x = _constrain(x, _act_spec(sp_manual))
        with jax.named_scope("attn"):
            h = rms_norm(x, lp["ln1"], cfg.norm_eps)
            b, s, _ = h.shape  # s is the sp-local block inside a manual region
            if sp_manual and sp_size > 1:
                positions = jax.lax.axis_index("sp") * s + jnp.arange(s)
            else:
                positions = jnp.arange(s)
            q, k = h @ lp["wq"], h @ lp["wk"]
            if cfg.qk_norm:
                q = rms_norm(q, lp["q_norm"], cfg.norm_eps)
                k = rms_norm(k, lp["k_norm"], cfg.norm_eps)
            q = q.reshape(b, s, cfg.n_heads, cfg.head_dim)
            k = k.reshape(b, s, cfg.n_heads, cfg.head_dim)
            v = (h @ lp["wv"]).reshape(b, s, cfg.n_heads, cfg.head_dim)
            q = rotary_embed(q, positions, cfg.rope_theta)
            k = rotary_embed(k, positions, cfg.rope_theta)
            # s is the sp-local block inside a manual region; the rule
            # reads sp from the mesh, not from s
            impl, why, blocks = _attention_path(cfg, s, b, mesh, sp_manual)
            _say_attention_path(impl, why, blocks, b, s, cfg)
            with jax.named_scope("core"):
                if impl == "ring" and sp_manual:
                    att = ring_attention_local(q, k, v, sp_size, causal=True)
                elif impl == "ring":
                    att = ring_attention(q, k, v, mesh, causal=True)
                elif impl == "flash":
                    att = _flash_sharded(q, k, v, mesh, blocks)
                elif impl == "chunked":
                    att = chunked_attention(
                        q, k, v, causal=True, chunk=_attn_chunk(s),
                        tiers=_attn_tiers(),
                    )
                else:
                    att = attention(q, k, v, causal=True)
            x = x + att.reshape(b, s, cfg.qkv_dim) @ lp["wo"]

        with jax.named_scope("moe" if cfg.n_experts else "ffn"):
            h = rms_norm(x, lp["ln2"], cfg.norm_eps)
            if experts_over_chips:
                x = x + _ffn_moe_ep(lp, h, cfg)
            elif cfg.n_experts:
                y, aux = _ffn_moe(lp, h, cfg)
                x = x + y
            else:
                x = x + _ffn_dense(lp, h)
        return _constrain(x, _act_spec(sp_manual)), aux

    return layer_fn


def _make_stage_fn(cfg: TransformerConfig, mesh, sp_manual: bool = False):
    layer_fn = _make_layer_fn(cfg, mesh, sp_manual)
    if cfg.remat:
        if cfg.remat_policy == "dots":
            policy = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        elif cfg.remat_policy == "all":
            policy = None
        else:
            raise ValueError(
                f"remat_policy={cfg.remat_policy!r}: expected 'all' or "
                "'dots' (a typo here would silently pay full recompute)"
            )
        layer_fn = jax.checkpoint(layer_fn, policy=policy)

    def stage_fn(stage_params: Dict[str, Any], x: jnp.ndarray):
        """(x, aux stacked over the stage's layers); see ``layer_fn``."""
        # stage_params leaves: [Lp, ...]; scan over the layer axis
        return jax.lax.scan(layer_fn, x, stage_params)

    return stage_fn


def _pipeline_stage_fn(cfg: TransformerConfig, mesh, sp_manual: bool):
    """The stage as ``pipeline_forward`` calls it: hidden state in, hidden
    state out. The experts' balance term does not cross stages
    (``loss_and_stats`` refuses a non-zero coefficient under ``pp`` > 1)."""
    stage_fn = _make_stage_fn(cfg, mesh, sp_manual=sp_manual)
    return lambda stage_params, x: stage_fn(stage_params, x)[0]


def _embed_lookup(
    params: Dict[str, Any], tokens: jnp.ndarray, dt
) -> jnp.ndarray:
    """Embedding gather with EXPLICIT gather partitioning (round-3 review
    missing #2): the table is stored P(None, ("tp","fsdp")) — vocab
    unsharded, D over (tp,fsdp) — so the token gather is fully LOCAL
    (SPMD cannot partition a vocab-sharded gather and previously fell
    back to "involuntary full rematerialization", replicating [V,D] on
    every device each step). Only the (much smaller) [B,S,D] activation
    is resharded to the standard spec afterwards."""
    with jax.named_scope("embed"):
        embed = _constrain(params["embed"].astype(dt), P(None, ("tp", "fsdp")))
        tok = _constrain(tokens, P("dp", "sp"))
        x = jnp.take(embed, tok, axis=0)
        # reshard to the activation spec ONE axis move per step — GSPMD
        # falls back to a full-remat copy on the combined move (fsdp D→B
        # while dropping tp) but handles each single-axis hop efficiently
        x = _constrain(x, P("dp", "sp", ("tp", "fsdp")))
        x = _constrain(x, P(("dp", "fsdp"), "sp", "tp"))
        return _constrain(x, _act_spec())


def _hidden_states(
    params: Dict[str, Any],
    tokens: jnp.ndarray,
    cfg: TransformerConfig,
    mesh=None,
):
    """tokens [B, S] -> (final-norm hidden states [B, S, D] in cfg.dtype, aux)
    (everything except the unembed — the chunked loss head consumes this
    without ever materializing [S, V] logits). ``aux`` is what the layer
    scan carries out beside the hidden state: (balance term [L], tokens per
    expert [L, E]) of dropless expert layers, else ()."""
    from torchft_tpu.parallel.pipeline import pipeline_forward

    b, s = tokens.shape
    dt = cfg.dtype
    x = _embed_lookup(params, tokens, dt)

    layers = jax.tree_util.tree_map(lambda a: a.astype(dt), params["layers"])

    pp = max(cfg.pp, 1)
    aux = ()
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

    return rms_norm(x, params["final_norm"].astype(dt), cfg.norm_eps), aux


def forward(
    params: Dict[str, Any],
    tokens: jnp.ndarray,
    cfg: TransformerConfig,
    mesh=None,
) -> jnp.ndarray:
    """tokens [B, S] int32 -> logits [B, S, V] (compute in cfg.dtype,
    logits in float32)."""
    x, _ = _hidden_states(params, tokens, cfg, mesh)
    return (x @ params["out"].astype(cfg.dtype)).astype(jnp.float32)


def loss_fn(
    params: Dict[str, Any],
    tokens: jnp.ndarray,
    cfg: TransformerConfig,
    mesh=None,
) -> jnp.ndarray:
    """The training loss: next-token cross entropy; position S-1 is
    unsupervised (targets are tokens shifted left; same [B, S] shape keeps
    sp sharding aligned). With dropless experts and a non-zero
    ``router_aux_loss_coef``, plus that times the load-balancing term."""
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
    layers of E·Σ_e f_e·P_e, before the coefficient) — what
    ``TrainStep`` keeps of its last step."""
    if max(cfg.pp, 1) > 1 and cfg.n_experts and cfg.router_aux_loss_coef:
        raise ValueError(
            f"pp={cfg.pp} with router_aux_loss_coef={cfg.router_aux_loss_coef}: "
            "the load-balancing term is not carried across pipeline stages, "
            "and is refused rather than dropped; set the coefficient to 0 or pp=1"
        )
    if max(cfg.pp, 1) > 1 and mesh is not None:
        # pipelined training path: the head (final norm + unembed + NLL)
        # runs inside the pipeline's manual region on the last stage and
        # only SCALAR reductions cross the pp axis — the replicate-the-
        # activations psum the plain forward() pays is for logits
        # consumers, not the training loop
        return _pipelined_loss(params, tokens, cfg, mesh), {}
    x, aux = _hidden_states(params, tokens, cfg, mesh)
    ce = _cross_entropy(params, x, tokens, cfg, mesh)
    if not aux:
        return ce, {}
    balance = jnp.mean(aux[0])
    stats = {"tokens_per_expert": aux[1], "balance_loss": balance}
    if cfg.router_aux_loss_coef:
        ce = ce + cfg.router_aux_loss_coef * balance
    return ce, stats


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
) -> jnp.ndarray:
    """Mean next-token cross entropy of final-norm hidden states ``x``."""
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
    if sp == 1 and _per_device_logit_elems(cfg, b, s, mesh) > _LOSS_CHUNK_ELEMS:
        return _chunked_loss(params, x, tokens, cfg, mesh)
    with jax.named_scope("head_loss"):
        logits = (x @ params["out"].astype(cfg.dtype)).astype(jnp.float32)
        targets = jnp.roll(tokens, -1, axis=1)
        logprobs = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logprobs, targets[..., None], axis=-1)[..., 0]
        mask = jnp.ones_like(nll).at[:, -1].set(0.0)
        return jnp.sum(nll * mask) / jnp.sum(mask)


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
) -> jnp.ndarray:
    """Cross entropy without materializing [B, S, V]: scan the unembed +
    softmax over sequence chunks (:func:`_chunked_nll`). Same numbers as
    the dense path (f32 log-sum-exp per position; accumulation order
    differs only in the final f32 sums)."""
    b, s = tokens.shape
    targets = jnp.roll(tokens, -1, axis=1)
    mask = jnp.ones((b, s), jnp.float32).at[:, -1].set(0.0)

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
    with jax.named_scope("head_loss"):
        return _chunked_nll(hs, params["out"], ts, ms)


def _chunk_nll(h_c, out_w, t_c, m_c):
    """One chunk's f32 logits, their log-sum-exp, and its masked NLL sum."""
    logits = h_c @ out_w
    # the target's logit is picked BEFORE the cast (the same number): picked
    # after it, XLA keeps an f32 copy of the chunk's logits for the gather
    target = jnp.take_along_axis(logits, t_c[..., None], axis=-1).astype(jnp.float32)
    logits = logits.astype(jnp.float32)
    mx = jnp.max(logits, axis=-1, keepdims=True)
    lse = jnp.log(jnp.sum(jnp.exp(logits - mx), axis=-1, keepdims=True)) + mx
    return logits, lse, jnp.sum((lse - target)[..., 0] * m_c)


@jax.custom_vjp
def _chunked_nll(hs, out, ts, ms):
    """Mean masked NLL of hidden-state chunks ``hs`` [n, B, c, d] through
    the unembed ``out`` [d, V] (cast to ``hs.dtype`` here, so its gradient
    arrives in ``out``'s own dtype). Called plainly it is the forward scan
    and nothing more. Differentiated (:func:`_chunked_nll_fwd`) the same
    scan also forms each chunk's ``softmax - onehot`` while its logits are
    on the chip and multiplies it into both gradients there: three matrix
    products a chunk, not the four of a backward that computes the logits
    again, and no logits kept."""
    out_w = out.astype(hs.dtype)

    def body(nll_sum, xt):
        h_c, t_c, m_c = xt
        return nll_sum + _chunk_nll(h_c, out_w, t_c, m_c)[2], None

    nll_sum, _ = jax.lax.scan(body, jnp.float32(0.0), (hs, ts, ms))
    return nll_sum / jnp.sum(ms)


def _chunked_nll_fwd(hs, out, ts, ms):
    out_w = out.astype(hs.dtype)
    cnt = jnp.sum(ms)

    def body(carry, xt):
        h_c, t_c, m_c = xt
        nll_sum, d_out = carry
        logits, lse, nll_c = _chunk_nll(h_c, out_w, t_c, m_c)
        onehot = jax.nn.one_hot(t_c, logits.shape[-1], dtype=logits.dtype)
        # in the dtype the transposed products of `(h_c @ out_w).astype(f32)`
        # read: that cast's cotangent is cast back to the compute dtype
        dlogits = ((jnp.exp(logits - lse) - onehot) * (m_c / cnt)[..., None]).astype(h_c.dtype)
        dh_c = jnp.einsum("bcv,dv->bcd", dlogits, out_w)
        # summed over the chunks in f32, so `d out` is rounded once and not
        # once a chunk: 1.2 ms of a 67 ms head at b8 x s2048 on a v5e
        d_out = d_out + jnp.einsum(
            "bcd,bcv->dv", h_c, dlogits, preferred_element_type=jnp.float32
        )
        return (nll_sum + nll_c, d_out), dh_c

    (nll_sum, d_out), dhs = jax.lax.scan(
        body, (jnp.float32(0.0), jnp.zeros(out.shape, jnp.float32)), (hs, ts, ms)
    )
    return nll_sum / cnt, (dhs, d_out.astype(out.dtype))


def _chunked_nll_bwd(res, g):
    dhs, d_out = res
    with jax.named_scope("head_loss"):
        return (g * dhs).astype(dhs.dtype), (g * d_out).astype(d_out.dtype), None, None


_chunked_nll.defvjp(_chunked_nll_fwd, _chunked_nll_bwd)


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
    layers = jax.tree_util.tree_map(lambda a: a.astype(dt), params["layers"])

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
        h = rms_norm(outs, hp["final_norm"], cfg.norm_eps)
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
