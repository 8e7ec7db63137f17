"""ResNet-18 (CIFAR variant) — the conv model family.

The reference's flagship real-data config is "ResNet-18 CIFAR-10 DDP with
kill/rejoin" (reference train_ddp.py:34-80 trains
it through torchvision). TPU-native rebuild: pure-JAX pytree params in
NHWC layout (the TPU conv-friendly layout — XLA lowers NHWC convs onto
the MXU without transposes), functional batch norm whose running stats
travel as explicit state (flax-style ``(params, batch_stats)``; torch's
module mutation has no JAX analogue), bf16 compute with f32 statistics.

DDP semantics match torch DDP: gradients average across replica groups;
batch-norm *running stats* stay local per group and ride the heal/disk
checkpoint state dict instead (torch DDP does not sync BN either —
broadcast-at-init + local updates).

CIFAR stem: 3×3 conv stride 1, no max-pool (the standard CIFAR ResNet-18
adaptation); stages [2,2,2,2] × channels [64,128,256,512].
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

__all__ = ["ResNetConfig", "init", "apply", "loss_fn"]

_DN = ("NHWC", "HWIO", "NHWC")  # lax conv dimension numbers


@dataclasses.dataclass(frozen=True)
class ResNetConfig:
    num_classes: int = 10
    channels: Tuple[int, ...] = (64, 128, 256, 512)
    blocks_per_stage: Tuple[int, ...] = (2, 2, 2, 2)  # resnet-18
    bn_momentum: float = 0.9  # running = m*running + (1-m)*batch
    bn_eps: float = 1e-5
    dtype: Any = jnp.bfloat16  # compute dtype; stats/params stay f32


def _conv_init(key, kh, kw, cin, cout):
    # He/Kaiming normal (fan_out, relu) — the torchvision resnet init
    std = (2.0 / (kh * kw * cout)) ** 0.5
    return jax.random.normal(key, (kh, kw, cin, cout), jnp.float32) * std


def _bn_init(c):
    return {
        "scale": jnp.ones((c,), jnp.float32),
        "bias": jnp.zeros((c,), jnp.float32),
    }


def _bn_state(c):
    return {
        "mean": jnp.zeros((c,), jnp.float32),
        "var": jnp.ones((c,), jnp.float32),
    }


def init(rng, cfg: ResNetConfig = ResNetConfig()) -> Tuple[Dict, Dict]:
    """Returns ``(params, batch_stats)`` pytrees (both f32)."""
    n_convs = 2 + sum(cfg.blocks_per_stage) * 3  # stem + per-block worst case
    keys = iter(jax.random.split(rng, n_convs * 2 + 2))

    params: Dict[str, Any] = {
        "stem": {"conv": _conv_init(next(keys), 3, 3, 3, cfg.channels[0]),
                 "bn": _bn_init(cfg.channels[0])},
    }
    stats: Dict[str, Any] = {"stem": {"bn": _bn_state(cfg.channels[0])}}

    cin = cfg.channels[0]
    for s, (cout, n_blocks) in enumerate(
        zip(cfg.channels, cfg.blocks_per_stage)
    ):
        blocks = []
        blocks_stats = []
        for b in range(n_blocks):
            stride = 2 if (s > 0 and b == 0) else 1
            blk = {
                "conv1": _conv_init(next(keys), 3, 3, cin, cout),
                "bn1": _bn_init(cout),
                "conv2": _conv_init(next(keys), 3, 3, cout, cout),
                # zero-init the residual's last BN scale (torchvision
                # zero_init_residual improves early training)
                "bn2": {**_bn_init(cout), "scale": jnp.zeros((cout,), jnp.float32)},
            }
            st = {"bn1": _bn_state(cout), "bn2": _bn_state(cout)}
            if stride != 1 or cin != cout:
                blk["down_conv"] = _conv_init(next(keys), 1, 1, cin, cout)
                blk["down_bn"] = _bn_init(cout)
                st["down_bn"] = _bn_state(cout)
            blocks.append(blk)
            blocks_stats.append(st)
            cin = cout
        params[f"stage{s}"] = blocks
        stats[f"stage{s}"] = blocks_stats

    params["fc"] = {
        "w": jax.random.normal(
            next(keys), (cfg.channels[-1], cfg.num_classes), jnp.float32
        )
        * (cfg.channels[-1] ** -0.5),
        "b": jnp.zeros((cfg.num_classes,), jnp.float32),
    }
    return params, stats


def _batch_norm(x, p, st, cfg: ResNetConfig, train: bool):
    """Returns (normalized x, new state). Stats compute in f32 regardless
    of the bf16 activations (small-batch variance in bf16 is garbage)."""
    if train:
        xf = x.astype(jnp.float32)
        mean = jnp.mean(xf, axis=(0, 1, 2))
        var = jnp.var(xf, axis=(0, 1, 2))
        m = cfg.bn_momentum
        new_st = {
            "mean": m * st["mean"] + (1.0 - m) * mean,
            "var": m * st["var"] + (1.0 - m) * var,
        }
    else:
        mean, var = st["mean"], st["var"]
        new_st = st
    inv = jax.lax.rsqrt(var + cfg.bn_eps) * p["scale"]
    out = (x.astype(jnp.float32) - mean) * inv + p["bias"]
    return out.astype(x.dtype), new_st


def _block(x, blk, st, cfg: ResNetConfig, stride: int, train: bool):
    new_st = dict(st)
    y = jax.lax.conv_general_dilated(
        x, blk["conv1"].astype(x.dtype), (stride, stride), "SAME",
        dimension_numbers=_DN,
    )
    y, new_st["bn1"] = _batch_norm(y, blk["bn1"], st["bn1"], cfg, train)
    y = jax.nn.relu(y)
    y = jax.lax.conv_general_dilated(
        y, blk["conv2"].astype(x.dtype), (1, 1), "SAME", dimension_numbers=_DN
    )
    y, new_st["bn2"] = _batch_norm(y, blk["bn2"], st["bn2"], cfg, train)

    if "down_conv" in blk:
        x = jax.lax.conv_general_dilated(
            x, blk["down_conv"].astype(x.dtype), (stride, stride), "SAME",
            dimension_numbers=_DN,
        )
        x, new_st["down_bn"] = _batch_norm(
            x, blk["down_bn"], st["down_bn"], cfg, train
        )
    return jax.nn.relu(y + x), new_st


def apply(
    params: Dict,
    stats: Dict,
    images: jnp.ndarray,
    cfg: ResNetConfig = ResNetConfig(),
    train: bool = True,
) -> Tuple[jnp.ndarray, Dict]:
    """``images`` [B, 32, 32, 3] (NHWC, any float dtype) → (logits f32,
    new batch_stats). Pass ``train=False`` to use running stats."""
    x = images.astype(cfg.dtype)
    new_stats: Dict[str, Any] = {"stem": {}}
    x = jax.lax.conv_general_dilated(
        x, params["stem"]["conv"].astype(x.dtype), (1, 1), "SAME",
        dimension_numbers=_DN,
    )
    x, new_stats["stem"]["bn"] = _batch_norm(
        x, params["stem"]["bn"], stats["stem"]["bn"], cfg, train
    )
    x = jax.nn.relu(x)

    for s in range(len(cfg.channels)):
        blocks = params[f"stage{s}"]
        new_blocks = []
        for b, blk in enumerate(blocks):
            stride = 2 if (s > 0 and b == 0) else 1
            x, st = _block(x, blk, stats[f"stage{s}"][b], cfg, stride, train)
            new_blocks.append(st)
        new_stats[f"stage{s}"] = new_blocks

    x = jnp.mean(x.astype(jnp.float32), axis=(1, 2))  # global avg pool
    logits = x @ params["fc"]["w"] + params["fc"]["b"]
    return logits, new_stats


def loss_fn(
    params: Dict,
    stats: Dict,
    images: jnp.ndarray,
    labels: jnp.ndarray,
    cfg: ResNetConfig = ResNetConfig(),
) -> Tuple[jnp.ndarray, Dict]:
    """Mean cross-entropy; returns ``(loss, new_batch_stats)`` — pair with
    ``jax.value_and_grad(..., has_aux=True)``."""
    logits, new_stats = apply(params, stats, images, cfg, train=True)
    logp = jax.nn.log_softmax(logits)
    nll = -jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(nll), new_stats
