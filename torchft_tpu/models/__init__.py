"""Model families for the TPU-native framework.

The reference wraps user-supplied torch models (CIFAR CNN in train_ddp.py,
nn.Linear toys in tests); here the framework owns a mesh-aware model stack.
``transformer`` is the flagship: a decoder-only LM with dp/fsdp/pp/sp/tp/ep
shardings, dense or MoE FFNs, RoPE, RMSNorm and ring attention.
``resnet`` is the conv family (ResNet-18 CIFAR variant, NHWC, functional
batch norm) for the reference's "ResNet-18 CIFAR-10 DDP" config.
"""

from torchft_tpu.models import resnet
from torchft_tpu.models.transformer import (
    TransformerConfig,
    init_params,
    loss_fn,
    forward,
    param_specs,
)

__all__ = [
    "TransformerConfig",
    "init_params",
    "loss_fn",
    "forward",
    "param_specs",
    "resnet",
]
