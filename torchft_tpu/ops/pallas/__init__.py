"""Pallas TPU kernels for the hot ops.

XLA fuses elementwise chains into matmuls on its own; these kernels cover
what it can't — fusion *across* the attention softmax (flash attention's
O(S) memory recurrence), and a recurrence whose every step is a dozen small
dependent products (``kda``: the chunked gated delta rule, a head's state
and a chunk's matrices kept in VMEM; reached through ``ops/kda.kda_chunked``).
CPU tests run the same kernels in interpreter mode.
"""

from torchft_tpu.ops.pallas.flash_attention import flash_attention

__all__ = ["flash_attention"]
