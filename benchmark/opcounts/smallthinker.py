"""The count of SmallThinker's decoder as the ``smallthinker-*`` configurations
run it — softmax layers of two kinds, ``window`` (a band of ``window`` keys,
rotated) and ``global`` (the whole prefix, here WITHOUT positions), 28 query
heads over 4 key/value heads in both, and in every layer a router over 64
experts of which a share is held, 6 chosen a token, no shared expert and no
dense layer: ``program.opcount`` ``smallthinker``, with ``opcount.INTERFACE``
and, for the readers of the two cores, :func:`core_flops_per_token` and
:func:`core_bytes_per_step` keyed ``window_core`` / ``global_core``.

**The arithmetic is ``opcounts/laguna.py``'s, and this file takes it from there
rather than keep a second copy**: a count is a function of shapes, and what
makes this architecture different from that one changes no shape the count
reads. A rotation (or none) is elementwise and not counted; a ReLU in place of
a SiLU is a compare; the router's ``d x E`` product is the same product whether
it reads the layer's input ahead of the mixer or the feed-forward's — it counts
to ``moe`` either way, since the program keeps it in scope ``moe/router``. What
is read from ``program.transformer_config``: ``d_model``, ``n_layers``,
``window_layers`` (every other layer is ``global``), ``window``, ``n_heads``,
``n_kv_heads``, ``head_dim``, ``moe_d_ff``, ``n_experts`` (the router's
width), ``n_experts_held``, ``top_k``, ``vocab_size`` (the rows HELD), ``dtype``.

At the cell's size (``smallthinker-21b-a3b-1g`` at b2 x s16384; worked by
hand in ``tests/test_smallthinker.py``):

* parameters held: a layer = attention 2 x 2560 x 3584 + 2 x 2560 x 512 =
  20 971 520, router 163 840, two norms 5 120, 8 experts x 3 x 2560 x 768 =
  47 185 920: 68 326 400; four layers, two tables of 18 992 x 2560 and the
  final norm: 370 547 200.
* keys a query: global (s + 1) / 2 = 8 192.5; the band's exact mean
  (4096 x 4097 / 2 + 12 288 x 4096) / 16 384 = 58 722 304 / 16 384 = 3 584.125.
* forward + backward operations a token: projections 6 x 4 x 20 971 520 =
  503.3 M; ``global_core`` 3 x 28 x 2 x 8 192.5 x 256 = 352.3 M; ``window_core``
  3 layers x 3 x 28 x 2 x 3 584.125 x 256 = 462.4 M; router 6 x 4 x 163 840 =
  3.9 M; held experts at 6 x 8 / 64 = 0.75 passes: 6 x 4 x 0.75 x 5 898 240 =
  106.2 M; head 6 x 2560 x 18 992 = 291.7 M: 1 719.8 M, 56.35 TFLOP a step of
  32 768 tokens, 0.286 s at the bf16 peak.
* a step's cores at the peak: ``global_core`` 11.54 TFLOP = 58.6 ms,
  ``window_core`` 15.15 TFLOP = 76.9 ms; their bytes at their own edge (q, k, v,
  o and their gradients) 1.6 and 4.8 GB = 2.0 and 5.9 ms: both bound by operations.
"""

from __future__ import annotations

import os

from common import load_module

_shapes = load_module(os.path.join(os.path.dirname(os.path.abspath(__file__)), "laguna.py"), "bench_opcount_laguna")

CORES = _shapes.CORES
layer_heads = _shapes.layer_heads
attn_params = _shapes.attn_params
expert_params = _shapes.expert_params
keys_per_query = _shapes.keys_per_query
n_params = _shapes.n_params
core_flops_per_token = _shapes.core_flops_per_token
core_bytes_per_step = _shapes.core_bytes_per_step
ffn_scopes = _shapes.ffn_scopes
flops_per_token_by_scope = _shapes.flops_per_token_by_scope
flops_per_token = _shapes.flops_per_token
bytes_per_step_by_scope = _shapes.bytes_per_step_by_scope
