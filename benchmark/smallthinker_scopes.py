"""Inside ``attn`` and ``moe`` of a model whose global softmax layers have no
positions, whose window layers are rotated under a band of 4096, and whose
ReLU-gated experts take a gate computed AHEAD of the mixer: the TABLE the nine
readers of such a configuration share, and the two functions that read it.

The program nests (``torchft_tpu/models/transformer.py``) under ``attn`` a
scope per kind of softmax layer — ``global`` (here the kind without positions:
its ``attention_path`` line says ``rotary_dim`` 0) and ``window`` — with the
causal core inside each, ``global_core`` and ``window_core``; and under ``moe``
the four parts ``router`` (under ``router_input`` "layer" it runs ahead of the
mixer, in a checkpoint of its own, and stays the ``moe`` scope's), ``dispatch``,
``experts`` (the three grouped matmuls and the ReLU) and ``combine``. Nothing
is read here that ``window_scopes.py`` (the kinds and the cores of ``attn``, a
core's share of its roofline) and ``moe_scopes.py`` (the parts of ``moe``, the
experts' share) do not already read: a row of :data:`SPLITS` says which split a
reader takes and which part of it, :data:`ROOFLINES` which reader's seconds a
share is taken over.

A program without the scopes (the parent of the PR that brought them, another
architecture), a count without the cores or a trace without them gives
``None``, never an error.
"""

from __future__ import annotations

from typing import Optional

import moe_scopes
import subscopes
import window_scopes

# reader -> (top-level scope, the names it is split among, the part the reader takes)
SPLITS = {
    "attn_nope_global_device_s": ("attn", window_scopes.KINDS, "global"),
    "attn_window4k_device_s": ("attn", window_scopes.KINDS, "window"),
    "nope_global_core_device_s": ("attn", window_scopes.CORES, "global_core"),
    "window4k_core_device_s": ("attn", window_scopes.CORES, "window_core"),
    "moe_early_router_device_s": (moe_scopes.MOE, moe_scopes.SUBSCOPES, "router"),
    "moe_reglu_experts_device_s": (moe_scopes.MOE, moe_scopes.SUBSCOPES, "experts"),
}
# share of a roofline -> the reader whose seconds it is taken over
ROOFLINES = {
    "nope_global_core_roofline": "nope_global_core_device_s",
    "window4k_core_roofline": "window4k_core_device_s",
    "moe_reglu_experts_roofline": "moe_reglu_experts_device_s",
}


def seconds(run, reader: str) -> Optional[float]:
    """Device seconds a step of what ``reader`` names in :data:`SPLITS`."""
    parent, names, part = SPLITS[reader]
    by = subscopes.seconds(run, parent, names)
    return by.get(part) if by else None


def roofline(run, reader: str) -> Optional[float]:
    """The least time the published peaks allow what ``reader`` names in
    :data:`ROOFLINES` a step, as a share of its device seconds: a core's by
    ``window_scopes.core_roofline`` (the configuration's count of the core's
    operations and of the bytes at its own edge), the experts' by
    ``moe_scopes.experts_roofline`` (the count's ``moe`` less the router).
    Never clamped."""
    parent, _, part = SPLITS[ROOFLINES[reader]]
    if parent == moe_scopes.MOE:
        return moe_scopes.experts_roofline(run)
    return window_scopes.core_roofline(run, part)
