"""Inside ``attn`` of a model whose every layer is a latent attention with
positions, and across the scopes of its multi-token-prediction module: what the
four readers of such a configuration share.

The program nests (``torchft_tpu/models/transformer.py``) under ``attn`` the
scope ``mla`` — inside it ``q_lora`` (the query's down-projection, norm and
up-projection), ``kv_lora`` (the key/value latent's), ``rope`` (the two
rotations) and ``mla_core``, the causal softmax core: the kernel's calls — and
gives every op of the module behind the stack the name ``mtp`` INSIDE its
top-level scope (``embed/mtp``: the two input norms and ``eh_proj``;
``attn/mtp``, ``moe/mtp`` or ``ffn/mtp``: its layer; ``head_loss/mtp``: its
final norm and its pass of the head). ``subscopes.py`` splits a top-level
scope's device seconds among such names (the innermost on an op's path wins).

The core's seconds and its share of its roofline are ``hybrid_scopes.py``'s
(``core_seconds`` / ``core_roofline`` of ``mla_core``, as ``kimi-linear-1g``'s
readers call them, over this configuration's count,
``opcounts/joyai_flash.py``): no second copy here. What this file adds is what
of ``attn/mla`` is NOT the core, and the module across its scopes. A program
without the scopes or a trace without them gives ``None``, never an error.
"""

from __future__ import annotations

from typing import Optional

import subscopes

CORE = "mla_core"
MODULE = "mtp"
MODULE_PARENTS = ("embed", "attn", "ffn", "moe", "head_loss")


def latent_seconds(run) -> Optional[float]:
    """Device seconds a step under ``attn/mla`` outside the core: the low-rank
    projections with their norms, the rotations, the output projection."""
    by = subscopes.seconds(run, "attn", ("mla", CORE))
    return by.get("mla") if by else None


def module_seconds(run) -> Optional[float]:
    """Device seconds a step of every op that carries the module's name,
    over the top-level scopes it nests in; None where no scope has one."""
    parts = [(subscopes.seconds(run, parent, (MODULE,)) or {}).get(MODULE) for parent in MODULE_PARENTS]
    found = [p for p in parts if p is not None]
    return sum(found) if found else None
