"""Inside ``attn`` of a model whose softmax layers come in two kinds: what the
readers of a configuration with ``window`` and ``global`` layers share.

The program nests (``torchft_tpu/models/transformer.py``, a model that declares
a band or grouped heads) under ``attn`` a scope per kind — ``window`` (a band of
keys) and ``global`` (the whole prefix) — and inside each the causal softmax
core, ``window_core`` and ``global_core``: the kernel's calls under that kind.
``subscopes.py`` splits ``attn``'s device seconds among such names (the
innermost on an op's path wins), so two splits serve the six readers.

A core's least work comes from the configuration's count
(``opcount.for_config``) where that has ``core_flops_per_token`` and
``core_bytes_per_step`` with the core's name (``opcounts/laguna.py``); a count
without them, a program without the scopes (the parent of the PR that brought
them) or a trace without them gives ``None``, never an error.
"""

from __future__ import annotations

from typing import Optional

import opcount
import subscopes

KINDS = ("window", "global")
CORES = ("window_core", "global_core")


def _part(run, names, part: str) -> Optional[float]:
    by = subscopes.seconds(run, "attn", names)
    return by.get(part) if by else None


def kind_seconds(run, kind: str) -> Optional[float]:
    """Device seconds a step in the layers of one kind: projections, rotation, core."""
    return _part(run, KINDS, kind)


def core_seconds(run, core: str) -> Optional[float]:
    return _part(run, CORES, core)


def core_roofline(run, core: str) -> Optional[float]:
    """The least time the published peaks allow a core a step — its counted
    operations over the bf16 peak or its bytes at its own edge over the HBM
    bandwidth, whichever is longer — as a share of its device seconds."""
    seconds = core_seconds(run, core)
    counts = opcount.for_config(run.config)
    if run.peaks is None or not seconds or not hasattr(counts, "core_flops_per_token"):
        return None
    tc = run.config["program"]["transformer_config"]
    batch, seq = int(run.traffic["batch"]), int(run.traffic["seq"])
    flops, moved = counts.core_flops_per_token(tc, seq), counts.core_bytes_per_step(tc, batch, seq)
    if core not in flops or core not in moved:
        return None
    least = max(
        flops[core] * batch * seq / run.peaks["bf16_flops_per_s"],
        moved[core] / run.peaks["hbm_bytes_per_s"],
    )
    chips = int(run.config["layout"]["chips_per_group"])
    return 100.0 * least / (seconds * chips)
