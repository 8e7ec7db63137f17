"""Inside ``attn`` and ``moe`` of a hybrid model: what the readers of a
configuration with two kinds of sequence mixing and a shared expert share.

The program nests (``torchft_tpu/models/transformer.py``) under ``attn`` a
scope per kind of mixer — ``kda`` (gated delta-rule linear attention; inside
it ``conv``, ``gates`` and ``kda_core``, the chunked recurrence) and ``mla``
(latent attention; inside it ``mla_core``, the causal softmax core) — and
under ``moe`` beside ``router``, ``dispatch``, ``experts`` and ``combine`` a
``shared`` expert. ``subscopes.py`` splits a top-level scope's device seconds
among such names (the innermost on an op's path wins), so three splits serve
the eight readers: ``attn`` by kind of mixer, ``attn`` by core, ``moe`` by part.

A core's least work comes from the configuration's count
(``opcount.for_config``) where that has ``core_flops_per_token`` and
``core_bytes_per_step`` (``opcounts/kimi_linear.py``); a count without them,
a program without the scopes or a trace without them gives ``None``.
"""

from __future__ import annotations

from typing import Dict, Optional

import opcount
import subscopes

MIXERS = ("kda", "mla")
CORES = ("kda_core", "mla_core")
MOE_PARTS = ("router", "dispatch", "experts", "combine", "shared")
ROUTED = ("router", "dispatch", "experts", "combine", "moe")  # all of ``moe`` but the shared expert


def _part(run, parent: str, names, part: str) -> Optional[float]:
    by = subscopes.seconds(run, parent, names)
    return by.get(part) if by else None


def mixer_seconds(run, mixer: str) -> Optional[float]:
    """Device seconds a step in the layers' ``kda`` or ``mla`` mixers."""
    return _part(run, "attn", MIXERS, mixer)


def core_seconds(run, core: str) -> Optional[float]:
    return _part(run, "attn", CORES, core)


def shared_seconds(run) -> Optional[float]:
    return _part(run, "moe", MOE_PARTS, "shared")


def routed_seconds(run) -> Optional[float]:
    """Router, dispatch, the routed experts, combine and what of ``moe``
    names none; None where the program has no ``shared`` scope to tell apart."""
    by: Optional[Dict[str, float]] = subscopes.seconds(run, "moe", MOE_PARTS)
    if not by or "shared" not in by:
        return None
    return sum(by.get(part, 0.0) for part in ROUTED)


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
    least = max(
        counts.core_flops_per_token(tc, seq)[core] * batch * seq / run.peaks["bf16_flops_per_s"],
        counts.core_bytes_per_step(tc, batch, seq)[core] / run.peaks["hbm_bytes_per_s"],
    )
    chips = int(run.config["layout"]["chips_per_group"])
    return 100.0 * least / (seconds * chips)
