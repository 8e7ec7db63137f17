"""Inside ``embed``, ``attn`` and ``moe`` of a model trained by block diffusion
— 2·S rows ``[noised ; clean]`` a sequence under the staircase mask, experts
held under a share: the TABLE the seven readers of such a configuration share,
and the two functions that read it.

The program nests (``torchft_tpu/models/transformer.py``) under ``embed`` the
scope ``noise`` (the checksum, the draw of t and m, the noised ids and the
concatenation); under ``attn`` the scope ``blockdiff`` — the kind of a softmax
layer over the 2·S rows: projections, the q / k norms, the rotation by
positions that repeat, the core, the output projection — with the staircase
core ``blockdiff_core`` inside (the kernel's forward and backward calls); and
under ``moe`` the four parts ``router``, ``dispatch``, ``experts`` and
``combine`` on 2·S rows a sequence. ``subscopes.py`` splits a top-level scope's
device seconds among such names; a row of :data:`SPLITS` says which split a
reader takes and which part of it (None: everything but ``experts``, as
``moe_scopes.route_seconds``), :data:`ROOFLINES` which reader's seconds a share
is taken over and which functions of the configuration's count
(``opcounts/sdar_moe.py``) give its least operations and bytes.

A program without the scopes (the parent of the PR that brought them, another
architecture), a count without the functions or a trace without the ops gives
``None``, never an error.
"""

from __future__ import annotations

from typing import Optional

import moe_scopes
import opcount
import subscopes

# reader -> (top-level scope, the names it is split among, the part the reader takes)
SPLITS = {
    "diffusion_noise_device_s": ("embed", ("noise",), "noise"),
    "attn_blockdiff_device_s": ("attn", ("blockdiff",), "blockdiff"),
    "blockdiff_core_device_s": ("attn", ("blockdiff_core",), "blockdiff_core"),
    "moe_held16_experts_device_s": (moe_scopes.MOE, moe_scopes.SUBSCOPES, "experts"),
    "moe_held16_route_device_s": (moe_scopes.MOE, moe_scopes.SUBSCOPES, None),
}
# share of a roofline -> the reader whose seconds it is taken over
ROOFLINES = {
    "blockdiff_core_roofline": "blockdiff_core_device_s",
    "moe_held16_experts_roofline": "moe_held16_experts_device_s",
}


def seconds(run, reader: str) -> Optional[float]:
    """Device seconds a step of what ``reader`` names in :data:`SPLITS`."""
    parent, names, part = SPLITS[reader]
    by = subscopes.seconds(run, parent, names)
    if not by:
        return None
    if part is None:  # everything of the scope that is not the experts' grouped matmuls
        return sum(sec for sub, sec in by.items() if sub != "experts")
    return by.get(part)


def _least(run, reader: str):
    """(operations, bytes) a step that the count gives what ``reader`` names, or None."""
    counts = opcount.for_config(run.config)
    tc = run.config["program"]["transformer_config"]
    batch, seq = int(run.traffic["batch"]), int(run.traffic["seq"])
    part = SPLITS[reader][2]
    if part == "experts" and hasattr(counts, "experts_flops_per_token"):
        return counts.experts_flops_per_token(tc) * batch * seq, counts.experts_bytes_per_step(tc, batch, seq)
    if hasattr(counts, "core_flops_per_token"):
        flops, moved = counts.core_flops_per_token(tc, seq), counts.core_bytes_per_step(tc, batch, seq)
        if part in flops and part in moved:
            return flops[part] * batch * seq, moved[part]
    return None


def roofline(run, name: str) -> Optional[float]:
    """The least time the published peaks allow what ``name`` names in
    :data:`ROOFLINES` a step — its counted operations (on LIVE pairs, for the
    core) over the bf16 peak or its counted bytes over the HBM bandwidth,
    whichever is longer — as a share of its device seconds. Never clamped."""
    reader = ROOFLINES[name]
    ran = seconds(run, reader)
    if run.peaks is None or not ran:
        return None
    least = _least(run, reader)
    if least is None:
        return None
    needs = max(least[0] / run.peaks["bf16_flops_per_s"], least[1] / run.peaks["hbm_bytes_per_s"])
    chips = int(run.config["layout"]["chips_per_group"])
    return 100.0 * needs / (ran * chips)
