"""Inside ``attn`` of a hybrid of Mamba-2 state-space mixers and unrotated
grouped-query softmax layers: the TABLE the eight readers of such a
configuration share, and the two functions that read it.

The program nests (``torchft_tpu/models/transformer.py``) under ``attn`` a
scope per kind of mixer — ``ssd`` (the state-space mixer, and inside it
``ssd_in`` and ``ssd_out`` the two projections, ``conv`` the short convolution
with its bias and SiLU, ``gates`` the step and the log-decay, ``ssd_core`` the
chunked rule, ``gated_norm`` the skip, the gate and the norm over all channels)
and ``global`` (the softmax layer without positions: projections, and
``global_core`` the causal core). ``subscopes.py`` splits a top-level scope's
device seconds among such names (the innermost on an op's path wins); a row of
:data:`SPLITS` says which split a reader takes and which of its parts it adds up.

A core's least work comes from the configuration's count
(``opcount.for_config``) where that has ``core_flops_per_token`` and
``core_bytes_per_step`` with the core's name (``opcounts/granite_hybrid.py``); a
count without them, a program without the scopes (the parent of the PR that
brought them, another architecture) or a trace without them gives ``None``,
never an error.
"""

from __future__ import annotations

from typing import Optional

import opcount
import subscopes

MIXERS = ("ssd", "global")
PARTS = ("ssd_in", "ssd_out", "conv", "gates", "ssd_core", "gated_norm", "global_core")

# reader -> (the names ``attn`` is split among, the parts the reader adds up)
SPLITS = {
    "attn_ssd_device_s": (MIXERS, ("ssd",)),
    "attn_nope64_device_s": (MIXERS, ("global",)),
    "ssd_core_device_s": (PARTS, ("ssd_core",)),
    "ssd_proj_device_s": (PARTS, ("ssd_in", "ssd_out")),
    "ssd_conv_gate_device_s": (PARTS, ("conv", "gates", "gated_norm")),
    "nope64_core_device_s": (PARTS, ("global_core",)),
}
# share of a roofline -> (the reader whose seconds it is taken over, the core's name in the count)
ROOFLINES = {
    "ssd_core_roofline": ("ssd_core_device_s", "ssd_core"),
    "nope64_core_roofline": ("nope64_core_device_s", "global_core"),
}


def seconds(run, reader: str) -> Optional[float]:
    """Device seconds a step of what ``reader`` names in :data:`SPLITS`;
    ``None`` where the trace has no state-space mixer (``attn/ssd``: another
    architecture's ``global`` layers are not this table's to read) or lacks
    the reader's first part."""
    names, parts = SPLITS[reader]
    by = subscopes.seconds(run, "attn", names)
    if not by or parts[0] not in by or not (subscopes.seconds(run, "attn", MIXERS) or {}).get("ssd"):
        return None
    return sum(by.get(part, 0.0) for part in parts)


def core_roofline(run, reader: str) -> Optional[float]:
    """The least time the published peaks allow the core that ``reader`` names
    in :data:`ROOFLINES` a step — its counted operations over the bf16 peak or
    its bytes at its own edge over the HBM bandwidth, whichever is longer — as
    a share of its device seconds. Never clamped."""
    timed, core = ROOFLINES[reader]
    spent = seconds(run, timed)
    counts = opcount.for_config(run.config)
    if run.peaks is None or not spent or not hasattr(counts, "core_flops_per_token"):
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
    return 100.0 * least / (spent * chips)
