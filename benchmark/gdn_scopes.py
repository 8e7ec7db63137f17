"""Inside ``attn`` and ``moe`` of a hybrid of Gated DeltaNet and gated softmax
layers with a gated shared expert: the TABLE the eight readers of such a
configuration share, and the two functions that read it.

The program nests (``torchft_tpu/models/transformer.py``) under ``attn`` a
scope per kind of mixer — ``gdn`` (Gated DeltaNet: projections, and inside it
``conv``, ``gates`` and ``gdn_core``, the chunked delta rule with one decay a
head) and ``global`` (the softmax layer: projections, per-head norms, rotation,
``global_core`` the causal core, ``out_gate`` the gate on its output) — and
under ``moe`` beside ``router``, ``dispatch``, ``experts`` and ``combine`` a
``shared`` expert, its gate inside. ``subscopes.py`` splits a top-level
scope's device seconds among such names (the innermost on an op's path wins);
a row of :data:`SPLITS` says which split a reader takes and which of its parts
it adds up.

A core's least work comes from the configuration's count
(``opcount.for_config``) where that has ``core_flops_per_token`` and
``core_bytes_per_step`` with the core's name (``opcounts/qwen3_next.py``); a
count without them, a program without the scopes (the parent of the PR that
brought them, another architecture) or a trace without them gives ``None``,
never an error.
"""

from __future__ import annotations

from typing import Optional

import opcount
import subscopes

MIXERS = ("gdn", "global")
CORES = ("gdn_core", "global_core")
MOE_PARTS = ("router", "dispatch", "experts", "combine", "shared")

# reader -> (top-level scope, the names it is split among, the parts the reader adds up)
SPLITS = {
    "attn_gdn_device_s": ("attn", MIXERS, ("gdn",)),
    "attn_gated_softmax_device_s": ("attn", MIXERS, ("global",)),
    "gdn_core_device_s": ("attn", CORES, ("gdn_core",)),
    "gated_softmax_core_device_s": ("attn", CORES, ("global_core",)),
    "moe_shared_gated_device_s": ("moe", MOE_PARTS, ("shared",)),
    # all of ``moe`` but the shared expert: what names none of the parts (the layers' second norm) counts here
    "moe_routed_top10_device_s": ("moe", MOE_PARTS, ("router", "dispatch", "experts", "combine", "moe")),
}


def seconds(run, reader: str) -> Optional[float]:
    """Device seconds a step of what ``reader`` names in :data:`SPLITS`;
    ``None`` where the trace lacks the reader's own scope — under ``moe`` the
    shared expert's, without which the routed side cannot be told apart."""
    parent, names, parts = SPLITS[reader]
    by = subscopes.seconds(run, parent, names)
    if not by or ("shared" if parent == "moe" else parts[0]) not in by:
        return None
    return sum(by.get(part, 0.0) for part in parts)


def core_roofline(run, reader: str) -> Optional[float]:
    """The least time the published peaks allow the core that ``reader``
    times a step — its counted operations over the bf16 peak or its bytes at
    its own edge over the HBM bandwidth, whichever is longer — as a share of
    its device seconds. Never clamped."""
    spent = seconds(run, reader)
    counts = opcount.for_config(run.config)
    if run.peaks is None or not spent or not hasattr(counts, "core_flops_per_token"):
        return None
    core = SPLITS[reader][2][0]
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
