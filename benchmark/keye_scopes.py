"""Inside ``attn`` of a model whose grouped-query cores run behind a learned
key selector (``sparse_topk`` > 0): the TABLE the seven readers of such a
configuration share, and the two functions that read it.

The program nests (``torchft_tpu/models/transformer.py``) under ``attn`` the
scope ``sparse`` — the kind of a softmax layer behind its selector:
projections, the q / k norms, the rotation, everything below, the output
projection — and inside it ``indexer`` (the selector's three projections from
the layer's normed input behind a ``stop_gradient``, their rotation, and the
scores ``I`` a q block at a time), ``select`` (a query's threshold — its k-th
largest causal score — and the int8 table of selected pairs), ``sparse_core``
(the flash kernel's forward and backward calls under the table) and
``indexer_loss`` (the target p, the KL term, and the selector's closed-form
gradient, which the term's forward pass emits). ``subscopes.py`` splits a
top-level scope's device seconds among such names; a row of :data:`SPLITS`
says which split a reader takes and which part of it, :data:`ROOFLINES` which
reader's seconds a share is taken over and which part of the configuration's
count (``opcounts/keye_sparse.py``: the core on SELECTED pairs) gives its least
operations and bytes. The held experts are read by the metrics every expert
cell reports (``ffn_device_s``, ``ffn_roofline``).

A program without the scopes (the parent of the PR that brought them, another
architecture), a count without the functions or a trace without the ops gives
``None``, never an error.
"""

from __future__ import annotations

from typing import Optional

import opcount
import subscopes

# reader -> (top-level scope, the names it is split among, the part the reader takes)
SPLITS = {
    "attn_sparse_device_s": ("attn", ("sparse",), "sparse"),
    "indexer_device_s": ("attn", ("indexer", "select", "sparse_core", "indexer_loss"), "indexer"),
    "select_device_s": ("attn", ("indexer", "select", "sparse_core", "indexer_loss"), "select"),
    "sparse_core_device_s": ("attn", ("indexer", "select", "sparse_core", "indexer_loss"), "sparse_core"),
    "indexer_loss_device_s": ("attn", ("indexer", "select", "sparse_core", "indexer_loss"), "indexer_loss"),
}
# share of a roofline -> the reader whose seconds it is taken over
ROOFLINES = {
    "sparse_core_roofline": "sparse_core_device_s",
    "indexer_loss_roofline": "indexer_loss_device_s",
}


def seconds(run, reader: str) -> Optional[float]:
    """Device seconds a step of what ``reader`` names in :data:`SPLITS`."""
    parent, names, part = SPLITS[reader]
    by = subscopes.seconds(run, parent, names)
    return by.get(part) if by else None


def _least(run, reader: str):
    """(operations, bytes) a step that the count gives what ``reader`` names, or None."""
    counts = opcount.for_config(run.config)
    if not hasattr(counts, "core_flops_per_token"):
        return None
    tc = run.config["program"]["transformer_config"]
    batch, seq = int(run.traffic["batch"]), int(run.traffic["seq"])
    part = SPLITS[reader][2]
    flops, moved = counts.core_flops_per_token(tc, seq), counts.core_bytes_per_step(tc, batch, seq)
    if part in flops and part in moved:
        return flops[part] * batch * seq, moved[part]
    return None


def roofline(run, name: str) -> Optional[float]:
    """The least time the published peaks allow what ``name`` names in
    :data:`ROOFLINES` a step — its counted operations (on SELECTED pairs) over
    the bf16 peak or its counted bytes over the HBM bandwidth, whichever is
    longer — as a share of its device seconds. Never clamped."""
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
