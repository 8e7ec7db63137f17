"""Deterministic heal-stripe planning (docs/heal_plane.md).

The striped multi-source heal treats the flattened state tree as ONE
logical byte stream (header excluded — it rides the control plane) and
partitions it into byte-balanced ranges served by different live peers.
Because the unit is a *byte range* of the concatenation, not a whole
leaf, the plan is balanced to the alignment quantum by construction —
the old chunk assignment (:func:`assign_chunk_groups`, greedy LPT over
whole buffers) can still leave one chunk carrying most of the bytes when
a single large leaf (an embedding table, a fused optimizer moment)
dominates the tree, and the heal tail is gated by the slowest stripe.

Both sides derive the same plan from the same inputs (total size, source
count, knobs), so no stripe coordination rides the wire: the healer puts
the concrete ``(offset, len)`` in each range request and any source can
serve any range.
"""

from __future__ import annotations

import os
from typing import Iterator, List, Sequence, Tuple

import numpy as np

__all__ = [
    "stripe_ranges",
    "slice_buffers",
    "assign_chunk_groups",
    "heal_sources_limit",
    "heal_stripes_per_source",
]

# align range boundaries down to this quantum so fetches land on cache-
# friendly offsets; the tail range absorbs the remainder
_ALIGN = 64


def heal_sources_limit() -> int:
    """Max peers a healer stripes over (``TORCHFT_HEAL_SOURCES``, default
    4; 1 disables multi-source)."""
    try:
        return max(1, int(os.environ.get("TORCHFT_HEAL_SOURCES", "4")))
    except ValueError:
        return 4


# one range, and so one stream, for about this many bytes of a source's
# share: a stream moves 1.4-1.7 GB/s over the blob plane on the v5e host,
# so a range is under a second of work and a multi-GB heal gets several
_BYTES_A_STRIPE = 1 << 30


def heal_stripes_per_source(bytes_a_source: int = 0) -> int:
    """Ranges per source, each fetched on a stream of its own: one per
    ``_BYTES_A_STRIPE`` of the bytes a source is to serve, at most the
    cores this process sees and at least two — more ranges than sources
    keeps the tail short and makes re-striping after a source death cheap
    (only the dead source's pending ranges move).
    ``TORCHFT_HEAL_STRIPES`` set raises that floor of two (or lowers it
    to one)."""
    try:
        floor = max(1, int(os.environ.get("TORCHFT_HEAL_STRIPES", "2")))
    except ValueError:
        floor = 2
    derived = min(
        len(os.sched_getaffinity(0)), -(-bytes_a_source // _BYTES_A_STRIPE)
    )
    return max(floor, derived)


def stripe_ranges(total_bytes: int, n: int) -> List[Tuple[int, int]]:
    """Partition ``[0, total_bytes)`` into ``n`` contiguous byte ranges,
    balanced to within the alignment quantum (the tail absorbs the
    remainder). Deterministic; empty ranges are dropped (tiny blobs may
    yield fewer than ``n``)."""
    if total_bytes <= 0:
        return []
    n = max(1, n)
    bounds = [((total_bytes * i // n) // _ALIGN) * _ALIGN for i in range(n)]
    bounds.append(total_bytes)
    out: List[Tuple[int, int]] = []
    for i in range(n):
        length = bounds[i + 1] - bounds[i]
        if length > 0:
            out.append((bounds[i], length))
    return out


def slice_buffers(
    buffers: Sequence[np.ndarray],
    sizes: Sequence[int],
    offset: int,
    length: int,
) -> Iterator[memoryview]:
    """Yield the byte slices of the logical buffer concatenation covering
    ``[offset, offset+length)`` — the HTTP serving side of a range request
    (the native blob server walks the same layout in C++). ``sizes[i]``
    must be ``buffers[i]``'s byte length."""
    from torchft_tpu.checkpointing.serialization import as_bytes

    pos = 0
    remaining = length
    for buf, size in zip(buffers, sizes):
        if remaining <= 0:
            return
        end = pos + size
        if end > offset and size > 0:
            lo = max(0, offset - pos)
            hi = min(size, offset + length - pos)
            if hi > lo:
                yield as_bytes(buf)[lo:hi]
                remaining -= hi - lo
        pos = end


def assign_chunk_groups(sizes: List[int], num_chunks: int) -> List[List[int]]:
    """Greedy LPT size-balanced assignment of whole-buffer indices to
    chunks — the legacy ``num_chunks`` HTTP mode's grouping (kept for the
    chunked endpoint; the striped heal path uses :func:`stripe_ranges`,
    which splits large leaves across stripes and balances exactly)."""
    order = sorted(range(len(sizes)), key=lambda i: -sizes[i])
    totals = [0] * num_chunks
    groups: List[List[int]] = [[] for _ in range(num_chunks)]
    for i in order:
        c = totals.index(min(totals))
        groups[c].append(i)
        totals[c] += sizes[i]
    for g in groups:
        g.sort()  # stream each chunk's buffers in deterministic order
    return groups
