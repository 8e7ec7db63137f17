"""Differential heal — changed-leaf checkpoint deltas (docs/heal_plane.md).

A replica that was absent for a few steps usually still holds a bit-exact
copy of the committed state at its last committed step (the commit
protocol's cross-group bit-identity invariant — every committed step's
state is identical on every group, proven end-to-end by the fault
matrix). Shipping the whole tree again is waste: the serving side keeps a
bounded **commit trail** of per-leaf digests at recent committed steps,
and a healer that reports ``(last_step, tree_digest)`` receives only the
leaves whose digest changed since — falling back to a full heal when the
trail has no entry for that step (absence past the horizon), when the
digests disagree (the healer's copy is not the committed lineage), or
when the leaf layout changed.

Safety is digest-anchored end to end: a delta is only built when the
healer's whole-tree digest at ``last_step`` matches the trail's, and an
unchanged leaf is kept from the healer's own buffers only because its
digest matches the server's — a mismatch anywhere degrades to the full
path rather than risking a silently mixed state.

Wire shape of a delta response (one body)::

    u64 manifest_len | pickle(manifest) | changed raw buffers...

with ``manifest = {"mode": "delta", "header": bytes, "changed": [idx...],
"sizes": [nbytes...]}`` or ``{"mode": "full"}`` (no payload) when the
server declines.
"""

from __future__ import annotations

import hashlib
import os
import pickle
import struct
import threading
from collections import OrderedDict
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

from torchft_tpu.checkpointing.serialization import as_bytes

__all__ = [
    "leaf_digests",
    "digest_workers",
    "tree_digest",
    "CommitTrail",
    "diff_enabled",
    "trail_horizon",
    "build_delta",
    "apply_delta",
    "pack_delta",
    "unpack_delta",
]

_LEN = struct.Struct("<Q")


def diff_enabled() -> bool:
    """``TORCHFT_HEAL_DIFF=1`` opts into differential heal. Off by
    default: the trail costs one state flatten + digest per committed
    step on the serving side (see docs/heal_plane.md for when that is
    worth it)."""
    return os.environ.get("TORCHFT_HEAL_DIFF", "0") == "1"


def trail_horizon() -> int:
    """Trail depth in committed steps (``TORCHFT_HEAL_TRAIL``, default
    8): absences older than this fall back to a full heal."""
    try:
        return max(1, int(os.environ.get("TORCHFT_HEAL_TRAIL", "8")))
    except ValueError:
        return 8


def _digest(buf: np.ndarray) -> str:
    h = hashlib.blake2b(digest_size=8)
    h.update(as_bytes(buf))
    return h.hexdigest()


# a hashing thread is worth starting for this many bytes (blake2b runs at
# 0.4-0.65 GB/s a core on the v5e hosts, so this is ~10 ms of work); a
# state under twice this is hashed inline, on no thread. Sixteen threads
# hash faster than a state lands off a v5e (3-4 GB/s), so the window waits
# for little more than the last leaf's own digest
_BYTES_A_WORKER = 4 << 20
_MAX_WORKERS = 16


def digest_workers(nbytes: int, nbuffers: int) -> int:
    """How many threads a whole-tree digest of ``nbytes`` in ``nbuffers``
    buffers is worth, from what the process can see: its cores, the
    number of buffers (one buffer is one blake2b stream and cannot be
    split) and the bytes. 1 means inline."""
    return max(
        1,
        min(
            _MAX_WORKERS,
            len(os.sched_getaffinity(0)),
            nbuffers,
            nbytes // _BYTES_A_WORKER,
        ),
    )


def leaf_digests(
    buffers: Iterable[np.ndarray], workers: int = 1
) -> List[str]:
    """Per-buffer content digest (blake2b-64bit — cryptographic-family,
    so a delta never mis-skips a changed leaf the way a short checksum
    eventually would), in the order of ``buffers``.

    ``workers`` > 1 (see :func:`digest_workers`) hashes on that many
    threads, each buffer handed over as the iterable yields it, so a
    caller that passes buffers still landing (``Flattening.buffers()``)
    has leaf *i* hashed under the landing of leaf *i + 1*; hashlib drops
    the GIL on large buffers. The pool is joined before this returns. The
    per-step callers (commit trail, divergence sentinel) pass nothing: a
    pool there would take cores from the step."""
    if workers <= 1:
        return [_digest(buf) for buf in buffers]
    with ThreadPoolExecutor(
        max_workers=workers, thread_name_prefix="tft_heal_digest"
    ) as pool:
        return [f.result() for f in [pool.submit(_digest, b) for b in buffers]]


def tree_digest(digests: Sequence[str]) -> str:
    """Whole-tree digest over the ordered per-buffer digests.

    Deliberately does NOT hash the header pickle: pickle is not a
    canonical encoding (its id-based memoization makes a freshly-built
    tree and a heal-round-tripped tree with IDENTICAL structure and
    bytes serialize to different header lengths — found the hard way
    when a once-healed survivor was excluded from every stripe plan),
    and buffer identity is the property both consumers actually need —
    stripes move only buffer bytes, and the delta path always adopts the
    SERVER's header while reusing digest-matched healer buffers."""
    h = hashlib.blake2b(digest_size=8)
    for d in digests:
        h.update(d.encode())
    return h.hexdigest()


class CommitTrail:
    """Bounded per-leaf digest trail over recent committed steps.

    Thread-safe: the main thread records at step boundaries while the
    quorum/HTTP serving threads look entries up mid-heal (the staged
    buffers themselves are guarded by the transport's RWLock; this trail
    only carries digests)."""

    def __init__(self, horizon: Optional[int] = None) -> None:
        self._lock = threading.Lock()
        self._horizon = horizon if horizon is not None else trail_horizon()
        # step -> {"tree": str, "leaves": [str...], "sizes": [int...]}
        self._entries: "OrderedDict[int, Dict[str, Any]]" = OrderedDict()

    def record(
        self,
        step: int,
        buffers: Sequence[np.ndarray],
        digests: Optional[List[str]] = None,
    ) -> List[str]:
        """Record (or return the existing) digests for ``step``; evicts
        entries past the horizon. Returns the per-leaf digests."""
        with self._lock:
            ent = self._entries.get(step)
            if ent is not None:
                return list(ent["leaves"])
        leaves = digests if digests is not None else leaf_digests(buffers)
        ent = {
            "tree": tree_digest(leaves),
            "leaves": leaves,
            "sizes": [int(b.nbytes) for b in buffers],
        }
        with self._lock:
            self._entries[step] = ent
            self._entries.move_to_end(step)
            while len(self._entries) > self._horizon:
                self._entries.popitem(last=False)
        return list(leaves)

    def get(self, step: int) -> Optional[Dict[str, Any]]:
        with self._lock:
            ent = self._entries.get(step)
            return None if ent is None else dict(ent)

    def steps(self) -> List[int]:
        with self._lock:
            return list(self._entries)


def build_delta(
    header: bytes,
    buffers: Sequence[np.ndarray],
    staged_digests: Sequence[str],
    trail_entry: Optional[Dict[str, Any]],
    healer_tree_digest: str,
) -> Optional[Tuple[Dict[str, Any], List[np.ndarray]]]:
    """Server side: the delta manifest + changed buffers for a healer at
    the trail step described by ``trail_entry``, or ``None`` when only a
    full heal is sound (no trail entry, tree-digest mismatch, or leaf
    count drift)."""
    if trail_entry is None:
        return None
    if trail_entry["tree"] != healer_tree_digest:
        return None
    then: List[str] = trail_entry["leaves"]
    if len(then) != len(staged_digests) or len(then) != len(buffers):
        return None
    changed = [
        i for i, (a, b) in enumerate(zip(then, staged_digests)) if a != b
    ]
    manifest = {
        "mode": "delta",
        "header": header,
        "changed": changed,
        "sizes": [int(buffers[i].nbytes) for i in changed],
    }
    return manifest, [buffers[i] for i in changed]


def pack_delta(
    manifest: Dict[str, Any], changed: Sequence[np.ndarray]
) -> List[bytes]:
    """Serialize a delta (or a bare ``{"mode": "full"}`` refusal) into
    response body parts."""
    blob = pickle.dumps(manifest)
    out: List[bytes] = [_LEN.pack(len(blob)), blob]
    out.extend(bytes(as_bytes(b)) for b in changed)
    return out


def unpack_delta(body: bytes) -> Tuple[Dict[str, Any], bytes]:
    """Split a response body into (manifest, payload bytes)."""
    (n,) = _LEN.unpack_from(body, 0)
    manifest = pickle.loads(body[_LEN.size : _LEN.size + n])
    return manifest, body[_LEN.size + int(n) :]


def apply_delta(
    manifest: Dict[str, Any],
    payload: bytes,
    own_buffers: Sequence[np.ndarray],
) -> Tuple[bytes, List[np.ndarray]]:
    """Healer side: combine the delta's changed buffers with the healer's
    own (digest-matched) buffers into the full ``(header, buffers)`` the
    normal unflatten path consumes. Raises ``ValueError`` on any layout
    inconsistency — the caller falls back to a full heal."""
    header: bytes = manifest["header"]
    changed: List[int] = list(manifest["changed"])
    sizes: List[int] = list(manifest["sizes"])
    if len(changed) != len(sizes):
        raise ValueError("delta manifest: changed/sizes length mismatch")
    total = sum(sizes)
    if len(payload) != total:
        raise ValueError(
            f"delta payload truncated: {len(payload)} != {total}"
        )
    buffers: List[np.ndarray] = [
        np.frombuffer(as_bytes(b), dtype=np.uint8) for b in own_buffers
    ]
    off = 0
    for idx, nbytes in zip(changed, sizes):
        if idx >= len(buffers):
            raise ValueError(f"delta manifest: leaf index {idx} out of range")
        buffers[idx] = np.frombuffer(
            payload, dtype=np.uint8, count=nbytes, offset=off
        )
        off += nbytes
    return header, buffers
