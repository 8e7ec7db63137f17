"""Wire codecs + error feedback — the compression layer of the cross-group
gradient plane (docs/wire_plane.md).

The cross-group average is bound by bytes moved on the host (at four
groups the ring takes 1.18 s for 2.43 GB of f32 gradients a group, of a
2.13 s step: ledger, PR 29, ``exchange_ring_s``), so the wire can carry
QUANTIZED bytes while local accumulation stays f32; what a codec gains
is not measured on the chip (no cell selects one). A codec maps an f32
chunk to its wire form and back:

* ``f32``      — identity (4 bytes/elem), the exact default.
* ``bfloat16`` — round-to-nearest-even truncation (2 bytes/elem).
* ``int8``     — per-chunk symmetric quantization (1 byte/elem + a 4-byte
  f32 scale header per chunk): ``scale = max|x| / 127``,
  ``q = clip(rint(x / scale), -127, 127)``.

Codecs are applied ON THE WIRE, before striping: both the native striped
plane (native/dataplane.cc mirrors the byte formats here exactly) and the
Python ring (collectives.py) ship codec bytes per hop while reducing in
f32 locally. Bit-identity of the decoded average across replica groups —
the faultmatrix invariant — is guaranteed BY CONSTRUCTION, not by fp
luck: after the reduce-scatter phase the owner of each fully-reduced
chunk encodes it once, decodes those same bytes back into its own copy,
and the allgather phase forwards the owner's wire bytes VERBATIM; every
rank decodes identical bytes.

Quantization is lossy; :class:`ErrorFeedback` keeps convergence honest
(Vogels et al., PowerSGD, NeurIPS 2019; Karimireddy et al., EF-SGD): the
residual of each step's quantization is accumulated and added back before
the next quantize, so the error stays bounded instead of compounding.
Accumulators are commit-lineage-aware — ``commit()`` promotes the step's
pending residual, ``rollback()`` discards it (an aborted or vetoed step
must not corrupt the residual state) — and serialize through
``state_dict``/``load_state_dict`` so heal/checkpoint round-trips carry
them.

:func:`lowrank_compress`/:func:`lowrank_decompress` add the optional
PowerSGD-style rank-r projection for the DiLoCo outer step (the one place
staleness already tolerates approximation): the projection basis is drawn
from a SEEDED rng keyed on (leaf, sync ordinal), so every replica group
derives the same basis without communicating it.

All scratch is preallocated per codec instance and grown monotonically —
the hot path never allocates per chunk per round (the ``astype`` tax the
old ring paid).
"""

from __future__ import annotations

import logging
import os
import struct
from typing import Any, Dict, Optional, Tuple

import numpy as np

logger = logging.getLogger(__name__)

__all__ = [
    "WireCodec",
    "F32Codec",
    "Bf16Codec",
    "Int8Codec",
    "get_codec",
    "CODEC_NAMES",
    "ErrorFeedback",
    "lowrank_basis",
    "lowrank_compress",
    "lowrank_decompress",
]

_SCALE_HDR = struct.Struct("<f")  # int8 per-chunk scale prefix (LE f32)

CODEC_NAMES = ("f32", "bfloat16", "int8")


class WireCodec:
    """One codec instance per collectives backend: owns the preallocated
    encode/decode scratch (single-threaded use on the collective op
    thread). ``lossy`` codecs only apply to f32 arrays; callers route
    other dtypes through the identity codec."""

    name = "f32"
    lossy = False

    def __init__(self) -> None:
        self._wire: Optional[np.ndarray] = None  # uint8 encode scratch
        self._f32: Optional[np.ndarray] = None   # f32 decode/temp scratch

    # -- layout --

    def wire_nbytes(self, nelems: int, itemsize: int = 4) -> int:
        raise NotImplementedError

    # -- scratch --

    def ensure_capacity(self, max_elems: int, itemsize: int = 4) -> None:
        """Grow the scratch to hold one max-size chunk; call once per op
        (amortized: buffers persist and only ever grow)."""
        need = self.wire_nbytes(max_elems, itemsize)
        if self._wire is None or self._wire.size < need:
            self._wire = np.empty(need, dtype=np.uint8)
        if self.lossy and (self._f32 is None or self._f32.size < max_elems):
            self._f32 = np.empty(max_elems, dtype=np.float32)

    # -- codec --

    def encode_into(self, src: np.ndarray) -> memoryview:
        """Encode the 1-D chunk ``src`` into this codec's scratch; returns
        the wire-byte view (valid until the next encode_into)."""
        raise NotImplementedError

    def decode_into(self, wire: np.ndarray, dst: np.ndarray) -> None:
        """Decode wire bytes (uint8 array/view) into the 1-D chunk
        ``dst``, overwriting it."""
        raise NotImplementedError

    def decode_tmp(self, wire: np.ndarray, nelems: int) -> np.ndarray:
        """Decode into the codec's own f32 scratch (for reduce steps);
        the view is valid until the next decode_tmp/encode_into."""
        raise NotImplementedError

    def roundtrip(self, arr: np.ndarray) -> None:
        """In-place ``arr = decode(encode(arr))`` — projects onto the wire
        grid (what error feedback measures its residual against)."""
        flat = arr.reshape(-1)
        self.ensure_capacity(flat.size, arr.dtype.itemsize)
        w = self.encode_into(flat)
        self.decode_into(np.frombuffer(w, dtype=np.uint8), flat)


class F32Codec(WireCodec):
    """Identity codec — raw bytes on the wire, any dtype."""

    name = "f32"
    lossy = False

    def wire_nbytes(self, nelems: int, itemsize: int = 4) -> int:
        return nelems * itemsize

    def encode_into(self, src: np.ndarray) -> memoryview:
        # zero-copy: the chunk's own bytes ARE the wire form
        src = np.ascontiguousarray(src)
        try:
            return memoryview(src).cast("B")
        except (ValueError, TypeError):  # ml_dtypes reject buffer protocol
            return memoryview(src.view(np.uint8)).cast("B")

    def decode_into(self, wire: np.ndarray, dst: np.ndarray) -> None:
        dst.view(np.uint8).reshape(-1)[:] = np.frombuffer(
            wire, dtype=np.uint8, count=dst.nbytes
        )

    def decode_tmp(self, wire: np.ndarray, nelems: int) -> np.ndarray:
        raise NotImplementedError(
            "identity codec callers reduce straight from the typed view"
        )

    def roundtrip(self, arr: np.ndarray) -> None:  # exact — nothing to do
        return


class Bf16Codec(WireCodec):
    """f32 → bfloat16 truncation (round-to-nearest-even), 2 bytes/elem.
    Matches numpy/ml_dtypes ``astype`` semantics and the native plane's
    ``f32_to_bf16`` bit for bit."""

    name = "bfloat16"
    lossy = True

    def __init__(self) -> None:
        super().__init__()
        import ml_dtypes  # registers the bfloat16 dtype

        self._bf16 = np.dtype(ml_dtypes.bfloat16)

    def wire_nbytes(self, nelems: int, itemsize: int = 4) -> int:
        return nelems * 2

    def encode_into(self, src: np.ndarray) -> memoryview:
        n = src.size
        self.ensure_capacity(n)
        view = self._wire[: n * 2].view(self._bf16)
        view[:] = src  # casting assignment: no allocation
        return memoryview(self._wire[: n * 2])

    def decode_into(self, wire: np.ndarray, dst: np.ndarray) -> None:
        n = dst.size
        dst[:] = np.frombuffer(wire, dtype=self._bf16, count=n)

    def decode_tmp(self, wire: np.ndarray, nelems: int) -> np.ndarray:
        self.ensure_capacity(nelems)
        out = self._f32[:nelems]
        out[:] = np.frombuffer(wire, dtype=self._bf16, count=nelems)
        return out


class Int8Codec(WireCodec):
    """Per-chunk symmetric int8 quantization: a 4-byte f32 scale header
    followed by one int8 per element. ``scale = max|x|/127`` adapts per
    chunk per hop, so partial sums in the reduce-scatter phase re-quantize
    at their own magnitude. A chunk containing non-finite values encodes
    ``scale = NaN`` + zero payload, so NaN propagates loudly through the
    decode instead of being laundered into a finite average."""

    name = "int8"
    lossy = True

    def wire_nbytes(self, nelems: int, itemsize: int = 4) -> int:
        return 4 + nelems

    def encode_into(self, src: np.ndarray) -> memoryview:
        n = src.size
        self.ensure_capacity(n)
        wire = self._wire[: 4 + n]
        tmp = self._f32[:n]
        np.abs(src, out=tmp)
        amax = float(tmp.max()) if n else 0.0
        q = wire[4:].view(np.int8)
        if not np.isfinite(amax):
            _SCALE_HDR.pack_into(wire.data, 0, np.float32(np.nan))
            q.fill(0)
            return memoryview(wire)
        scale = np.float32(amax / 127.0) if amax > 0.0 else np.float32(0.0)
        _SCALE_HDR.pack_into(wire.data, 0, scale)
        if scale == 0.0:
            q.fill(0)
            return memoryview(wire)
        np.divide(src, scale, out=tmp)
        np.rint(tmp, out=tmp)
        np.clip(tmp, -127.0, 127.0, out=tmp)
        q[:] = tmp  # casting assignment
        return memoryview(wire)

    def _scale_of(self, wire: np.ndarray) -> float:
        return _SCALE_HDR.unpack_from(
            np.frombuffer(wire, dtype=np.uint8, count=4).tobytes(), 0
        )[0]

    def decode_into(self, wire: np.ndarray, dst: np.ndarray) -> None:
        n = dst.size
        scale = self._scale_of(wire)
        q = np.frombuffer(wire, dtype=np.int8, count=4 + n)[4:]
        dst[:] = q
        np.multiply(dst, np.float32(scale), out=dst)

    def decode_tmp(self, wire: np.ndarray, nelems: int) -> np.ndarray:
        self.ensure_capacity(nelems)
        out = self._f32[:nelems]
        self.decode_into(wire, out)
        return out


def get_codec(name: Optional[str]) -> WireCodec:
    """Codec by wire-dtype name (``None``/"f32"/"float32" → identity)."""
    if name in (None, "", "f32", "float32"):
        return F32Codec()
    if name == "bfloat16":
        return Bf16Codec()
    if name == "int8":
        return Int8Codec()
    raise ValueError(
        f"unknown wire codec {name!r}; expected one of {CODEC_NAMES}"
    )


# ---------------------------------------------------------------------------
# error feedback
# ---------------------------------------------------------------------------


class ErrorFeedback:
    """Persistent per-bucket quantization residuals with commit-lineage
    semantics.

    Per step, per bucket: ``apply(key, buf)`` adds the committed residual
    back into ``buf``, projects ``buf`` onto the codec's grid in place,
    and stages the new residual as PENDING. The caller then promotes or
    discards it with the step's fate: ``commit()`` after a committed
    step, ``rollback()`` after an abort/veto — a discarded step's
    residual must never leak into the next step's compensation (that is
    the "silent residual corruption" the faultmatrix scenarios assert
    against).

    Scope of the compensation: the residual measures the BUCKET-level
    projection. For bf16 (a per-element grid) the wire's subsequent
    encode of the projected values is exact, so the residual captures
    the full input-quantization error. For int8 the wire re-quantizes
    per ring chunk (and per native stripe) with its own scale, so a
    chunk whose magnitude sits far below the bucket max picks up an
    additional, finer-grid error that stays UNCOMPENSATED — bounded per
    step (≤ half a chunk-scale step per element) and of the same class
    as the per-hop partial-sum re-quantization error, which EF never
    covers either. What EF guarantees is that the dominant, coarse-grid
    error cannot accumulate across steps.

    State serializes via ``state_dict``/``load_state_dict`` so heals and
    disk checkpoints carry the accumulators (a healed replica restarting
    from zero residuals would re-pay the cold-start quantization bias).
    """

    def __init__(self, codec: WireCodec) -> None:
        if not codec.lossy:
            raise ValueError(
                "error feedback is meaningless on an exact codec"
            )
        self._codec = codec
        self._acc: Dict[str, np.ndarray] = {}       # committed residuals
        self._pending: Dict[str, np.ndarray] = {}   # this step's residuals
        self._pre: Dict[str, np.ndarray] = {}       # reusable pre-quant copies

    @property
    def codec(self) -> WireCodec:
        return self._codec

    def apply(self, key: str, buf: np.ndarray) -> None:
        """Compensate + project ``buf`` (owned, f32, 1-D) in place and
        stage the fresh residual under ``key``. Keys must be stable across
        steps (bucket ordinal + size); a stale key whose size changed is
        dropped rather than mis-added."""
        if buf.dtype != np.float32:
            return  # lossy wire only applies to f32 buffers
        acc = self._acc.get(key)
        if acc is not None:
            if acc.size == buf.size:
                buf += acc
            else:
                del self._acc[key]  # bucket plan changed: residual stale
        pre = self._pre.get(key)
        if pre is None or pre.size != buf.size:
            pre = np.empty_like(buf)
            self._pre[key] = pre
        pre[:] = buf
        self._codec.roundtrip(buf)   # project onto the wire grid
        np.subtract(pre, buf, out=pre)
        self._pending[key] = pre

    def commit(self) -> None:
        """Promote this step's pending residuals (the step committed)."""
        for key, pre in self._pending.items():
            acc = self._acc.get(key)
            if acc is None or acc.size != pre.size:
                self._acc[key] = pre.copy()
            else:
                acc[:] = pre
        self._pending.clear()

    def rollback(self) -> None:
        """Discard this step's pending residuals (abort/veto): the
        committed accumulators are untouched — exactly the state the
        replayed/retried step must compensate with."""
        self._pending.clear()

    def pending_keys(self) -> Tuple[str, ...]:
        return tuple(self._pending)

    def state_dict(self) -> Dict[str, Any]:
        # committed residuals only: a pending residual belongs to an
        # unresolved lineage and must never travel through a heal
        return {
            "codec": self._codec.name,
            "acc": {k: v.copy() for k, v in self._acc.items()},
        }

    def load_state_dict(self, state: Dict[str, Any]) -> None:
        if state.get("codec") not in (None, self._codec.name):
            # codec changed between checkpoint and restore: the residuals
            # measure a different grid — start clean rather than mis-add
            logger.warning(
                "error-feedback state was recorded for codec %r but the "
                "live codec is %r; dropping accumulators",
                state.get("codec"), self._codec.name,
            )
            self._acc = {}
        else:
            self._acc = {
                k: np.asarray(v, dtype=np.float32).copy()
                for k, v in state.get("acc", {}).items()
            }
        self._pending.clear()


class ErrorFeedbackBinding:
    """Resolves which :class:`ErrorFeedback` (if any) applies to a
    manager's LIVE data plane — the one shared implementation behind
    ``ManagedOptimizer`` and ``LocalSGD``/``DiLoCo``.

    ``explicit=None`` is auto mode (vetoed by ``TORCHFT_WIRE_EF=0``): the
    accumulator is created as soon as a lossy codec is observed — at
    construction if the plane already reports one, else lazily via
    :meth:`live` (a proxied backend only learns its child's codec at the
    first configure). ``live()`` also gates compensation OFF while the
    transport is exact (the CMA bypass): projecting onto a codec grid
    with no lossy wire underneath would ADD error (docs/wire_plane.md).
    ``explicit=False`` disables; an :class:`ErrorFeedback` instance is
    used as-is (shared)."""

    def __init__(self, manager: Any, explicit: Any = None) -> None:
        self._manager = manager
        self._auto = False
        self.instance: Optional[ErrorFeedback] = None
        if explicit is None:
            if os.environ.get("TORCHFT_WIRE_EF", "1") != "0":
                self._auto = True
                codec = get_codec(self._codec_name())
                if codec.lossy:
                    self.instance = ErrorFeedback(codec)
        elif explicit is not False:
            self.instance = explicit

    def _codec_name(self) -> str:
        # getattr: duck-typed test managers may predate the knob
        fn = getattr(self._manager, "wire_codec", None)
        return fn() if callable(fn) else "f32"

    def live(self) -> Optional[ErrorFeedback]:
        """The error feedback to use for THIS step/sync, or None when the
        live transport is exact."""
        name = self._codec_name()
        if name == "f32":
            return None
        if self.instance is None and self._auto:
            codec = get_codec(name)
            if codec.lossy:
                self.instance = ErrorFeedback(codec)
        return self.instance

    def ensure_for_state(self, ef_state: Any) -> Optional[ErrorFeedback]:
        """Restore path: a heal/checkpoint carries EF state, but in auto
        mode the instance may not exist yet (a proxied backend reports
        its codec only after the first configure — possibly AFTER the
        heal lands). Create it from the state's own codec name so the
        accumulators are adopted instead of silently dropped."""
        if (
            self.instance is None
            and self._auto
            and isinstance(ef_state, dict)
        ):
            try:
                codec = get_codec(ef_state.get("codec"))
            except ValueError:
                return None  # unknown codec in foreign state: skip
            if codec.lossy:
                self.instance = ErrorFeedback(codec)
        return self.instance


# ---------------------------------------------------------------------------
# PowerSGD-style low-rank projection (DiLoCo outer step)
# ---------------------------------------------------------------------------


def lowrank_basis(shape: Tuple[int, int], rank: int, seed: int) -> np.ndarray:
    """Deterministic orthonormal basis ``Q`` (n × rank) for the rank-r
    projection of an (m × n) matrix. Seeded, so every replica group
    derives the SAME basis from the same (leaf, sync ordinal) coordinates
    without shipping it — the cross-group average of projections is then
    well-defined.

    Determinism caveat (docs/wire_plane.md): "same" here requires every
    group to run the SAME numpy + BLAS/LAPACK wheels — the Generator
    stream and the QR bit-patterns are stable within one build, not
    contractually across builds (OpenBLAS vs MKL differ). A mixed-wheel
    fleet must not enable the low-rank outer step; the deployment story
    (one container image for all groups) satisfies this by construction."""
    _m, n = shape
    rng = np.random.default_rng(seed)
    g = rng.standard_normal((n, rank)).astype(np.float32)
    q, _r = np.linalg.qr(g)
    return np.ascontiguousarray(q, dtype=np.float32)


def lowrank_compress(mat: np.ndarray, q: np.ndarray) -> np.ndarray:
    """Project ``mat`` (m × n) onto the basis: returns ``P = mat @ Q``
    (m × rank) — the only tensor that crosses the wire."""
    # asarray, not astype: callers guarantee f32, and astype's default
    # copy would duplicate the largest tensors in the outer-sync path
    return np.ascontiguousarray(np.asarray(mat, dtype=np.float32) @ q)


def lowrank_decompress(p: np.ndarray, q: np.ndarray) -> np.ndarray:
    """Reconstruct the rank-r approximation ``P @ Q^T`` (m × n)."""
    return p @ q.T


def lowrank_eligible(shape: Tuple[int, ...], rank: int) -> bool:
    """A leaf is worth projecting when it is a true 2-D matrix and the
    rank-r form is meaningfully smaller than the dense one."""
    if len(shape) != 2 or rank <= 0:
        return False
    m, n = shape
    return min(m, n) >= 4 * rank


class LowRankErrorFeedback:
    """Residual carry for the DiLoCo outer-step low-rank projection —
    same commit/rollback lineage contract as :class:`ErrorFeedback`, but
    the residual measures the projection error ``M − P·Qᵀ`` per leaf."""

    def __init__(self) -> None:
        self._acc: Dict[str, np.ndarray] = {}
        self._pending: Dict[str, np.ndarray] = {}

    def compensate(self, key: str, mat: np.ndarray) -> np.ndarray:
        acc = self._acc.get(key)
        if acc is not None and acc.shape == mat.shape:
            return mat + acc
        return mat

    def stage(self, key: str, mat: np.ndarray, approx: np.ndarray) -> None:
        self._pending[key] = mat - approx

    def commit(self) -> None:
        self._acc.update(self._pending)
        self._pending = {}

    def rollback(self) -> None:
        self._pending = {}

    def state_dict(self) -> Dict[str, Any]:
        return {"acc": {k: v.copy() for k, v in self._acc.items()}}

    def load_state_dict(self, state: Dict[str, Any]) -> None:
        self._acc = {
            k: np.asarray(v, dtype=np.float32).copy()
            for k, v in state.get("acc", {}).items()
        }
        self._pending = {}
