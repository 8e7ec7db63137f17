"""What a profiler trace (``*.xplane.pb``) knows about each device op beyond
its name: the ``op_name`` JAX gave it (``jit(tft_grads)/jvp()/while/body/
closed_call/attn/dot_general``: the program's ``jax.named_scope``s are
components of that path) and the program it belongs to.

The runtime (libtpu 0.0.34) writes them as stats of each op's
``XEventMetadata`` — ``tf_op`` = ``<op_name>:`` and ``program_id`` — whether
``enable_hlo_proto`` is on or off (PR 25's probe: the two files differ by
600 bytes). ``jax.profiler.ProfileData`` hands out an event's own stats and
not its metadata's, so this file reads the protobuf's wire format itself,
and only the two maps it needs: the events, by far the most of the file,
are skipped by their length prefix.

The fields read (``tsl/profiler/protobuf/xplane.proto``): ``XSpace.planes``
= 1; ``XPlane.name`` = 2, ``.event_metadata`` = 4, ``.stat_metadata`` = 5
(maps: key = 1, value = 2); ``XEventMetadata.name`` = 2, ``.stats`` = 5;
``XStatMetadata.name`` = 2; ``XStat.metadata_id`` = 1, ``.uint64_value``
= 3, ``.int64_value`` = 4, ``.str_value`` = 5, ``.ref_value`` = 7.
"""

from __future__ import annotations

from typing import Dict, Iterator, Optional, Tuple

__all__ = ["op_names", "scope_of", "SCOPES", "UNSCOPED"]

# the scopes the program's one transformer names (``models/transformer.py``,
# ``parallel/train_step.py``); the PR that brings a model with another appends it
SCOPES = ("embed", "attn", "ffn", "moe", "head_loss", "optimizer")
UNSCOPED = "unscoped"
DEVICE_PREFIX = "/device:TPU"
OP_NAME_STAT = "tf_op"
PROGRAM_STAT = "program_id"

# {plane name: {(program id, the op's name as the event carries it): op_name}}
OpNames = Dict[str, Dict[Tuple[int, str], str]]


def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        byte = buf[i]
        i += 1
        out |= (byte & 0x7F) << shift
        if not byte & 0x80:
            return out, i
        shift += 7


def _fields(buf: bytes) -> Iterator[Tuple[int, object]]:
    """(field number, value) of one message: an int for a varint, the bytes
    of a length-delimited or fixed-width field."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i : i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = buf[i : i + size], i + size
        else:
            raise ValueError(f"wire type {wire} at byte {i}: not an xplane.pb")
        yield key >> 3, value


def _map_entry(buf: bytes) -> Tuple[Optional[int], bytes]:
    key, value = None, b""
    for field, v in _fields(buf):
        if field == 1:
            key = v
        elif field == 2:
            value = v
    return key, value


def _plane(buf: bytes):
    """(name, {stat metadata id: stat name}, [XEventMetadata bytes])."""
    name, stat_names, event_metadata = "", {}, []
    for field, v in _fields(buf):
        if field == 2:
            name = v.decode()
        elif field == 5:
            key, meta = _map_entry(v)
            stat_names[key] = next(
                (x.decode() for f, x in _fields(meta) if f == 2), ""
            )
        elif field == 4:
            event_metadata.append(_map_entry(v)[1])
    return name, stat_names, event_metadata


def op_names(path: str) -> OpNames:
    """Per device plane: the ``op_name`` of every op that has one, keyed by
    its program's id (0 where the op names none) and the name its events
    carry."""
    with open(path, "rb") as f:
        space = f.read()
    out: OpNames = {}
    for field, plane in _fields(space):
        if field != 1:
            continue
        plane_name, stat_names, event_metadata = _plane(plane)
        if not plane_name.startswith(DEVICE_PREFIX):
            continue
        ops = out.setdefault(plane_name, {})
        for meta in event_metadata:
            name, op_name, program = "", None, 0
            for f, v in _fields(meta):
                if f == 2:
                    name = v.decode(errors="replace")
                elif f == 5:
                    stat = dict(_fields(v))
                    which = stat_names.get(stat.get(1))
                    if which == OP_NAME_STAT:
                        text = stat[5].decode(errors="replace") if 5 in stat else stat_names.get(stat.get(7), "")
                        op_name = text.rsplit(":", 1)[0]
                    elif which == PROGRAM_STAT:
                        program = stat.get(3, stat.get(4, 0))
            if op_name:
                ops[(program, name)] = op_name
    return out


def scope_of(op_name: Optional[str]) -> str:
    """The innermost of :data:`SCOPES` on the path ``op_name``, else
    :data:`UNSCOPED`. JAX wraps a scope's name in what transformed it
    (``transpose(jvp(head_loss))``), and ``jax.checkpoint`` puts the forward
    ops it computes again under ``rematted_computation/<scope>``: both
    count to the scope."""
    for part in reversed((op_name or "").split("/")):
        core = part.rsplit("(", 1)[-1].rstrip(")")
        if core in SCOPES:
            return core
    return UNSCOPED
