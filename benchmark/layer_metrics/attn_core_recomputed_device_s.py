"""Device seconds a step the attention core's forward kernel runs a SECOND time:
the ops of the Pallas kernel ``flash_fwd`` (``ops/pallas/flash_attention.py``) whose
``op_name`` lies under ``rematted_computation``, where ``jax.checkpoint`` puts the
forward ops it computes again for the backward (``xplane_meta.scope_of``). What the
kernel's forward costs beyond the once the algorithm needs: the second forwards'
seconds on a program whose checkpoints keep nothing of the core, 0 once they keep its
output and row statistics (PR 47) — an exact 0 is a reading. ``None`` without a device
trace or without a device op under ``attn``.

``subscopes.units`` gives an op to a name through ``subscopes.innermost``, which looks
on the op's path BELOW its top-level scope; ``rematted_computation`` sits ABOVE it
(``…/checkpoint/rematted_computation/attn/core/flash_fwd/pallas_call``). So this reader
hands ``units`` its own rule for the length of the call, and every other rule — which
traced unit owns a program run, self times, the last of several units left out, per
step, the mean over chips and groups — is ``subscopes``'s own."""

from unittest import mock

import subscopes
import xplane_meta

NAME, UNIT, SOURCE = "attn_core_recomputed_device_s", "s", "device_trace"
LAYER = "device compute"
MOVES = "step_p50_s"

PARENT = "attn"
KERNEL, AGAIN = "flash_fwd", "rematted_computation"
KEY = "flash_fwd_again"


def again(op_name, parent=PARENT, names=()):
    """In ``subscopes.innermost``'s place: ``None`` for an op that does not count
    to ``parent``, :data:`KEY` for the kernel's forward under a recomputation,
    ``parent`` for every other op of the scope."""
    if xplane_meta.scope_of(op_name) != parent:
        return None
    parts = {part.rsplit("(", 1)[-1].rstrip(")") for part in op_name.split("/")}
    return KEY if {KERNEL, AGAIN} <= parts else parent


def rows(path):
    with mock.patch.object(subscopes, "innermost", again):
        return subscopes.units(path, PARENT, (KEY,))


def compute(run):
    by = subscopes.seconds(run, PARENT, (KEY,), rows_of=rows)
    return None if by is None else by.get(KEY, 0.0)
