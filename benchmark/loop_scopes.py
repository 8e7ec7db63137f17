"""Inside ``attn``, ``ffn`` and ``head_loss`` of a LOOPED stack (the same
layers run ``ut_steps`` times a step, each layer norming its two parts'
outputs, an exit gate a loop step): the TABLE the readers of such a
configuration share, and the function that reads it.

The program nests (``torchft_tpu/models/transformer.py``) ``post_norm`` under
``attn`` and under ``ffn`` — the norm of the mixer's and of the feed-forward's
OUTPUT, ``x + N(mix(N(x)))`` — and ``exit`` under ``head_loss``: the gate's
projection, the exit distribution, its entropy, the statistics and their
backward. ``subscopes.py`` splits a top-level scope's device seconds among
such names (the innermost on an op's path wins); a row of :data:`SPLITS` says
which splits a reader takes, one a parent, and adds them up.

A program without the scopes (the parent of the PR that brought them, another
architecture) or a trace without them gives ``None``, never an error.
"""

from __future__ import annotations

from typing import Optional

import subscopes

# reader -> ((top-level scope, the name nested in it), ...): the reader's seconds are the sum over the rows
SPLITS = {
    "loop_post_norm_device_s": (("attn", "post_norm"), ("ffn", "post_norm")),
    "loop_exit_device_s": (("head_loss", "exit"),),
}


def seconds(run, reader: str) -> Optional[float]:
    """Device seconds a step of what ``reader`` names in :data:`SPLITS`;
    ``None`` where the trace lacks one of its scopes."""
    total = 0.0
    for parent, name in SPLITS[reader]:
        by = subscopes.seconds(run, parent, (name,))
        if not by or name not in by:
            return None
        total += by[name]
    return total
