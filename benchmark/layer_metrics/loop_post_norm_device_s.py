"""Device seconds a step in the norms of the layers' two OUTPUTS (scopes ``attn/post_norm`` and ``ffn/post_norm``:
``x + N(mix(N(x)))``, ``x + N(ffn(N(x)))``) of a looped stack, all ``ut_steps`` x ``n_layers`` applications: forward,
the forward ``remat`` runs again, and backward. Bandwidth-bound passes over ``[tokens, d_model]``
(``opcounts/ouro.py``: ``post_norm_bytes_per_step``); part of ``attn_device_s`` and ``ffn_device_s`` (``loop_scopes.py``)."""

import loop_scopes

NAME, UNIT, SOURCE = "loop_post_norm_device_s", "s", "device_trace"
LAYER = "device compute"
MOVES = "step_p50_s"


def compute(run):
    return loop_scopes.seconds(run, NAME)
