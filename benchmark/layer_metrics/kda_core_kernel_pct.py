"""How much of the KDA core ran in its Pallas kernels: device seconds under
``attn/…/kda_core/…/kda_kernel`` (``ops/kda.py`` puts that scope around the
kernels' calls only) over ``kda_core_device_s``. About 100 where every call's
decay lets the kernels take it; it falls when a run's decays send blocks down
the exact ``jax.numpy`` path, and then says why ``kda_core_device_s`` rose.
``None`` for a program without the scope (the ``jax.numpy`` core alone)."""

import hybrid_scopes
import subscopes

NAME, UNIT, SOURCE = "kda_core_kernel_pct", "%", "device_trace"
LAYER = "device compute"
MOVES = "step_p50_s"

KERNEL = ("kda_kernel",)


def compute(run):
    by = subscopes.seconds(run, "attn", KERNEL)
    core = hybrid_scopes.core_seconds(run, "kda_core")
    if not by or KERNEL[0] not in by or not core:
        return None
    return 100.0 * by[KERNEL[0]] / core
