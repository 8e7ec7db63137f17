"""Placement of JAX's persistent compilation cache — the one place the
program names it.

A respawned replica group recompiles exactly what its predecessor
compiled, so a cold compile is part of rejoin time unless the cache
persists and stays put: the directory is part of the cache key, so a
directory that moves (``tempfile``, a pid, the clock) never hits.

Whether a build hit it is seen from inside: ``telemetry/builds.py`` listens
on ``jax.monitoring`` and puts every trace, lowering and compile-or-load on
the Tracer as a ``build.*`` span (``cache`` = ``hit`` / ``miss`` / ``off``)
and in the totals ``tft.build.counters`` carries.
"""

from __future__ import annotations

import os
import sys

__all__ = ["place_compile_cache", "DEFAULT_DIR"]

_ENV = "JAX_COMPILATION_CACHE_DIR"
_MIN_SECS_ENV = "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"
# fixed, inside the checkout, git-ignored
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def place_compile_cache() -> str:
    """Resolve the cache directory; call before first use of JAX in every
    process that jits, and in any parent whose children do.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set it wins untouched: JAX reads
    the variable itself and no code sets another. Otherwise the directory
    is :data:`DEFAULT_DIR`, exported through the environment so children —
    a respawned group above all — resolve the same one. Returns the
    directory in use.

    Either way every program is kept, however quickly it compiled, unless
    the environment says otherwise: JAX's default skips those under a
    second, which on a v5e left out ``apply`` (0.78 s at 647M parameters)
    and a dozen small ones a respawn compiles again."""
    jax = sys.modules.get("jax")
    if jax is not None:
        # a process that imports jax later gets them from TrainStep
        from torchft_tpu.telemetry import builds

        builds.install()
    if _MIN_SECS_ENV not in os.environ:
        os.environ[_MIN_SECS_ENV] = "0"
        if jax is not None:
            jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    path = os.environ.get(_ENV)
    if path:
        return path
    os.environ[_ENV] = DEFAULT_DIR
    if jax is not None:
        # jax read the (then unset) variable when it was imported
        jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
