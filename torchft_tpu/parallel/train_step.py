"""Mesh-aware train step builder.

Couples the flagship model to optax under jit with explicit shardings.
Two drive modes:

* ``step``  — fused grads+update, buffers donated; the single-replica-group
  hot path (everything stays on device).
* ``grads`` / ``apply`` — split pair for fault-tolerant cross-group
  training: grads come to host, the Manager averages them over the elastic
  replica axis (outside jit, so membership changes never recompile), then
  ``apply`` updates on device.
"""

from __future__ import annotations

import time
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from torchft_tpu.models.transformer import (
    TransformerConfig,
    init_params,
    loss_and_stats,
    param_specs,
)
from torchft_tpu.telemetry import builds

__all__ = ["TrainStep"]


class TrainStep:
    def __init__(self, cfg: TransformerConfig, tx, mesh) -> None:
        # before the first tft_* program is traced: its build.* spans and
        # the totals of tft.build.counters (telemetry/builds.py)
        builds.install()
        self.cfg = cfg
        self.tx = tx
        self.mesh = mesh
        self._pspecs = param_specs(cfg)
        self._param_shardings = jax.tree_util.tree_map(
            lambda spec: NamedSharding(mesh, spec), self._pspecs
        )
        self._batch_sharding = NamedSharding(mesh, P(("dp", "fsdp"), "sp"))
        replicated = NamedSharding(mesh, P())
        # optimizer state: every subtree shaped like the params (Adam's
        # moments) is sharded like them, the rest (counts) is replicated
        params_abs = jax.eval_shape(
            lambda: init_params(jax.random.PRNGKey(0), cfg)
        )
        pdef = jax.tree_util.tree_structure(params_abs)

        def like_params(sub) -> bool:
            return jax.tree_util.tree_structure(sub) == pdef

        self._opt_shardings = jax.tree_util.tree_map(
            lambda sub: self._param_shardings if like_params(sub) else replicated,
            jax.eval_shape(tx.init, params_abs),
            is_leaf=like_params,
        )

        # statistics of the last step (``loss_and_stats``: tokens per expert
        # per layer and the balance term of dropless experts; ``main_loss`` and
        # ``mtp_loss`` where a multi-token-prediction module ran; ``exit_probs``,
        # ``exit_entropy`` and ``loss_by_step`` of a looped stack's exit gate),
        # left on the device: whoever wants them fetches them
        # (``FTTrainer.step``, with the loss). ``{}`` for a model with none.
        self.last_stats: Dict[str, jnp.ndarray] = {}

        def compute_loss(params, tokens):
            return loss_and_stats(params, tokens, cfg, mesh)

        # Shardings are pinned on both sides of grads/apply, so each is ONE
        # program whatever placed its inputs — init, the previous step, a
        # heal, the host after cross-group averaging. Left to inference,
        # apply compiled three variants (first step, steady state, first
        # step after a heal): the last is a persistent-cache miss for every
        # respawned group, and none matched what warm_apply lowers.
        # The three programs carry deliberate names (jit_tft_grads,
        # jit_tft_apply, jit_tft_fused): a profiler trace's `XLA Modules`
        # line and the compile log name a program after its function, and
        # what reads them must find it after a refactor.
        def tft_grads(params, tokens):
            (loss, stats), grads = jax.value_and_grad(compute_loss, has_aux=True)(
                params, tokens
            )
            return loss, grads, stats

        self._value_and_grad = jax.jit(
            tft_grads,
            in_shardings=(self._param_shardings, self._batch_sharding),
            out_shardings=(replicated, self._param_shardings, replicated),
        )

        def tft_apply(params, opt_state, grads):
            import optax

            with jax.named_scope("optimizer"):
                updates, opt_state = tx.update(grads, opt_state, params)
                return optax.apply_updates(params, updates), opt_state

        self._apply = jax.jit(
            tft_apply,
            donate_argnums=(0, 1),
            in_shardings=(
                self._param_shardings, self._opt_shardings, self._param_shardings
            ),
            out_shardings=(self._param_shardings, self._opt_shardings),
        )

        def tft_fused(params, opt_state, tokens):
            loss, grads, stats = tft_grads(params, tokens)
            new_params, opt_state = tft_apply(params, opt_state, grads)
            return loss, new_params, opt_state, stats

        self._fused = jax.jit(tft_fused, donate_argnums=(0, 1))
        # programs whose first call (the build as a caller feels it) is
        # still to come
        self._uncalled = {"fused", "grads", "apply"}

    # -- state --

    def init_params(self, rng) -> Dict[str, Any]:
        with jax.set_mesh(self.mesh):
            params = jax.jit(
                lambda r: init_params(r, self.cfg),
                out_shardings=self._param_shardings,
            )(rng)
        return params

    def init_opt(self, params) -> Any:
        with jax.set_mesh(self.mesh):
            return jax.jit(self.tx.init, out_shardings=self._opt_shardings)(
                params
            )

    def warm_apply(self, params_spec, opt_state_spec) -> None:
        """AOT-compile the donated ``apply`` jit from abstract specs (the
        heal/compile overlap, docs/heal_plane.md): called on a background
        thread while checkpoint stripes stream, so the healer's first
        post-heal apply finds the executable in the persistent compilation
        cache (utils/compile_cache.py) instead of paying the compile
        serially after recv. It is the same program as the main thread's
        because ``apply`` pins its shardings; the AOT path and the later
        call share nothing in memory. Grad specs mirror param specs
        (identical pytree/shapes/dtypes)."""
        with jax.set_mesh(self.mesh):
            self._apply.lower(params_spec, opt_state_spec, params_spec).compile()

    def shard_batch(self, tokens) -> jnp.ndarray:
        if not self._batch_sharding.is_fully_addressable:
            # multi-host group: every process holds the full batch (same
            # sampler state); carve out each local device's shard
            import numpy as np

            arr = np.asarray(tokens)
            return jax.make_array_from_callback(
                arr.shape, self._batch_sharding, lambda idx: arr[idx]
            )
        return jax.device_put(tokens, self._batch_sharding)

    # -- drive --

    def _record_compute(self, t0: float, program: str) -> None:
        # step-anatomy `compute` phase: main-thread time inside the jitted
        # calls (dispatch + any blocking; with async dispatch the device
        # tail lands in whoever blocks next — usually the host copy, which
        # the ledger attributes to host_copy/wire). Best-effort. A
        # program's first call is its build: `first_call_s` of
        # tft.build.counters.
        try:
            seconds = time.perf_counter() - t0
            if program in self._uncalled:
                self._uncalled.discard(program)
                builds.first_call(seconds)
            from torchft_tpu.telemetry.anatomy import LEDGER

            LEDGER.record("compute", seconds)
        except Exception:  # noqa: BLE001 — observability never fails a step
            pass

    def step(self, params, opt_state, tokens) -> Tuple[jnp.ndarray, Any, Any]:
        """Fused grads+update (single replica group / no FT averaging)."""
        t0 = time.perf_counter()
        with jax.set_mesh(self.mesh):
            *out, self.last_stats = self._fused(params, opt_state, tokens)
        self._record_compute(t0, "fused")
        builds.annotate_counters()
        return tuple(out)

    def grads(self, params, tokens) -> Tuple[jnp.ndarray, Any]:
        """Loss + gradient pytree (still on device)."""
        t0 = time.perf_counter()
        with jax.set_mesh(self.mesh):
            *out, self.last_stats = self._value_and_grad(params, tokens)
        self._record_compute(t0, "grads")
        builds.annotate_counters()
        return tuple(out)

    def apply(self, params, opt_state, grads) -> Tuple[Any, Any]:
        """Apply (possibly host-averaged) grads; ``params`` and
        ``opt_state`` are donated."""
        t0 = time.perf_counter()
        with jax.set_mesh(self.mesh):
            out = self._apply(params, opt_state, grads)
        self._record_compute(t0, "apply")
        return out
