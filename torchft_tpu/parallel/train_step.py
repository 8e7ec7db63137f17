"""Mesh-aware train step builder.

Couples the flagship model to optax under jit with explicit shardings.
Two drive modes:

* ``step``  — fused grads+update, buffers donated; the single-replica-group
  hot path (everything stays on device).
* ``grads`` / ``apply`` — split pair for fault-tolerant cross-group
  training: grads come to host, the Manager averages them over the elastic
  replica axis (outside jit, so membership changes never recompile), then
  ``apply`` updates on device. Where the stack can be cut a layer at a time
  (``transformer.cuts_by_layer``) ``grads`` is a chain of L + 2 programs
  dispatched back to back, and its gradient a :class:`~torchft_tpu.utils.pieces.GradPieces`
  in the order the pieces become ready — the head's first, then the layers'
  from the last to the first, then the embedding's — so the host exchange
  moves one piece while the chip computes the next; ``apply`` stacks them
  inside its program. Any other stack is one program and one tree.
"""

from __future__ import annotations

import time
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from torchft_tpu.models.transformer import (
    TransformerConfig,
    cuts_by_layer,
    grads_chain,
    grads_of_pieces,
    init_params,
    loss_and_stats,
    param_specs,
)
from torchft_tpu.telemetry import builds
from torchft_tpu.utils.pieces import GradPieces

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

        # The same value and gradient as a chain (transformer.grads_chain),
        # where the stack can be cut: every link carries tft_grads' name (a
        # trace's `XLA Modules` line then sums them as it summed the one)
        # and pins its parameters' and its gradients' shardings as tft_grads
        # does, and the cotangent's that one link hands the next (the
        # activations' own, as the model states it: the layer's program is
        # ONE, whichever link gave it its cotangent); what the forward kept
        # stays where the compiler put it. None: `grads` is the one program
        # above.
        self._chain = None
        if cuts_by_layer(cfg):
            sh = self._param_shardings

            def link(fn, **jit_kwargs):
                fn.__name__ = fn.__qualname__ = tft_grads.__name__
                return jax.jit(fn, **jit_kwargs)

            head, layer, tail, dx_spec = grads_chain(cfg, mesh)
            act = NamedSharding(mesh, dx_spec)
            self._chain = (
                link(
                    head,
                    in_shardings=(sh, self._batch_sharding),
                    out_shardings=(replicated, replicated, {k: sh[k] for k in ("final_norm", "out")}, (act, None)),
                ),
                # the cotangent from above is the one below's buffer
                link(layer, donate_argnums=(3,), in_shardings=(sh["layers"], None, None, act), out_shardings=(sh["layers"], act)),
                link(tail, in_shardings=(sh["embed"], self._batch_sharding, act), out_shardings={"embed": sh["embed"]}),
            )

        def tft_apply(params, opt_state, grads):
            import optax

            with jax.named_scope("optimizer"):
                updates, opt_state = tx.update(grads, opt_state, params)
                return optax.apply_updates(params, updates), opt_state

        def apply_jit(update, grad_shardings):
            update.__name__ = update.__qualname__ = tft_apply.__name__
            return jax.jit(
                update,
                donate_argnums=(0, 1),
                in_shardings=(self._param_shardings, self._opt_shardings, grad_shardings),
                out_shardings=(self._param_shardings, self._opt_shardings),
            )

        # the update of a gradient shaped like the parameters: what `apply`
        # runs where `grads` is one program (and what compile_check lowers)
        self._apply = apply_jit(tft_apply, self._param_shardings)
        # where `grads` is a chain, `apply` runs THIS one and no other (and
        # `warm_apply` lowers it): the pieces are stacked here, inside the
        # program — the donated state and the update are what they were, and
        # the stack is one pass over the gradient (dynamic-update-slices;
        # 4 ms of device time at 6 x 67M parameters, PERF.md §6, PR 59)
        self._apply_pieces = self._chain and apply_jit(
            lambda params, opt_state, pieces: tft_apply(params, opt_state, grads_of_pieces(pieces)),
            self.grad_pieces(self._param_shardings),
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
        (identical pytree/shapes/dtypes), cut into the pieces ``grads``
        gives where it is a chain."""
        with jax.set_mesh(self.mesh):
            if self._chain:
                self._apply_pieces.lower(params_spec, opt_state_spec, self.grad_pieces(params_spec)).compile()
            else:
                self._apply.lower(params_spec, opt_state_spec, params_spec).compile()

    def grad_pieces(self, tree) -> GradPieces:
        """A tree of shardings or of ``jax.ShapeDtypeStruct``s shaped like
        the parameters, cut as ``grads`` cuts its gradient where it is a
        chain: the head's leaves, a layer's ([1, 1, ...]: the stacked leaf's
        leading axes kept) from the last to the first, the embedding's."""

        def row(leaf):
            if isinstance(leaf, jax.sharding.Sharding):  # holds for a row as for the stack
                return leaf
            return jax.ShapeDtypeStruct((1, 1) + tuple(leaf.shape[2:]), leaf.dtype, sharding=getattr(leaf, "sharding", None))

        layer = jax.tree_util.tree_map(row, tree["layers"])
        return GradPieces(
            [{k: tree[k] for k in ("final_norm", "out")}]
            + [layer] * self.cfg.layers_per_stage
            + [{"embed": tree["embed"]}]
        )

    def grads_tree(self, grads) -> Dict[str, Any]:
        """``grads``' gradient as a tree shaped like the parameters: the
        pieces stacked where ``grads`` is a chain, else the tree it gave."""
        return grads_of_pieces(grads) if self._chain else grads

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
        """Loss + gradient (still on device, or still being computed: the
        call dispatches and does not wait). The gradient is a tree shaped
        like the parameters, or where the stack can be cut a layer at a time
        a :class:`GradPieces` in the order the chain's programs finish them
        (``grads_tree`` gives the parameters' shape; ``apply`` takes what
        this gave, averaged or not).

        The head's program is enqueued at once; the layers' and the tail's
        once the update before this step has ended (a wait of the update's
        own 30 ms at most, with the head already queued behind it, so the
        chip never waits): the runtime allocates a program's outputs when it
        is enqueued, and the last step's averaged gradient is still held by
        that update — enqueued all at once, this step's pieces and what the
        head keeps for the layers would lie beside it (13.2 GB where the one
        program peaks at 12.3 at ``olmo1b-4g``, PERF.md §6, PR 59)."""
        t0 = time.perf_counter()
        with jax.set_mesh(self.mesh):
            if self._chain:
                head, layer, tail = self._chain
                loss, self.last_stats, top, (dx, kept) = head(params, tokens)
                jax.block_until_ready(params["final_norm"])  # the update that gave these parameters has ended
                pieces = [top]
                for l in reversed(range(self.cfg.layers_per_stage)):
                    piece, dx = layer(params["layers"], np.int32(l), kept, dx)
                    pieces.append(piece)
                pieces.append(tail(params["embed"], tokens, dx))
                out = loss, GradPieces(pieces)
            else:
                *out, self.last_stats = self._value_and_grad(params, tokens)
        self._record_compute(t0, "grads")
        builds.annotate_counters()
        return tuple(out)

    def apply(self, params, opt_state, grads) -> Tuple[Any, Any]:
        """Apply (possibly host-averaged) grads, in the form ``grads`` gave
        them — pieces where it is a chain, else a tree shaped like the
        parameters; ``params`` and ``opt_state`` are donated."""
        t0 = time.perf_counter()
        with jax.set_mesh(self.mesh):
            out = (self._apply_pieces or self._apply)(params, opt_state, grads)
        self._record_compute(t0, "apply")
        return out
