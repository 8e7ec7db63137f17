"""Commit-gated optimizer — the OptimizerWrapper analogue for optax.

The reference wraps a torch optimizer so ``zero_grad()`` starts the quorum
and ``step()`` only applies when the group votes to commit
(torchft/optim.py:48-55). Torch mutates the model in place, which is also
how a healed checkpoint reaches the optimizer mid-step; in JAX the state is
immutable pytrees, so this wrapper *owns* them — recovery (which lands via
the manager's ``load_state_dict`` callback inside ``should_commit``)
replaces the internal pytrees before the update applies::

    opt = ManagedOptimizer(manager, optax.adam(1e-3))
    opt.init(params)                      # registers state fns on the manager
    for batch in data:
        opt.begin_step()                  # zero_grad() analogue: start quorum
        loss, grads = value_and_grad_fn(opt.params, batch)
        opt.step(grads)                   # average + commit gate + update

``step`` averages gradients across replica groups through the Manager and
applies the optax update only if ``should_commit()`` — otherwise the state
is untouched and the step is discarded.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

from torchft_tpu.ddp import allreduce_gradients
from torchft_tpu.manager import Manager
from torchft_tpu.wire_codec import ErrorFeedback, ErrorFeedbackBinding

__all__ = ["ManagedOptimizer"]


class ManagedOptimizer:
    def __init__(
        self,
        manager: Manager,
        tx,
        register_state: bool = True,
        error_feedback: "Optional[ErrorFeedback | bool]" = None,
    ) -> None:
        """``tx`` is an ``optax.GradientTransformation``. With
        ``register_state`` (default) ``init`` wires this wrapper's
        state_dict/load_state_dict into the manager so live recovery
        restores params and optimizer state automatically; pass False if the
        user snapshot covers more than the optimizer (then include
        ``opt.state_dict()`` in it).

        ``error_feedback``: residual compensation for a lossy wire codec
        (docs/wire_plane.md). Default (None) AUTO-enables when the
        manager's data plane reports a lossy codec — the convergence-
        preserving configuration — unless ``TORCHFT_WIRE_EF=0``; pass
        ``False`` to force off or a prebuilt
        :class:`~torchft_tpu.wire_codec.ErrorFeedback` to share one."""
        self._manager = manager
        self._tx = tx
        self._register_state = register_state
        self._apply = None
        self._params: Optional[Any] = None
        self._opt_state: Optional[Any] = None
        # wire-plane error feedback (accumulators ride state_dict through
        # heal/checkpoint; pending residuals follow the commit lineage)
        # auto/lazy/CMA-gate semantics live in the shared binding
        # (wire_codec.ErrorFeedbackBinding) — LocalSGD resolves the same
        # way, so the two wrappers cannot drift
        self._efb = ErrorFeedbackBinding(manager, error_feedback)

    @property
    def error_feedback(self) -> Optional[ErrorFeedback]:
        return self._efb.instance

    # -- state --

    @property
    def params(self) -> Any:
        assert self._params is not None, "call init(params) first"
        return self._params

    @property
    def opt_state(self) -> Any:
        return self._opt_state

    def init(self, params: Any) -> None:
        self._params = params
        self._opt_state = self._tx.init(params)
        if self._register_state:
            self._manager.set_state_dict_fns(self.load_state_dict, self.state_dict)

    def state_dict(self) -> Dict[str, Any]:
        out = {"params": self._params, "opt_state": self._opt_state}
        ef = self.error_feedback
        if ef is not None:
            # committed residuals only (state_dict() on ErrorFeedback
            # excludes pending) — a heal/checkpoint restart must resume
            # the compensation stream, not restart it from zero
            out["ef"] = ef.state_dict()
        return out

    def load_state_dict(self, state: Dict[str, Any]) -> None:
        self._params = state["params"]
        self._opt_state = state["opt_state"]
        ef = self.error_feedback
        if ef is None and "ef" in state:
            # lazy auto mode (e.g. proxied backend): the heal may land
            # before the first live() — adopt the state's accumulators,
            # don't drop them
            ef = self._efb.ensure_for_state(state["ef"])
        if ef is not None:
            if "ef" in state:
                ef.load_state_dict(state["ef"])
            else:
                # healed from a peer without EF state: start clean rather
                # than compensate with residuals of a dead lineage
                ef.load_state_dict({"codec": None, "acc": {}})

    # -- step --

    def begin_step(self, allow_heal: bool = True, shrink_only: bool = False) -> None:
        """Start the (async) quorum — call before the forward pass so the
        RPC overlaps compute (the reference hooks this into zero_grad)."""
        self._manager.start_quorum(allow_heal=allow_heal, shrink_only=shrink_only)

    def step(self, grads: Any, average: bool = True) -> Any:
        """Average ``grads`` across replica groups, then apply the update
        iff the step commits. Returns the current params (healed and/or
        updated). Pass ``average=False`` if the gradients already went
        through ``manager.allreduce``."""
        m = self._manager
        ef = self._efb.live()
        if average:
            grads = allreduce_gradients(m, grads, error_feedback=ef)
        committed = m.should_commit()
        # should_commit may have healed: self._params now reflects the
        # recovered state; the gradient applied to it is the participants'
        # average (a healing replica contributed zeros)
        ef_inst = self.error_feedback
        if ef_inst is not None:
            # heal inside should_commit restored EF state already (via
            # load_state_dict); commit/rollback is then a no-op on the
            # cleared pending set
            if committed:
                ef_inst.commit()
            else:
                ef_inst.rollback()
        if committed:
            self._params, self._opt_state = self._apply_update(
                self._params, self._opt_state, grads
            )
        return self._params

    def _apply_update(self, params: Any, opt_state: Any, grads: Any):
        # non-donating on purpose: the input pytrees double as the live
        # recovery snapshot — they must stay alive across the update
        if self._apply is None:
            import jax
            import optax

            tx = self._tx

            def apply(params, opt_state, grads):
                updates, new_state = tx.update(grads, opt_state, params)
                return optax.apply_updates(params, updates), new_state

            self._apply = jax.jit(apply)
        return self._apply(params, opt_state, grads)
