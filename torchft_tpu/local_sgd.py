"""LocalSGD and DiLoCo — communication-reduced outer-loop synchronization.

Reference: torchft/local_sgd.py. LocalSGD (arxiv 1805.09767) runs
``sync_every`` purely-local optimizer steps, then averages *parameters*
across replica groups; DiLoCo (arxiv 2311.08105) instead averages
*pseudogradients* (the parameter delta since the last sync) and feeds them
to an outer optimizer.

Functional JAX shape: instead of hooking a torch optimizer, the caller
threads the params pytree through ``step()`` after every inner update::

    lsgd = LocalSGD(manager, sync_every=32)
    lsgd.save(params)                       # backup before the first step
    for batch in data:
        params, opt_state = inner_step(params, opt_state, batch)
        params = lsgd.step(params)          # averages every sync_every calls

A host-side backup of the last synced params makes failed syncs safe: if
the quorum doesn't commit, ``step`` returns the backup and the
``sync_every`` local steps are discarded (same guarantee as the reference).

DiLoCo note: this implementation uses the paper's pseudogradient sign
``backup − local`` (so the outer optimizer *descends* toward the inner
progress). The reference computes ``local − backup`` (local_sgd.py:211-215),
which inverts the outer step direction; we keep the paper semantics.
"""

from __future__ import annotations

import os
from typing import Any, Dict, Optional

import numpy as np

from torchft_tpu.checkpointing.serialization import to_host_tree as _to_host
from torchft_tpu.ddp import allreduce_gradients
from torchft_tpu.manager import Manager
from torchft_tpu.wire_codec import (
    ErrorFeedback,
    ErrorFeedbackBinding,
    LowRankErrorFeedback,
    lowrank_basis,
    lowrank_compress,
    lowrank_decompress,
    lowrank_eligible,
)

__all__ = ["LocalSGD", "DiLoCo"]


class LocalSGD:
    """Parameter averaging every ``sync_every`` local steps.

    The exchange keeps its bucket buffers from one sync to the next
    (``ddp._KeptBuckets``) when ``params`` are device arrays off the CPU
    backend: a model's size of host memory stays resident between syncs
    for one saved allocation per sync. Host ``params`` (and ``DiLoCo``'s
    pseudogradients) come back as slices of their buckets, so nothing is
    kept for them."""

    def __init__(
        self,
        manager: Manager,
        sync_every: int,
        error_feedback: "Optional[ErrorFeedback | bool]" = None,
    ) -> None:
        assert sync_every >= 1, "sync_every must be >= 1"
        self._manager = manager
        self._sync_every = sync_every
        self._local_step = 0
        self._backup: Optional[Any] = None
        self._just_healed = False
        # auto/lazy/CMA-gate semantics shared with ManagedOptimizer via
        # the one binding implementation (wire_codec.ErrorFeedbackBinding)
        self._efb = ErrorFeedbackBinding(manager, error_feedback)

    @property
    def error_feedback(self) -> Optional[ErrorFeedback]:
        return self._efb.instance

    def save(self, params: Any) -> None:
        """Snapshot ``params`` to host as the restore point. ``copy=True``
        guarantees the backup owns its buffers — without it a contiguous
        numpy params tree would alias the live params and in-place inner
        updates would silently corrupt the rollback state."""
        self._backup = _to_host(params, copy=True)

    def step(self, params: Any) -> Any:
        """Count one local optimizer step; every ``sync_every`` calls run a
        fault-tolerant sync and return the post-sync params."""
        if self._backup is None:
            raise RuntimeError("call save(params) before the first step")
        self._local_step += 1
        if self._local_step >= self._sync_every:
            params = self.sync(params)
            self._local_step = 0
        return params

    def sync(self, params: Any) -> Any:
        self._manager.start_quorum()
        # Functional-JAX heal gap the reference never has: torch heals
        # mutate the model in place, so the caller's reference aliases the
        # healed tensors — here `params` was captured BEFORE start_quorum
        # ran the (sync-mode) heal. A just-healed group's only consistent
        # state is the received backup: syncing from it contributes a zero
        # pseudogradient (DiLoCo) / the healed params (LocalSGD), exactly
        # what a replica with no inner progress since the backup should.
        if self._just_healed:
            params = _to_host(self._backup, copy=True)
        try:
            return self._perform_sync(params)
        finally:
            # also covers async-quorum heals that land inside
            # _perform_sync's commit barrier: the received backup is
            # reconciled there (backup := committed average), so the flag
            # must never leak into the next sync and discard real work
            self._just_healed = False

    # live-recovery snapshot (wire into Manager.set_state_dict_fns along
    # with the caller's params/inner state; the reference leaves this to
    # the integ harness — here it's part of the wrapper)
    def state_dict(self) -> dict:
        out = {"backup": self._backup, "local_step": self._local_step}
        if self._efb.instance is not None:
            out["ef"] = self._efb.instance.state_dict()
        return out

    def load_state_dict(self, state: dict) -> None:
        self._backup = _to_host(state["backup"], copy=True)
        self._local_step = int(state["local_step"])
        ef = self._efb.instance
        if ef is None and "ef" in state:
            # lazy auto mode: adopt the healed accumulators (see
            # ErrorFeedbackBinding.ensure_for_state), don't drop them
            ef = self._efb.ensure_for_state(state["ef"])
        if ef is not None:
            ef.load_state_dict(state.get("ef") or {"acc": {}})
        # the caller's local params are stale relative to this received
        # state; the next sync must start from the backup (see sync())
        self._just_healed = True

    def _perform_sync(self, params: Any) -> Any:
        ef = self._efb.live()
        # allreduce_gradients averages any pytree — here, the params
        averaged = allreduce_gradients(
            self._manager, params, error_feedback=ef
        )
        if self._manager.should_commit():
            if ef is not None:
                ef.commit()
            # the caller continues training on `averaged`; the backup must
            # not alias it or in-place inner steps corrupt the restore point
            self._backup = _to_host(averaged, copy=True)
            return averaged
        if ef is not None:
            ef.rollback()
        # discard the local steps; hand out a copy so in-place training on
        # the restored tree cannot corrupt the snapshot either
        return _to_host(self._backup, copy=True)


class DiLoCo(LocalSGD):
    """Pseudogradient averaging with an outer optimizer.

    ``outer_tx`` is an optax transformation (the paper uses SGD with
    Nesterov momentum). Requires ``use_async_quorum=False``: the outer step
    must start from a fully-healed state or replicas diverge
    (local_sgd.py:195-199).

    ``outer_rank`` (or ``TORCHFT_WIRE_OUTER_RANK``) enables the
    PowerSGD-style low-rank projection on the outer step — the one place
    in the stack where staleness already tolerates approximation
    (docs/wire_plane.md): each eligible 2-D pseudogradient leaf ships as
    its rank-r projection ``P = M·Q`` (the basis ``Q`` is derived from a
    seeded rng keyed on (leaf, outer-sync ordinal), so every replica
    group holds the same basis without communicating it), and a
    projection-error accumulator feeds the truncated component back into
    the next sync."""

    def __init__(
        self,
        manager: Manager,
        outer_tx,
        sync_every: int,
        error_feedback: "Optional[ErrorFeedback | bool]" = None,
        outer_rank: Optional[int] = None,
    ) -> None:
        if manager._use_async_quorum:
            raise ValueError(
                "DiLoCo requires synchronous quorum; construct the Manager "
                "with use_async_quorum=False"
            )
        super().__init__(manager, sync_every, error_feedback=error_feedback)
        self._outer_tx = outer_tx
        self._outer_state: Optional[Any] = None
        if outer_rank is None:
            try:
                outer_rank = int(os.environ.get("TORCHFT_WIRE_OUTER_RANK", "0"))
            except ValueError:
                outer_rank = 0
        self._outer_rank = max(0, outer_rank)
        self._lr_ef = LowRankErrorFeedback() if self._outer_rank else None
        # outer-sync ordinal: seeds each sync's projection basis. Synced
        # across groups because it only advances on COMMIT and rides
        # state_dict through heal/checkpoint like local_step does.
        self._outer_syncs = 0

    def save(self, params: Any) -> None:
        super().save(params)
        if self._outer_state is None:
            self._outer_state = self._outer_tx.init(self._backup)

    def _compress_pseudograd(self, leaves: list) -> "tuple[list, dict]":
        """Swap eligible 2-D leaves for their rank-r projections; returns
        (wire leaves, {leaf index: basis})."""
        bases: Dict[int, np.ndarray] = {}
        out = list(leaves)
        for li, leaf in enumerate(leaves):
            m = np.asarray(leaf)
            if m.dtype != np.float32 or not lowrank_eligible(
                m.shape, self._outer_rank
            ):
                continue
            assert self._lr_ef is not None
            m = self._lr_ef.compensate(f"l{li}", m)
            q = lowrank_basis(
                m.shape, self._outer_rank,
                seed=(li * 1_000_003 + self._outer_syncs) & 0x7FFFFFFF,
            )
            p = lowrank_compress(m, q)
            self._lr_ef.stage(f"l{li}", m, lowrank_decompress(p, q))
            bases[li] = q
            out[li] = p
        return out, bases

    def _perform_sync(self, params: Any) -> Any:
        import jax
        import optax

        assert self._backup is not None and self._outer_state is not None
        local = _to_host(params)
        # paper-sign pseudogradient: descend from the backup toward the
        # averaged inner progress
        pseudograd = jax.tree_util.tree_map(np.subtract, self._backup, local)
        ef = self._efb.live()
        bases: Dict[int, np.ndarray] = {}
        if self._outer_rank:
            leaves, treedef = jax.tree_util.tree_flatten(pseudograd)
            leaves, bases = self._compress_pseudograd(leaves)
            pseudograd = jax.tree_util.tree_unflatten(treedef, leaves)
        pseudograd = allreduce_gradients(
            self._manager, pseudograd, error_feedback=ef
        )
        if bases:
            leaves, treedef = jax.tree_util.tree_flatten(pseudograd)
            for li, q in bases.items():
                leaves[li] = lowrank_decompress(np.asarray(leaves[li]), q)
            pseudograd = jax.tree_util.tree_unflatten(treedef, leaves)

        if not self._manager.should_commit():
            if ef is not None:
                ef.rollback()
            if self._lr_ef is not None:
                self._lr_ef.rollback()
            return _to_host(self._backup, copy=True)
        if ef is not None:
            ef.commit()
        if self._lr_ef is not None:
            self._lr_ef.commit()
        self._outer_syncs += 1

        updates, self._outer_state = self._outer_tx.update(
            pseudograd, self._outer_state, self._backup
        )
        new_params = optax.apply_updates(self._backup, updates)
        self._backup = _to_host(new_params, copy=True)
        return new_params

    def outer_state(self) -> Any:
        return self._outer_state

    def state_dict(self) -> dict:
        d = super().state_dict()
        d["outer_state"] = self._outer_state
        d["outer_syncs"] = self._outer_syncs
        if self._lr_ef is not None:
            d["lr_ef"] = self._lr_ef.state_dict()
        return d

    def load_state_dict(self, state: dict) -> None:
        super().load_state_dict(state)
        self._outer_state = state["outer_state"]
        self._outer_syncs = int(state.get("outer_syncs", 0))
        if self._lr_ef is not None:
            self._lr_ef.load_state_dict(state.get("lr_ef") or {"acc": {}})
