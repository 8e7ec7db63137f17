"""FT × mesh composition — the HSDP story, TPU-native.

The reference splices its managed (elastic) process group into the torch
DeviceMesh so FSDP sees a "replicate" dim of dynamic size
(ManagedDeviceMesh / ft_init_device_mesh, process_group.py:1361-1606). The
TPU equivalent keeps the two planes apart by construction:

* inner: a fixed ``jax.sharding.Mesh`` (dp/fsdp/pp/ep/sp/tp) baked into the
  compiled TrainStep — never changes, never recompiles;
* outer: the Manager's replica axis on host buffers — gradients cross it
  via ``manager.allreduce`` between ``grads`` and ``apply``, so quorum
  membership changes are invisible to XLA.

``FTTrainer`` ties the two together and registers host-side state
snapshots with the Manager so live recovery (send/recv checkpoint) works
for sharded params: leaves are gathered to host for transfer and re-placed
with the TrainStep's shardings on load.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

from torchft_tpu.ddp import allreduce_gradients
from torchft_tpu.manager import Manager
from torchft_tpu.parallel.train_step import TrainStep
from torchft_tpu.telemetry import tracing
from torchft_tpu.telemetry.tracing import TRACER

__all__ = ["FTTrainer"]


class FTTrainer:
    def __init__(self, manager: Manager, train_step: TrainStep) -> None:
        self._manager = manager
        self._ts = train_step
        self._params: Optional[Any] = None
        self._opt_state: Optional[Any] = None

    # -- state (registered with the Manager for live recovery) --

    def init(self, rng) -> None:
        self._params = self._ts.init_params(rng)
        self._opt_state = self._ts.init_opt(self._params)
        self._manager.set_state_dict_fns(self.load_state_dict, self.state_dict)
        if hasattr(self._manager, "set_heal_warmup"):
            self._manager.set_heal_warmup(self._heal_warmup)

    def _heal_warmup(self, spec_tree: Any) -> None:
        """Heal/compile overlap (docs/heal_plane.md): runs on a daemon
        thread as soon as the incoming checkpoint's header lands — AOT-
        compile the apply step from the transferred shapes while the
        stripes are still streaming, so the post-heal first step doesn't
        serialize recv → compile."""
        user = spec_tree.get("user") if isinstance(spec_tree, dict) else None
        if not isinstance(user, dict):
            return
        params, opt_state = user.get("params"), user.get("opt_state")
        if params is None or opt_state is None:
            return
        self._ts.warm_apply(params, opt_state)

    @property
    def params(self) -> Any:
        return self._params

    @property
    def opt_state(self) -> Any:
        return self._opt_state

    def state_dict(self) -> Dict[str, Any]:
        # hand the raw sharded jax.Arrays to the transports: flatten_state
        # ships each leaf per shard with its NamedSharding descriptor
        # (serialization.py "shards" infos — the DTensor-spec analogue,
        # pg_transport.py:104-114), so a sharded group never gathers the
        # full model onto one host and replicated copies ship once
        return {"params": self._params, "opt_state": self._opt_state}

    def load_state_dict(self, state: Dict[str, Any]) -> None:
        import jax

        from torchft_tpu.checkpointing.serialization import from_transfer_tree

        # rebuild sharded leaves shard-by-shard on this group's mesh, then
        # pin params to the step's shardings (no-op when already placed)
        state = from_transfer_tree(state, self._ts.mesh)
        self._params = jax.device_put(
            state["params"], self._ts._param_shardings
        )
        # opt_state shardings mirror params; let placement follow use.
        # NOTE (flake post-mortem, PR 2): transferred dense leaves stay as
        # UNCOMMITTED host arrays on purpose. Re-committing them onto the
        # live tree's shardings via device_put looks like the obvious
        # placement-parity fix for the healed replica's retrace churn, but
        # in a multi-controller group it is wrong: jit-output scalars
        # (e.g. adam's count) carry shardings that device_put resolves to
        # THIS process's single local device, and the next `apply` then
        # rejects the mix of a global-mesh param with a single-device
        # opt leaf ("Received incompatible devices"). Leaving the leaves
        # uncommitted lets jit place them consistently on every process.
        self._opt_state = state["opt_state"]

    def _record_moe_counters(self, step: int, sync_span) -> None:
        """The router's load of the step just taken, fetched with the loss
        (the device is idle by then): on the ``loss_sync`` span in the
        Tracer ring and, as ``tft.moe.counters``, in a profiler trace. A
        model without dropless experts has no statistics and emits nothing."""
        stats = self._ts.last_stats
        if "tokens_per_expert" not in stats:
            return
        import numpy as np

        load = np.asarray(stats["tokens_per_expert"])  # [layers with experts, the router's experts]
        # token-expert rows the step routed, and those whose expert is held
        # here: all of them, but under a share (``n_experts_held``)
        rows_routed = int(load.sum())
        counters = dict(
            step=step,
            max_load=int(load.max()),
            min_load=int(load.min()),
            mean_load=float(load.mean()),
            balance_loss=float(stats["balance_loss"]),
            rows_routed=rows_routed,
            rows_held=int(np.asarray(stats["rows_held"]).sum()) if "rows_held" in stats else rows_routed,
        )
        if "window_live_share" in stats:  # under a share: rows held over the window's slots, a layer (the row moves cost by it)
            share = np.asarray(stats["window_live_share"])
            counters.update(window_live_share=float(share.mean()), window_live_share_max=float(share.max()))
        if "gate_zero_share" in stats:  # ReLU-gated experts: of the computed rows' gate lanes, the share left zero
            counters["gate_zero_share"] = float(np.mean(stats["gate_zero_share"]))
        sync_span.set(**counters)
        # an annotation takes its stats at entry: a zero-length one carries them
        with tracing.annotate("moe.counters", **counters):
            pass

    def _record_mtp_counters(self, step: int, sync_span) -> None:
        """The two terms of the step's loss where a multi-token-prediction
        module ran (``loss_and_stats``: ``main_loss``, ``mtp_loss``), fetched
        with the loss: on the ``loss_sync`` span in the Tracer ring and, as
        ``tft.mtp.counters``, in a profiler trace. Any other model emits nothing."""
        stats = self._ts.last_stats
        if "mtp_loss" not in stats:
            return
        counters = dict(
            step=step,
            main_loss=float(stats["main_loss"]),
            mtp_loss=float(stats["mtp_loss"]),
            mtp_weight=float(self._ts.cfg.mtp_loss_weight),
        )
        sync_span.set(**counters)
        with tracing.annotate("mtp.counters", **counters):
            pass

    def _record_gdn_counters(self, step: int, sync_span) -> None:
        """What the Gated DeltaNet mixers and a gated shared expert say of the
        step (``loss_and_stats``), fetched with the loss: the least log-decay
        of a position over the layers (``ops/kda.gdn_chunked`` is exact
        whatever it is; the per-channel kernels' two-factor form holds 80 nats
        over 16 positions), the mean write strength, the mean of the shared
        expert's gate — on the ``loss_sync`` span in the Tracer ring and, as
        ``tft.gdn.counters``, in a profiler trace. Any other model emits nothing."""
        stats = self._ts.last_stats
        if "gdn_decay_min" not in stats:
            return
        import numpy as np

        counters = dict(
            step=step,
            decay_min=float(np.min(stats["gdn_decay_min"])),
            beta_mean=float(np.mean(stats["gdn_beta_mean"])),
        )
        if "shared_gate_mean" in stats:
            counters["shared_gate_mean"] = float(np.mean(stats["shared_gate_mean"]))
        sync_span.set(**counters)
        with tracing.annotate("gdn.counters", **counters):
            pass

    def _record_loop_counters(self, step: int, sync_span) -> None:
        """What a looped stack's exit gate says of the step (``loss_and_stats``:
        ``exit_probs``, ``exit_entropy``, ``loss_by_step``), fetched with the
        loss: each exit's mean probability and its own cross entropy, one
        number a loop step (``exit_p1`` …, ``loss_step1`` …), and the exit
        distribution's mean entropy — on the ``loss_sync`` span in the Tracer
        ring and, as ``tft.loop.counters``, in a profiler trace. Any other
        model emits nothing."""
        stats = self._ts.last_stats
        if "exit_probs" not in stats:
            return
        import numpy as np

        counters = dict(step=step, ut_steps=int(self._ts.cfg.ut_steps), exit_entropy=float(stats["exit_entropy"]))
        for t, (p, loss) in enumerate(zip(np.asarray(stats["exit_probs"]), np.asarray(stats["loss_by_step"])), 1):
            counters[f"exit_p{t}"] = float(p)
            counters[f"loss_step{t}"] = float(loss)
        sync_span.set(**counters)
        with tracing.annotate("loop.counters", **counters):
            pass

    def _record_diffusion_counters(self, step: int, sync_span) -> None:
        """What block-diffusion training says of the step's noise
        (``loss_and_stats``: ``masked_share``, ``noise_weight_mean``,
        ``loss_masked_unweighted``), fetched with the loss: the share of
        positions that showed the mask id, the mean of their weights m/t (about
        1: a step far from it drew few blocks or small levels) and the masked
        positions' own cross entropy — on the ``loss_sync`` span in the Tracer
        ring and, as ``tft.diffusion.counters``, in a profiler trace. Any other
        model emits nothing."""
        stats = self._ts.last_stats
        if "masked_share" not in stats:
            return
        counters = dict(
            step=step,
            block=int(self._ts.cfg.diffusion_block),
            masked_share=float(stats["masked_share"]),
            noise_weight_mean=float(stats["noise_weight_mean"]),
            loss_masked_unweighted=float(stats["loss_masked_unweighted"]),
        )
        sync_span.set(**counters)
        with tracing.annotate("diffusion.counters", **counters):
            pass

    def _record_sparse_counters(self, step: int, sync_span) -> None:
        """What a learned key selector says of the step (``loss_and_stats``:
        ``lm_loss``, ``indexer_loss``, ``selected_mean``, ``selected_over_k``,
        ``sparse_tiles_live_share``), fetched with the loss: the next-token
        loss apart from the selector's own term (summed over layers), the keys
        a query attended to, the rows a tie at the threshold gave more than k,
        and the share of the kernel's causal key tiles that hold a selected
        pair (the mean over layers: what a walk that steps over dead tiles
        saves) — on the ``loss_sync`` span in the Tracer ring and, as
        ``tft.sparse.counters``, in a profiler trace. Any other model emits
        nothing."""
        stats = self._ts.last_stats
        if "indexer_loss" not in stats:
            return
        import numpy as np

        counters = dict(
            step=step,
            lm_loss=float(stats["lm_loss"]),
            indexer_loss=float(np.sum(stats["indexer_loss"])),
            selected_mean=float(stats["selected_mean"]),
            selected_over_k=float(stats["selected_over_k"]),
            sparse_tiles_live_share=float(np.mean(stats["sparse_tiles_live_share"])),
        )
        sync_span.set(**counters)
        with tracing.annotate("sparse.counters", **counters):
            pass

    # -- drive --

    def step(self, tokens) -> Tuple[float, bool]:
        """One fault-tolerant step: quorum → device grads → cross-group
        average (host) → commit gate → device update. Returns
        (loss, committed).

        ``grads`` only dispatches, so the exchange starts while the chip
        computes. Where the stack can be cut a layer at a time ``grads`` is
        a chain of programs and its gradient comes in pieces, the head's
        first (``TrainStep.grads``): a piece's device-to-host copy starts as
        its program ends, so the gradients land on the host while the chip
        runs the layers below, and what the step adds to the backward is the
        last pieces' landing and the rings. Any other stack is one program:
        the exchange's first landing wait is the wait for all of it, and
        nothing of the exchange lies under the backward. Either way every
        bucket is awaited before ``should_commit``'s result is used and
        before ``apply``."""
        mgr = self._manager
        # the step's own timeline (docs/observability.md): one span per piece
        # of the step, in the Tracer ring and — as tft.<name> — in a profiler
        # trace.
        label = mgr.current_step()
        with TRACER.span("step", step_num=label) as step_span:
            # the call only: the quorum itself runs on the quorum thread
            with TRACER.span("quorum.start"):
                mgr.start_quorum()
            with TRACER.span("shard_batch"):
                tokens = self._ts.shard_batch(tokens)
            with TRACER.span("grads"):  # dispatch only
                loss, grads = self._ts.grads(self._params, tokens)
            # cross the elastic replica axis on host (the `exchange` span)
            grads = allreduce_gradients(mgr, grads)
            with TRACER.span("commit"):
                committed = mgr.should_commit()
            if committed:
                with TRACER.span("apply"):  # dispatch only
                    self._params, self._opt_state = self._ts.apply(
                        self._params, self._opt_state, grads
                    )
            with TRACER.span("loss_sync") as sync_span:
                loss = float(loss)
                self._record_moe_counters(label, sync_span)
                self._record_mtp_counters(label, sync_span)
                self._record_gdn_counters(label, sync_span)
                self._record_loop_counters(label, sync_span)
                self._record_diffusion_counters(label, sync_span)
                self._record_sparse_counters(label, sync_span)
            step_span.set(committed=committed)
        return loss, committed
