"""The ``ft`` loop: what ``examples/train_hsdp.py`` builds — StoreServer →
CollectivesTcp → Manager → FTTrainer over the TrainStep — driven one
``FTTrainer.step`` per unit. One instance per replica group (one process).
"""

from __future__ import annotations

import os
from datetime import timedelta
from typing import Any, Dict, List

# host spans a traced run records, innermost names the gaps are given to
SPAN_NAMES = (
    "ft.start_quorum", "ft.grads", "ft.exchange", "ft.should_commit", "ft.apply",
)


class Loop:
    def __init__(self, ctx) -> None:
        import jax

        from torchft_tpu.collectives import CollectivesTcp
        from torchft_tpu.manager import Manager
        from torchft_tpu.parallel.ft import FTTrainer
        from torchft_tpu.store import StoreServer

        self._ctx = ctx
        self._store = None
        store_addr = os.environ.get("TORCHFT_STORE_ADDR")
        if store_addr is None:
            self._store = StoreServer()
            store_addr = self._store.address()
        # train_hsdp's deadline: one timeout for every collective, vote and
        # heal transfer; a heal moves 12 B/param, budgeted at 50 MB/s
        timeout = timedelta(seconds=30 + 12 * ctx.n_params / 50e6)
        self.collectives = CollectivesTcp(timeout=timeout)
        self.manager = Manager(
            collectives=self.collectives,
            load_state_dict=None,
            state_dict=None,
            min_replica_size=ctx.groups,
            replica_id=f"bench_{ctx.group}",
            store_addr=store_addr,
            rank=int(os.environ.get("RANK", 0)),
            world_size=int(os.environ.get("WORLD_SIZE", 1)),
            timeout=timeout,
        )
        self.trainer = FTTrainer(self.manager, ctx.ts)
        self.trainer.init(jax.random.PRNGKey(ctx.seed))

    def params(self) -> Any:
        return self.trainer.params

    def instrument(self, spans) -> None:
        """Traced run only: spans around the calls into each layer, from
        here, without touching the program. ``grads`` blocks on its result
        so that device time and the host trip of the gradients separate."""
        import torchft_tpu.parallel.ft as ft_mod

        ts, mgr = self._ctx.ts, self.manager
        ts.grads = spans.wrap("ft.grads", ts.grads, block=True)
        ts.apply = spans.wrap("ft.apply", ts.apply)
        mgr.start_quorum = spans.wrap("ft.start_quorum", mgr.start_quorum)
        mgr.should_commit = spans.wrap("ft.should_commit", mgr.should_commit)
        # FTTrainer.step calls the name its module imported
        ft_mod.allreduce_gradients = spans.wrap(
            "ft.exchange", ft_mod.allreduce_gradients
        )

    def run_unit(self, batches: List[Any]) -> List[Dict[str, Any]]:
        out = []
        for tokens in batches:
            loss, committed = self.trainer.step(tokens)
            out.append(
                {
                    "loss": float(loss),
                    "committed": bool(committed),
                    "participants": int(self.manager.num_participants()),
                    "step": int(self.manager.current_step()),
                }
            )
        return out

    def close(self) -> Dict[str, Any]:
        from torchft_tpu import telemetry

        extras = {
            "plane_info": self.collectives.plane_info(),
            "ledger_rows": telemetry.LEDGER.dump()["rows"],
            "heal_events": telemetry.EVENTS.recent("heal_end"),
            "exchange_bytes": 4 * self._ctx.n_params,
            # the program's own record of each exchange (its ring of spans), traced or not
            "exchange_spans": [
                {"dur_s": s["dur_s"], **s.get("attrs", {})}
                for s in telemetry.TRACER.recent("exchange")
            ],
        }
        self.manager.shutdown(wait=False)
        if self._store is not None:
            self._store.shutdown()
        return extras
