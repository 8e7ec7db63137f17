"""The ``fused`` loop: ``TrainStep.step`` (grads + update in one program,
buffers donated) with no Manager — the plain single-worker run of the same
task. A unit is a block of steps that ends in ``block_until_ready``.
"""

from __future__ import annotations

from typing import Any, Dict, List

SPAN_NAMES = ("fused.step",)


class Loop:
    def __init__(self, ctx) -> None:
        import jax

        if ctx.groups != 1:
            raise ValueError("the fused loop has no cross-group exchange: groups must be 1")
        self._ts = ctx.ts
        self._params = ctx.ts.init_params(jax.random.PRNGKey(ctx.seed))
        self._opt = ctx.ts.init_opt(self._params)
        self._steps = 0

    def params(self) -> Any:
        return self._params

    def instrument(self, spans) -> None:
        self._ts.step = spans.wrap("fused.step", self._ts.step)

    def run_unit(self, batches: List[Any]) -> List[Dict[str, Any]]:
        import jax

        losses = []
        for tokens in batches:
            loss, self._params, self._opt = self._ts.step(
                self._params, self._opt, self._ts.shard_batch(tokens)
            )
            losses.append(loss)
        jax.block_until_ready((self._params, self._opt))
        self._steps += len(batches)
        return [
            {"loss": float(l), "committed": True, "participants": 1,
             "step": self._steps - len(batches) + i + 1}
            for i, l in enumerate(losses)
        ]

    def close(self) -> Dict[str, Any]:
        return {}
