"""Inside the scope ``moe``: what the three readers ``moe_experts_device_s``,
``moe_route_device_s`` and ``moe_experts_roofline`` share.

The program nests four ``jax.named_scope``s under ``moe``
(``torchft_tpu/models/transformer._ffn_moe``, ``ops/layers.moe_dropless``):
``router`` (the router's matmul, softmax, top-k, the balance term),
``dispatch`` (ordering the token-expert rows by expert, counting them,
gathering the rows), ``experts`` (the three grouped matmuls and the gate) and
``combine`` (putting the rows back, weighting and summing them per token).
``xplane_meta.scope_of`` gives all of them to ``moe`` — ``ffn_device_s`` reads
the whole — and ``subscopes.py``, the one reader of scopes inside a scope,
splits that whole among the four, with ``moe`` itself for what names none.

The experts' least work is counted here beside its hand-worked test
(``tests/test_moe_scopes.py``), from the configuration's count of the scope
(``opcount.for_config``) less the router's part.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import opcount
import subscopes

MOE = "moe"
SUBSCOPES = ("router", "dispatch", "experts", "combine")


def subscope_of(op_name: Optional[str]) -> Optional[str]:
    """``None`` for an op that does not count to ``moe``; else the innermost
    of :data:`SUBSCOPES` on its path, or ``"moe"`` where it names none."""
    return subscopes.innermost(op_name, MOE, SUBSCOPES)


def _units_by_subscope(path: str):
    """Per traced unit, device seconds by subscope (mean over chips); None
    where the trace has no unit or no device op."""
    return subscopes.units(path, MOE, SUBSCOPES)


def device_seconds(run) -> Optional[Dict[str, float]]:
    """Device seconds a step by subscope of ``moe`` (``router``, ``dispatch``,
    ``experts``, ``combine``, and ``moe`` for what names none), as
    ``subscopes.seconds`` reduces the traced units' rows."""
    return subscopes.seconds(run, MOE, SUBSCOPES, rows_of=_units_by_subscope)


def experts_seconds(run) -> Optional[float]:
    by = device_seconds(run)
    return by.get("experts") if by else None


def route_seconds(run) -> Optional[float]:
    """Everything of ``moe`` that is not the experts' grouped matmuls."""
    by = device_seconds(run)
    if not by:
        return None
    return sum(sec for sub, sec in by.items() if sub != "experts")


def experts_flops_per_step(tc: Dict[str, Any], batch: int, seq: int, counts=opcount) -> float:
    """Forward + backward operations of the experts alone: what ``counts``
    (``opcount.py``, or the configuration's own: ``opcount.for_config``)
    counts for ``moe`` less the router's ``d x E`` matmul (6 per parameter
    per token). Recomputation and padding to a tile are not counted."""
    router = 6.0 * tc["n_layers"] * tc["d_model"] * tc["n_experts"]
    return (counts.flops_per_token_by_scope(tc, seq)[MOE] - router) * batch * seq


def experts_bytes_per_step(tc: Dict[str, Any], batch: int, seq: int, counts=opcount) -> float:
    """The experts' share of ``counts.bytes_per_step_by_scope``'s ``moe``:
    all E experts' weights read forward and backward and their gradient
    written, and the activations at the scope's edge — less the router's
    weights, three times as the scope counts them."""
    c = opcount.ITEMSIZE[tc.get("dtype", "bfloat16")]
    router = 3 * c * tc["n_layers"] * tc["d_model"] * tc["n_experts"]
    return counts.bytes_per_step_by_scope(tc, batch, seq)[MOE] - router


def experts_roofline(run) -> Optional[float]:
    """The least time the published peaks allow the experts a step — their
    operations over the bf16 peak or their bytes over the HBM bandwidth,
    whichever is longer — as a share of ``moe_experts_device_s``. Never
    clamped."""
    tc = run.config["program"]["transformer_config"]
    seconds = experts_seconds(run)
    if run.peaks is None or not seconds or not tc.get("n_experts"):
        return None
    batch, seq = int(run.traffic["batch"]), int(run.traffic["seq"])
    counts = opcount.for_config(run.config)
    least = max(
        experts_flops_per_step(tc, batch, seq, counts) / run.peaks["bf16_flops_per_s"],
        experts_bytes_per_step(tc, batch, seq, counts) / run.peaks["hbm_bytes_per_s"],
    )
    chips = int(run.config["layout"]["chips_per_group"])
    return 100.0 * least / (seconds * chips)
