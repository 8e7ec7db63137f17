"""Pipeline parallelism: a GPipe-style microbatched ring over the ``pp``
mesh axis.

The reference has no pipeline support (SURVEY.md §2.3 — PP: "No"); this is
part of the intra-group parallelism the TPU framework owns. Design: stage
parameters carry a leading ``[pp, ...]`` axis sharded over the ``pp`` mesh
axis; inside a partial-manual ``shard_map`` each stage runs every tick,
activations hop stage→stage via ``ppermute``, and microbatch m exits stage
P-1 at tick ``m + P - 1``. The fill/drain bubble is the standard GPipe
cost: utilization M / (M + P - 1) for M microbatches.

Reverse-mode AD through the scan + ppermute gives the backward pipeline
automatically (transposed permutes run the ring in reverse).
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

__all__ = ["pipeline_forward"]


def pipeline_forward(
    stage_params: Any,
    x_mb: jnp.ndarray,
    stage_fn: Callable[[Any, jnp.ndarray], jnp.ndarray],
    mesh,
    axis: str = "pp",
    sp_axis: str = "sp",
    head_fn: Callable[..., Any] = None,
    head_params: Any = None,
    head_extras: tuple = (),
) -> Any:
    """Run microbatches through the stage pipeline.

    Args:
        stage_params: pytree, every leaf with leading axis ``pp_size``
            (sharded ``P(axis, ...)``)
        x_mb: ``[M, mb, S, D]`` microbatched activations (replicated over
            ``axis``; other mesh axes GSPMD-sharded as usual)
        stage_fn: ``(params_for_one_stage, [mb, S, D]) -> [mb, S, D]``.
            When the mesh has ``sp_axis`` > 1, the sequence axis is ALSO
            manual inside this region (Shardy rejects nested manual
            regions), so stage_fn sees the local S/sp block and must use
            sp-local ops (ring_attention_local, local positions).
        head_fn: optional ``(head_params, outputs, *head_extras) -> pytree``
            applied to the final-stage outputs INSIDE the manual region.
            Leaves must be sums over local elements (e.g. an NLL sum and a
            token count): they are summed across the manual axes and
            returned replicated. This is the cheap exit path — a scalar
            psum instead of replicating the full ``[M, mb, S, D]``
            activations over ``axis`` (which costs an O(activations)
            collective purely to make the result location-independent).
        head_params: pytree for ``head_fn``, replicated over the manual
            axes (sharding over auto axes, e.g. tp, passes through GSPMD).
        head_extras: extra arrays for ``head_fn``, microbatched like
            ``x_mb`` (leading M, sequence axis sp-sharded if sp > 1).
    Returns:
        Without ``head_fn``: ``[M, mb, S, D]`` outputs of the final stage,
        replicated over ``axis``. With ``head_fn``: its reduced pytree.
    """
    pp = mesh.shape[axis]
    for leaf in jax.tree_util.tree_leaves(stage_params):
        if leaf.shape[0] != pp:
            raise ValueError(
                f"stage_params leading axis {leaf.shape[0]} != mesh {axis} "
                f"size {pp}: the model was configured for a different "
                f"pipeline depth than the mesh provides"
            )
    sp = mesh.shape.get(sp_axis, 1)
    if pp == 1:
        squeezed = jax.tree_util.tree_map(lambda a: a[0], stage_params)
        out = jax.vmap(lambda x: stage_fn(squeezed, x))(x_mb)
        if head_fn is None:
            return out
        if sp == 1:
            # no manual axes: local == global, sums need no reduction
            return head_fn(head_params, out, *head_extras)
        # keep the head's contract (it runs inside a manual region and may
        # use axis_index(sp)): manualize sp alone and psum its reductions
        act_spec1 = P(None, None, sp_axis, None)
        extra_spec1 = P(None, None, sp_axis)

        def sp_head(hp, o, *e):
            res = head_fn(hp, o, *e)
            return jax.tree_util.tree_map(
                lambda a: jax.lax.psum(a, sp_axis), res
            )

        return jax.shard_map(
            sp_head,
            in_specs=(
                jax.tree_util.tree_map(lambda _: P(), head_params),
                act_spec1,
                *[extra_spec1 for _ in head_extras],
            ),
            out_specs=P(),
            axis_names={sp_axis},
        )(head_params, out, *head_extras)

    m = x_mb.shape[0]
    ticks = m + pp - 1

    def per_stage(params_local, x_all, head_params, *extras):
        # params_local leaves: [1, ...] (this stage's slice) -> drop axis
        params_local = jax.tree_util.tree_map(lambda a: a[0], params_local)
        my = jax.lax.axis_index(axis)
        is_first = my == 0
        is_last = my == pp - 1
        perm = [(r, (r + 1) % pp) for r in range(pp)]

        def tick(carry, t):
            cur, outputs = carry
            feed_idx = jnp.clip(t, 0, m - 1)
            inp = jnp.where(
                is_first, jax.lax.dynamic_index_in_dim(x_all, feed_idx, 0, False), cur
            )
            y = stage_fn(params_local, inp)
            out_idx = t - (pp - 1)
            ci = jnp.clip(out_idx, 0, m - 1)
            valid = is_last & (out_idx >= 0)
            prev = jax.lax.dynamic_index_in_dim(outputs, ci, 0, False)
            outputs = jax.lax.dynamic_update_index_in_dim(
                outputs, jnp.where(valid, y, prev), ci, 0
            )
            cur = jax.lax.ppermute(y, axis, perm)
            return (cur, outputs), ()

        # initial carries must be VMA-typed as varying over every manual
        # axis the scan outputs vary over; deriving from x_all (zeroed, XLA
        # folds it) inherits the right set, then add 'pp' which enters via
        # axis_index/ppermute
        cur0, out0 = jax.lax.pcast(
            (x_all[0] * 0, x_all * 0), (axis,), to="varying"
        )
        (_, outputs), _ = jax.lax.scan(
            tick, (cur0, out0), jnp.arange(ticks)
        )
        if head_fn is not None:
            # the cheap exit: reduce on the last stage, psum the (scalar)
            # reductions over every manual axis — non-last stages computed
            # on zeros and are masked out; sp blocks each contribute their
            # local partial sum
            res = head_fn(head_params, outputs, *extras)
            reduce_axes = (axis,) if sp == 1 else (axis, sp_axis)
            return jax.tree_util.tree_map(
                lambda a: jax.lax.psum(
                    jnp.where(is_last, a, jnp.zeros_like(a)), reduce_axes
                ),
                res,
            )
        # only the last stage holds real outputs; replicate over pp
        outputs = jnp.where(is_last, outputs, jnp.zeros_like(outputs))
        return jax.lax.psum(outputs, axis)

    param_specs = jax.tree_util.tree_map(lambda _: P(axis), stage_params)
    manual = {axis} if sp == 1 else {axis, sp_axis}
    act_spec = P() if sp == 1 else P(None, None, sp_axis, None)
    # P() as a pytree-prefix spec: every head-output leaf comes back
    # replicated over the manual axes (they are full psum reductions)
    out_specs = act_spec if head_fn is None else P()
    head_param_specs = jax.tree_util.tree_map(lambda _: P(), head_params)
    extra_spec = P() if sp == 1 else P(None, None, sp_axis)
    extra_specs = tuple(extra_spec for _ in head_extras)
    # context mesh (set via jax.set_mesh) rather than an explicit one
    return jax.shard_map(
        per_stage,
        in_specs=(param_specs, act_spec, head_param_specs, *extra_specs),
        out_specs=out_specs,
        axis_names=manual,
    )(stage_params, x_mb, head_params, *head_extras)
