"""ResNet-18/CIFAR FT-loop benchmark, subprocess-isolated.

Round-4 review weak #1: the resnet row regressed 88 -> 49 steps/s with
the model file untouched — the row ran LAST inside bench.py's process,
after the headline, four long-context variants and the 647M scale model
had churned device/host state. Isolated re-measurement on the same box
gave 72–93 steps/s (median ~85), and re-running it after single variants
reproduced only noise-range dips — i.e. suite interference plus
unreported run variance, not a model regression. The fix is structural:
the row now runs in its OWN process (this module), first touch of the
chip, median of 5 reps with the runs list recorded.

Round-5 addendum: even isolated, per-invocation medians spanned 44-96
steps/s (within-invocation reps 53->98, first rep always lowest). At
b256 a step is ~10-15 ms against ~5 host round trips (quorum, commit, 3
dispatches), so the row is DISPATCH-LATENCY-bound — the regression gate
carries a wide tolerance for it (bench.py), and a real conv regression
must be judged against the runs list, not the median alone. Not
re-measured since the chip became directly attached (ROADMAP S6).

Run: ``python -m torchft_tpu.benchmarks.resnet_ft`` — prints one JSON
line.
"""

import json
import sys
import time


def run(steps: int = 20, warmup: int = 3, batch: int = 256, reps: int = 5) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from bench import _single_group_ft_runtime  # repo-root bench helpers
    from torchft_tpu.ddp import allreduce_gradients
    from torchft_tpu.models import resnet
    from torchft_tpu.utils.compile_cache import place_compile_cache

    place_compile_cache()

    runs = []
    for _ in range(reps):
        with _single_group_ft_runtime("bench_resnet") as manager:
            cfg = resnet.ResNetConfig(dtype=jnp.bfloat16)
            params, bn = resnet.init(jax.random.PRNGKey(0), cfg)
            tx = optax.sgd(0.1, momentum=0.9)
            opt_state = tx.init(params)

            rng = np.random.default_rng(0)
            x = jnp.asarray(rng.standard_normal((batch, 32, 32, 3)), jnp.float32)
            y = jnp.asarray(rng.integers(0, 10, batch), jnp.int32)

            @jax.jit
            def grads_fn(params, bn):
                (loss, new_bn), grads = jax.value_and_grad(
                    lambda p: resnet.loss_fn(p, bn, x, y, cfg), has_aux=True
                )(params)
                return loss, grads, new_bn

            @jax.jit
            def apply_fn(params, opt_state, grads):
                updates, opt_state = tx.update(grads, opt_state, params)
                return optax.apply_updates(params, updates), opt_state

            def ft_step(params, opt_state, bn):
                manager.start_quorum()
                loss, grads, new_bn = grads_fn(params, bn)
                grads = allreduce_gradients(manager, grads)
                if manager.should_commit():
                    params, opt_state = apply_fn(params, opt_state, grads)
                    bn = new_bn
                return loss, params, opt_state, bn

            for _ in range(warmup):
                loss, params, opt_state, bn = ft_step(params, opt_state, bn)
            jax.block_until_ready((loss, params, opt_state, bn))
            t0 = time.perf_counter()
            for _ in range(steps):
                loss, params, opt_state, bn = ft_step(params, opt_state, bn)
            jax.block_until_ready((loss, params, opt_state, bn))
            runs.append(steps / (time.perf_counter() - t0))
    runs.sort()
    sps = runs[len(runs) // 2]
    return {
        "steps_per_sec": round(sps, 4),
        "imgs_per_sec": round(sps * batch),
        "runs_steps_per_sec": [round(r, 4) for r in runs],
        "spread_pct": round((runs[-1] - runs[0]) / sps * 100.0, 1),
        "config": f"resnet18-cifar NHWC bf16 b{batch}, single-group FT "
        f"loop, OWN process (median of {reps}; dispatch-latency-bound "
        "— see module docstring for both post-mortems)",
    }


if __name__ == "__main__":
    import os

    sys.path.insert(
        0,
        os.path.dirname(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        ),
    )
    print(json.dumps(run()))
