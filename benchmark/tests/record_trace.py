#!/usr/bin/env python3
"""How ``benchmark/tests/recorded_v5e.xplane.pb`` and
``recorded_scopes_v5e.xplane.pb`` were made: three annotated units of a small
jitted program on one TPU v5e chip, traced with the options ``worker.py``
uses. Run on the chip; writes the trace to the directory given and prints what
the planes and lines of such a trace look like, which is what
``reduce_trace.py`` was written against.

    python3 benchmark/tests/record_trace.py chiprun_out/recorded          # the scan
    python3 benchmark/tests/record_trace.py chiprun_out/recorded scopes   # the scoped step

The scoped step (PR 25) is a training step in miniature under the scope names
the program uses: a ``lax.scan`` over three ``jax.checkpoint``ed layers with an
``attn`` and an ``ffn`` scope, a ``head_loss``, the gradient of it all, and an
``optimizer`` update — so that the trace holds a ``while`` whose body's ops carry
scopes, ops under ``rematted_computation/<scope>`` and ``transpose(jvp(<scope>))``,
and ops of no scope.
"""

import glob
import os
import shutil
import sys
import time

import jax
import jax.numpy as jnp


def scan_step():
    @jax.jit
    def step(x):
        def body(c, _):
            return jnp.tanh(c @ c) * 0.5, ()

        y, _ = jax.lax.scan(body, x, None, length=3)
        return y + 1.0

    x = jnp.ones((1024, 1024), jnp.bfloat16)
    return (lambda: step(x)), "recorded_v5e.xplane.pb"


def scoped_step():
    def loss_fn(params, x):
        def layer(h, lp):
            with jax.named_scope("attn"):
                h = h + jnp.tanh(h @ lp["a"])
            with jax.named_scope("ffn"):
                h = h + jax.nn.silu(h @ lp["w1"]) @ lp["w2"]
            return h, ()

        h, _ = jax.lax.scan(jax.checkpoint(layer), x, params["layers"])
        with jax.named_scope("head_loss"):
            return jnp.mean(jnp.square((h @ params["out"]).astype(jnp.float32)))

    @jax.jit
    def tft_scoped(params, x):
        loss, grads = jax.value_and_grad(loss_fn)(params, x)
        with jax.named_scope("optimizer"):
            params = jax.tree_util.tree_map(lambda p, g: p - 0.01 * g, params, grads)
        return loss, params

    keys = jax.random.split(jax.random.PRNGKey(0), 5)
    w = lambda k, *shape: (jax.random.normal(k, shape) * 0.02).astype(jnp.bfloat16)
    params = {
        "layers": {"a": w(keys[0], 3, 512, 512), "w1": w(keys[1], 3, 512, 1024), "w2": w(keys[2], 3, 1024, 512)},
        "out": w(keys[3], 512, 2048),
    }
    x = w(keys[4], 1024, 512)
    return (lambda: tft_scoped(params, x)), "recorded_scopes_v5e.xplane.pb"


def main(out_dir: str, which: str = "scan") -> None:
    print("device:", jax.devices()[0].platform, jax.devices()[0].device_kind)
    step, file_name = scoped_step() if which == "scopes" else scan_step()
    jax.block_until_ready(step())
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    opts.enable_hlo_proto = False
    tmp = out_dir + ".tmp"
    shutil.rmtree(tmp, ignore_errors=True)
    jax.profiler.start_trace(tmp, profiler_options=opts)
    for _ in range(3):
        with jax.profiler.TraceAnnotation("bench_unit"):
            with jax.profiler.TraceAnnotation("ft.grads"):
                if which == "scopes":
                    time.sleep(0.003)  # the device plane's clock runs ~1.3 ms ahead: keep a program in its unit
                jax.block_until_ready(step())
            with jax.profiler.TraceAnnotation("ft.exchange"):
                time.sleep(0.002)
    jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(tmp, "plugins", "profile", "*", "*.xplane.pb"))
    os.makedirs(out_dir, exist_ok=True)
    dst = os.path.join(out_dir, file_name)
    shutil.copy(path, dst)
    shutil.rmtree(tmp, ignore_errors=True)
    print("wrote", dst, os.path.getsize(dst), "bytes")
    for plane in jax.profiler.ProfileData.from_file(dst).planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            events = list(line.events)
            print("  LINE", line.name, len(events), "events")
            for ev in events[:8]:
                print("     ", ev.name[:70], ev.start_ns, ev.duration_ns, list(ev.stats)[:4])


if __name__ == "__main__":
    main(*sys.argv[1:3])
