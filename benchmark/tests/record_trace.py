#!/usr/bin/env python3
"""How ``benchmark/tests/recorded_v5e.xplane.pb`` was made: three annotated
units of a small jitted loop on one TPU v5e chip, traced with the options
``worker.py`` uses. Run on the chip; writes the trace to the directory given
and prints what the planes and lines of such a trace look like, which is
what ``reduce_trace.py`` was written against.

    python3 benchmark/tests/record_trace.py chiprun_out/recorded
"""

import glob
import os
import shutil
import sys
import time

import jax
import jax.numpy as jnp


def main(out_dir: str) -> None:
    print("device:", jax.devices()[0].platform, jax.devices()[0].device_kind)

    @jax.jit
    def step(x):
        def body(c, _):
            return jnp.tanh(c @ c) * 0.5, ()

        y, _ = jax.lax.scan(body, x, None, length=3)
        return y + 1.0

    x = jnp.ones((1024, 1024), jnp.bfloat16)
    jax.block_until_ready(step(x))
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
                jax.block_until_ready(step(x))
            with jax.profiler.TraceAnnotation("ft.exchange"):
                time.sleep(0.002)
    jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(tmp, "plugins", "profile", "*", "*.xplane.pb"))
    os.makedirs(out_dir, exist_ok=True)
    dst = os.path.join(out_dir, "recorded_v5e.xplane.pb")
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
    main(sys.argv[1])
