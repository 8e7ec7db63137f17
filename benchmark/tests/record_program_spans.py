#!/usr/bin/env python3
"""How ``benchmark/tests/recorded_program_spans_v5e.xplane.pb`` was made: three
traced units of the benchmark's own ``ft`` loop (``loops/ft.py``, instrumented
as ``worker.py`` instruments it) at a small size on one TPU v5e chip — one
group, world size 1, 64 KB buckets so that a step has several — traced with
the options ``worker.py`` uses. Run on the chip; writes the trace to the
directory given and prints the program's ``tft.*`` events by thread, which is
what ``program_spans.py`` was written against.

    python3 benchmark/tests/record_program_spans.py chiprun_out/recorded
"""

import glob
import os
import shutil
import sys
import types

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path[:0] = [os.path.dirname(BENCH), BENCH]

TRANSFORMER = dict(
    vocab_size=256, d_model=64, n_layers=2, n_heads=2, head_dim=32, d_ff=128,
)


def main(out_dir: str) -> None:
    os.environ["TORCHFT_WIRE_BUCKET_BYTES"] = str(1 << 16)
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    import worker
    from common import load_module
    from torchft_tpu.coordination import LighthouseServer
    from torchft_tpu.models.transformer import TransformerConfig, init_params
    from torchft_tpu.parallel.mesh import MeshConfig, make_mesh
    from torchft_tpu.parallel.train_step import TrainStep

    print("device:", jax.devices()[0].platform, jax.devices()[0].device_kind)
    lighthouse = LighthouseServer(bind="[::]:0", min_replicas=1)
    os.environ["TORCHFT_LIGHTHOUSE"] = lighthouse.address()
    cfg = TransformerConfig(dtype=jnp.bfloat16, **TRANSFORMER)
    ts = TrainStep(cfg, optax.adamw(3e-4), make_mesh(MeshConfig(), devices=jax.devices()[:1]))
    n_params = sum(
        int(np.prod(l.shape))
        for l in jax.tree_util.tree_leaves(jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg)))
    )
    print("parameters:", n_params, "exchange bytes:", 4 * n_params)
    ctx = types.SimpleNamespace(group=0, groups=1, seed=0, ts=ts, n_params=n_params)
    loop = load_module(os.path.join(BENCH, "loops", "ft.py"), "bench_loop").Loop(ctx)
    loop.instrument(worker.Spans())
    rng = np.random.default_rng(0)

    def unit():
        tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (4, 128)), jnp.int32)
        with jax.profiler.TraceAnnotation("bench_unit"):
            (step,) = loop.run_unit([tokens])
        assert step["committed"], step

    for _ in range(2):  # compile and warm up outside the trace
        unit()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    opts.enable_hlo_proto = False
    tmp = out_dir + ".tmp"
    shutil.rmtree(tmp, ignore_errors=True)
    jax.profiler.start_trace(tmp, profiler_options=opts)
    for _ in range(3):
        unit()
    jax.profiler.stop_trace()
    loop.close()
    lighthouse.shutdown()

    (path,) = glob.glob(os.path.join(tmp, "plugins", "profile", "*", "*.xplane.pb"))
    os.makedirs(out_dir, exist_ok=True)
    dst = os.path.join(out_dir, "recorded_program_spans_v5e.xplane.pb")
    shutil.copy(path, dst)
    shutil.rmtree(tmp, ignore_errors=True)
    print("wrote", dst, os.path.getsize(dst), "bytes")
    for plane in jax.profiler.ProfileData.from_file(dst).planes:
        print("PLANE", plane.name, [(ln.name, len(list(ln.events))) for ln in plane.lines])
        for i, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith(("tft.", "ft.", "bench_unit", "jit_")):
                    print("  ", i, line.name, ev.name, ev.start_ns, ev.duration_ns, dict(ev.stats))


if __name__ == "__main__":
    main(sys.argv[1])
