#!/usr/bin/env python3
"""How ``benchmark/tests/recorded_build_counters_v5e.xplane.pb`` was made
(PR 56): the program's own fused step of a small dense model on one TPU v5e
chip, built and stepped once outside the session (the set-up: its builds are in
the account before the first traced unit), then two annotated units of five
``TrainStep.step``s each under the options ``worker.py`` uses — every call
leaves a zero-length ``tft.build.counters`` with the account's totals — and
last, still inside the session but outside any unit, a SECOND ``TrainStep`` of
the same model stepped once: a rebuild as a respawned group or a changed shape
makes one, which shows as ``tft.build.trace`` / ``.lower`` / ``.compile`` on
the building thread with ``program`` and ``cache``. Run on the chip:

    python3 benchmark/tests/record_build_counters.py chiprun_out/recorded

Prints the ``tft.build.*`` events it recorded, which is what
``build_account.py`` and its readers were written against.
"""

import glob
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from torchft_tpu.utils.compile_cache import place_compile_cache

place_compile_cache()  # as every process that jits: the rebuild below is a load

import jax
import jax.numpy as jnp
import numpy as np
import optax

from torchft_tpu.models.transformer import TransformerConfig
from torchft_tpu.parallel.mesh import MeshConfig, make_mesh
from torchft_tpu.parallel.train_step import TrainStep

FILE_NAME = "recorded_build_counters_v5e.xplane.pb"
SIZES = dict(vocab_size=512, d_model=128, n_layers=1, n_heads=1, head_dim=128, d_ff=256, dtype=jnp.bfloat16)
UNITS, STEPS_PER_UNIT = 2, 5


def main(out_dir: str) -> None:
    print("device:", jax.devices()[0].platform, jax.devices()[0].device_kind)
    cfg, mesh = TransformerConfig(**SIZES), make_mesh(MeshConfig(), devices=jax.devices()[:1])

    def fresh():
        ts = TrainStep(cfg, optax.adamw(3e-4), mesh)
        params = ts.init_params(jax.random.PRNGKey(0))
        return ts, params, ts.init_opt(params)

    ts, params, opt = fresh()
    tokens = ts.shard_batch(jnp.asarray(np.random.default_rng(0).integers(0, 512, (2, 256)), jnp.int32))
    for _ in range(2):  # the second call looks its trace up again: its arguments are the first's outputs
        loss, params, opt = ts.step(params, opt, tokens)
    jax.block_until_ready(params)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    opts.enable_hlo_proto = False
    tmp = out_dir + ".tmp"
    shutil.rmtree(tmp, ignore_errors=True)
    jax.profiler.start_trace(tmp, profiler_options=opts)
    for _ in range(UNITS):
        with jax.profiler.TraceAnnotation("bench_unit"):
            time.sleep(0.003)  # the device plane's clock runs ~1.3 ms ahead: keep a program in its unit
            for _ in range(STEPS_PER_UNIT):
                loss, params, opt = ts.step(params, opt, tokens)
            jax.block_until_ready(params)
    ts2, params2, opt2 = fresh()
    jax.block_until_ready(ts2.step(params2, opt2, tokens))
    jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(tmp, "plugins", "profile", "*", "*.xplane.pb"))
    os.makedirs(out_dir, exist_ok=True)
    dst = os.path.join(out_dir, FILE_NAME)
    shutil.copy(path, dst)
    shutil.rmtree(tmp, ignore_errors=True)
    print("wrote", dst, os.path.getsize(dst), "bytes; loss", float(loss))
    for plane in jax.profiler.ProfileData.from_file(dst).planes:
        for i, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith("tft.build.") or ev.name == "bench_unit":
                    print("  ", i, line.name, ev.name, ev.start_ns, ev.duration_ns, dict(ev.stats))


if __name__ == "__main__":
    main(sys.argv[1])
