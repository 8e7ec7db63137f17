#!/usr/bin/env python3
"""How ``benchmark/tests/recorded_moe_v5e.xplane.pb`` was made (PR 26): three
annotated units of one ``TrainStep.step`` each — the program's own fused step
of a small dropless-expert model (one layer, 8 experts, 2 a token, QK-norm,
the balance term) — on one TPU v5e chip, traced with the options ``worker.py``
uses. The ops carry the program's real ``op_name``s: ``moe/router``,
``moe/dispatch``, ``moe/experts`` (XLA's grouped-matmul kernel for
``ragged_dot``), ``moe/combine``, under ``checkpoint``, ``rematted_computation``
and ``transpose(jvp())``. Run on the chip:

    python3 benchmark/tests/record_moe_trace.py chiprun_out/recorded
"""

import glob
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

import jax
import jax.numpy as jnp
import numpy as np
import optax

from torchft_tpu.models.transformer import TransformerConfig
from torchft_tpu.parallel.mesh import MeshConfig, make_mesh
from torchft_tpu.parallel.train_step import TrainStep

FILE_NAME = "recorded_moe_v5e.xplane.pb"
SIZES = dict(
    vocab_size=512, d_model=256, n_layers=1, n_heads=2, head_dim=128, d_ff=256, n_experts=8, top_k=2,
    qk_norm=True, router_aux_loss_coef=0.01, norm_eps=1e-5, dtype=jnp.bfloat16,
)


def main(out_dir: str) -> None:
    print("device:", jax.devices()[0].platform, jax.devices()[0].device_kind)
    ts = TrainStep(TransformerConfig(**SIZES), optax.adamw(3e-4), make_mesh(MeshConfig(), devices=jax.devices()[:1]))
    params = ts.init_params(jax.random.PRNGKey(0))
    opt = ts.init_opt(params)
    tokens = ts.shard_batch(jnp.asarray(np.random.default_rng(0).integers(0, 512, (4, 256)), jnp.int32))
    loss, params, opt = ts.step(params, opt, tokens)
    jax.block_until_ready(params)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    opts.enable_hlo_proto = False
    tmp = out_dir + ".tmp"
    shutil.rmtree(tmp, ignore_errors=True)
    jax.profiler.start_trace(tmp, profiler_options=opts)
    for _ in range(3):
        with jax.profiler.TraceAnnotation("bench_unit"):
            time.sleep(0.003)  # the device plane's clock runs ~1.3 ms ahead: keep a program in its unit
            loss, params, opt = ts.step(params, opt, tokens)
            jax.block_until_ready(params)
    jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(tmp, "plugins", "profile", "*", "*.xplane.pb"))
    os.makedirs(out_dir, exist_ok=True)
    dst = os.path.join(out_dir, FILE_NAME)
    shutil.copy(path, dst)
    shutil.rmtree(tmp, ignore_errors=True)
    print("wrote", dst, os.path.getsize(dst), "bytes; loss", float(loss), "load", np.asarray(ts.last_stats["tokens_per_expert"]))


if __name__ == "__main__":
    main(sys.argv[1])
