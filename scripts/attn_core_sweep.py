#!/usr/bin/env python3
"""Time the causal attention core alone on the chip, one candidate a row.

What ``attention_impl`` "auto" chooses between (models/transformer.py
``_attention_path``) was measured with this: at the benchmark cells' shape
``[8, 2048, 16, 128]`` bf16 (and ``scale_647M``'s ``[4, 1024, 16, 64]``),
forward + backward under ``jax.checkpoint`` — the layer's ``remat`` "all"
runs the forward twice — for ``ops.attention`` plain and chunked, the
repo's Pallas kernel over block sizes, and JAX's own ``flash_attention``
and splash attention. Each candidate is held to the chunked path's output
and gradients. One JSON line a candidate on stdout and in
``chiprun_out/attn_core_sweep.jsonl``; PERF.md §6 (PR 31) holds the table.

    python scripts/attn_core_sweep.py            # on a TPU; minutes
    python scripts/attn_core_sweep.py --tiny     # CPU rehearsal of the control flow
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from torchft_tpu.ops.attention import attention, chunked_attention
from torchft_tpu.ops.pallas.flash_attention import flash_attention

OUT = os.path.join("chiprun_out", "attn_core_sweep.jsonl")


def inputs(shape, seed=31):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q, k, v, w = (jax.random.normal(kk, shape, jnp.float32).astype(jnp.bfloat16) for kk in ks)
    return q, k, v, w


def core_step(fn):
    """loss and (dq, dk, dv) of fn under jax.checkpoint: fwd, fwd again, bwd."""

    def run(q, k, v, w):
        def loss(q, k, v):
            o = jax.checkpoint(fn)(q, k, v)
            return jnp.sum(o.astype(jnp.float32) * w.astype(jnp.float32)), o

        return jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True)(q, k, v)

    return jax.jit(run)


def measure(name, fn, args, ref, reps, **cols):
    row = {"candidate": name, "shape": list(args[0].shape), **cols}
    try:
        step = core_step(fn)
        t0 = time.perf_counter()
        out = jax.block_until_ready(step(*args))
        row["compile_and_first_s"] = round(time.perf_counter() - t0, 2)
        jax.block_until_ready(step(*args))
        # a burst of calls, one wait: the host enqueues ahead of the device,
        # so a call's dispatch (~1.5 ms on the chip's shared host, as much as
        # a good kernel's forward) is not read as device time
        times = []
        for _ in range(3):
            t0 = time.perf_counter()
            for _ in range(reps):
                last = step(*args)
            jax.block_until_ready(last)
            times.append((time.perf_counter() - t0) / reps)
        row["ms"] = round(1e3 * statistics.median(times), 3)
        row["ms_min"] = round(1e3 * min(times), 3)
        (_, o), g = out
        if ref is not None:
            (_, o_ref), g_ref = ref
            row["max_abs_diff_vs_chunked"] = {
                n: round(float(jnp.max(jnp.abs(a.astype(jnp.float32) - r.astype(jnp.float32)))), 5)
                for n, a, r in zip(("o", "dq", "dk", "dv"), (o, *g), (o_ref, *g_ref))
            }
        row["device"] = jax.devices()[0].device_kind
    except Exception as e:  # a candidate the compiler refuses is a row, not the end
        row["error"] = f"{type(e).__name__}: {str(e)[:400]}"
        out = None
    line = json.dumps(row)
    print(line, flush=True)
    with open(OUT, "a") as f:
        f.write(line + "\n")
    return out


def jax_flash(bq, bkm, bk):
    from jax.experimental.pallas.ops.tpu import flash_attention as fa

    def fn(q, k, v):
        sizes = fa.BlockSizes(
            block_q=bq, block_k_major=bkm, block_k=bk, block_b=1,
            block_q_major_dkv=bq, block_k_major_dkv=bkm, block_k_dkv=bk, block_q_dkv=bq,
            block_k_major_dq=bkm, block_k_dq=bk, block_q_dq=bq,
        )
        t = lambda x: x.transpose(0, 2, 1, 3)
        return t(fa.flash_attention(
            t(q), t(k), t(v), causal=True, sm_scale=q.shape[-1] ** -0.5, block_sizes=sizes
        ))

    return fn


def jax_splash(bq, bkv, bkc, fused, interpret):
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk,
        splash_attention_mask as sm,
    )

    def fn(q, k, v):
        b, s, h, d = q.shape
        mask = sm.MultiHeadMask([sm.CausalMask((s, s))] * h)
        sizes = sk.BlockSizes(
            block_q=bq, block_kv=bkv, block_kv_compute=bkc,
            block_q_dkv=bq, block_kv_dkv=bkv, block_kv_dkv_compute=bkc,
            block_q_dq=None if fused else bq, block_kv_dq=None if fused else bkv,
            use_fused_bwd_kernel=fused,
        )
        kernel = sk.make_splash_mha(
            mask, block_sizes=sizes, head_shards=1, q_seq_shards=1, interpret=interpret
        )
        t = lambda x: x.transpose(0, 2, 1, 3)
        qs = (q.astype(jnp.float32) * d**-0.5).astype(q.dtype)  # splash applies no scale
        return t(jax.vmap(kernel)(t(qs), t(k), t(v)))

    return fn


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--tiny", action="store_true", help="CPU rehearsal at toy sizes")
    ap.add_argument("--reps", type=int, default=10, help="calls in a burst")
    args = ap.parse_args()
    os.makedirs("chiprun_out", exist_ok=True)
    on_tpu = jax.default_backend() == "tpu"
    if not (on_tpu or args.tiny):
        raise SystemExit("no TPU: a CPU time is not a measurement; --tiny rehearses the control flow")
    interpret = not on_tpu

    if args.tiny:
        shapes = {"cells": (1, 256, 2, 128), "scale_647M": (1, 256, 2, 64)}
        blocks = [(128, 128), (128, 256)]
        jf, sp = [], [(128, 128, 128, False)]  # jax's flash_attention has no interpret switch
        reps = 1
    else:
        shapes = {"cells": (8, 2048, 16, 128), "scale_647M": (4, 1024, 16, 64)}
        blocks = [(a, b) for a in (256, 512, 1024) for b in (256, 512, 1024)] + [(128, 128), (2048, 512)]
        jf = [(512, 512, 512), (512, 1024, 512), (1024, 1024, 512), (1024, 1024, 1024),
              (256, 512, 256), (512, 2048, 512), (128, 128, 128)]
        sp = [(512, 512, 512, False), (512, 1024, 512, False), (1024, 1024, 512, False),
              (512, 1024, 512, True), (1024, 2048, 512, True), (512, 512, 512, True)]
        reps = args.reps

    for label, shape in shapes.items():
        a = inputs(shape)
        s = shape[1]
        ref = measure(
            "chunked_c128_t4", functools.partial(chunked_attention, causal=True, chunk=128),
            a, None, reps, set=label,
        )
        measure("plain", functools.partial(attention, causal=True), a, ref, reps, set=label)
        for bq, bk in blocks:
            if bq > s or bk > s:
                continue
            measure(
                f"repo_flash_q{bq}_k{bk}",
                functools.partial(flash_attention, causal=True, block_q=bq, block_k=bk),
                a, ref, reps, set=label, block_q=bq, block_k=bk,
            )
        for bq, bkm, bk in jf:
            if bq > s or bkm > s:
                continue
            measure(f"jax_flash_q{bq}_km{bkm}_k{bk}", jax_flash(bq, bkm, bk),
                    a, ref, reps, set=label)
        for bq, bkv, bkc, fused in sp:
            if bq > s or bkv > s:
                continue
            measure(f"jax_splash_q{bq}_kv{bkv}_c{bkc}{'_fused' if fused else ''}",
                    jax_splash(bq, bkv, bkc, fused, interpret), a, ref, reps, set=label)
        # the reference check of the benchmark runs batch 1 through the same rule
    a1 = inputs((1,) + shapes["cells"][1:])
    ref1 = measure("chunked_c128_t4", functools.partial(chunked_attention, causal=True, chunk=128),
                   a1, None, reps, set="batch1")
    for bq, bk in blocks[:6]:
        if bq <= a1[0].shape[1] and bk <= a1[0].shape[1]:
            measure(f"repo_flash_q{bq}_k{bk}",
                    functools.partial(flash_attention, causal=True, block_q=bq, block_k=bk),
                    a1, ref1, reps, set="batch1", block_q=bq, block_k=bk)


if __name__ == "__main__":
    main()
