#!/usr/bin/env python3
"""Time the causal attention core alone on the chip, one candidate a row.

What ``attention_impl`` "auto" chooses between (models/transformer.py
``_attention_path``) was measured with this: at the benchmark cells' shape
``[8, 2048, 16, 128]`` bf16 (and ``scale_647M``'s ``[4, 1024, 16, 64]``),
forward + backward under ``jax.checkpoint`` — the layer's ``remat`` "all"
runs the forward twice — for ``ops.attention`` plain and chunked, the
repo's Pallas kernel over block sizes, and JAX's own ``flash_attention``
and splash attention (``--sets wide``: PERF.md §6, PR 31). ``--sets
heads64`` (PR 52) is the 64-wide head at 16 384 tokens a call, from
``lfm2-8b-a1b-1g``'s 32 query heads over 8 at s8192 down to ``scale_647M``'s
shape: the chunked scan against the kernel over block sizes, through
``[B, H, S, Dh]`` (a transpose a side) and with the lanes padded to 128 and
read in place, each under the plain checkpoint and under the one that keeps
the kernel's output and row statistics (``_remat``: the forward once). Each
candidate is held to the chunked path's output and gradients. One JSON line
a candidate on stdout and in ``chiprun_out/attn_core_sweep.jsonl``.

    python scripts/attn_core_sweep.py --sets heads64   # on a TPU; minutes
    python scripts/attn_core_sweep.py --tiny           # CPU rehearsal of the control flow
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

fa = sys.modules[flash_attention.__module__]  # the package's name for the module is the function

OUT = os.path.join("chiprun_out", "attn_core_sweep.jsonl")


def inputs(shape, kv_heads=None, seed=31):
    """q, k, v and the output's weights; k and v with ``kv_heads`` where the heads are grouped."""
    b, s, h, d = shape
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q, k, v, w = (
        jax.random.normal(kk, (b, s, n, d), jnp.float32).astype(jnp.bfloat16)
        for kk, n in zip(ks, (h, kv_heads or h, kv_heads or h, h))
    )
    return q, k, v, w


# what ``models/transformer._remat`` keeps: the kernel's forward then runs once
KEEP_CORE = jax.checkpoint_policies.save_only_these_names(fa.CORE_OUT, fa.CORE_LSE)


def core_step(fn, policy=None):
    """loss and (dq, dk, dv) of fn under jax.checkpoint: fwd, fwd again, bwd
    (``policy`` KEEP_CORE: fwd, bwd where fn is the kernel; "off": no
    checkpoint around fn, a layer under ``remat=False``)."""

    def run(q, k, v, w):
        def loss(q, k, v):
            o = (fn if policy == "off" else jax.checkpoint(fn, policy=policy))(q, k, v)
            return jnp.sum(o.astype(jnp.float32) * w.astype(jnp.float32)), o

        return jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True)(q, k, v)

    return jax.jit(run)


def measure(name, fn, args, ref, reps, policy=None, **cols):
    row = {"candidate": name, "shape": list(args[0].shape), "kv_heads": args[1].shape[2], **cols}
    try:
        step = core_step(fn, policy)
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


def kernel_call(q, k, v, bq, bk, heads, seq, lanes, group, scale):
    """``flash_attention``'s call on arrays already in the kernel's layout,
    K and V resident as it keeps them."""
    shape = (q.shape[0], seq, heads, lanes, lanes, scale, group, None)
    return fa._flash(q, k, v, shape, (bq, bk * fa._resident_tiles(seq, bk, lanes), bk), True, fa._should_interpret())


def padded_flash(bq, bk):
    """The kernel with a 64-wide head's lanes padded to one lane tile and read
    in place from ``[B, S, H·128]``: the scores are the 64 lanes' (zero columns
    add nothing, the scale is the 64's), the output's upper lanes are dropped."""

    def fn(q, k, v):
        b, s, h, d = q.shape
        wide = d + -d % 128
        pack = lambda x: jnp.pad(x, ((0, 0), (0, 0), (0, 0), (0, wide - d))).reshape(b, s, x.shape[2] * wide)
        o = kernel_call(pack(q), pack(k), pack(v), bq, bk, h, s, wide, h // k.shape[2], d**-0.5)
        return o.reshape(b, s, h, wide)[..., :d]

    return fn


def untransposed_flash(bq, bk):
    """The kernel alone on ``[B, H, S, Dh]`` arrays: what the transposed
    layout costs less its transposes (no reference: another layout's output)."""

    def fn(q, k, v):
        _, h, s, d = q.shape
        return kernel_call(q, k, v, bq, bk, h, s, d, h // k.shape[1], d**-0.5)

    return fn


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


def wide_sets(tiny, interpret, reps):
    """PR 31's table: 16 heads x 128 at the dense cells' shape, ``scale_647M``'s
    shape beside it, every candidate, and the reference check's batch 1."""
    if tiny:
        shapes = {"cells": (1, 256, 2, 128), "scale_647M": (1, 256, 2, 64)}
        blocks = [(128, 128), (128, 256)]
        jf, sp = [], [(128, 128, 128, False)]  # jax's flash_attention has no interpret switch
    else:
        shapes = {"cells": (8, 2048, 16, 128), "scale_647M": (4, 1024, 16, 64)}
        blocks = [(a, b) for a in (256, 512, 1024) for b in (256, 512, 1024)] + [(128, 128), (2048, 512)]
        jf = [(512, 512, 512), (512, 1024, 512), (1024, 1024, 512), (1024, 1024, 1024),
              (256, 512, 256), (512, 2048, 512), (128, 128, 128)]
        sp = [(512, 512, 512, False), (512, 1024, 512, False), (1024, 1024, 512, False),
              (512, 1024, 512, True), (1024, 2048, 512, True), (512, 512, 512, True)]

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


def heads64_sets(tiny, reps, only=None):
    """PR 52's table: 64-wide heads at 16 384 tokens a call, the chunked scan
    (what "auto" took at this width) against the kernel; ``(b, s, h, kv heads,
    window)``. ``only``: the kernel's tiles to run, all of the table's if None."""
    if tiny:
        shapes = {"lfm2_cell": (1, 256, 4, 2, None), "scale_647M": (1, 256, 2, 2, None), "band": (1, 256, 4, 2, 64)}
        blocks = [(128, 128), (128, 256)]
    else:
        shapes = {
            "lfm2_cell": (2, 8192, 32, 8, None), "s4096_grouped": (4, 4096, 32, 8, None),
            "s2048": (8, 2048, 16, 16, None), "scale_647M": (4, 1024, 16, 16, None),
            "lfm2_cell_band512": (2, 8192, 32, 8, 512),
        }
        blocks = [(256, 512), (512, 512), (1024, 512), (512, 1024), (1024, 1024), (512, 256)]
    for label, (b, s, h, kv, window) in shapes.items():
        a = inputs((b, s, h, 64), kv)
        chunk = 64 if tiny else 128
        scan = functools.partial(chunked_attention, causal=True, chunk=chunk, window=window)
        ref = measure(f"chunked_c{chunk}", scan, a, None, reps, set=label)
        measure(f"chunked_c{chunk}_remat_off", scan, a, ref, reps, "off", set=label)
        for bq, bk in only or blocks:
            if bq > s or bk > s:
                continue
            layouts = [("transposed", functools.partial(flash_attention, causal=True, block_q=bq, block_k=bk, window=window))]
            if window is None:
                layouts.append(("padded", padded_flash(bq, bk)))
            for layout, fn in layouts:
                for kept, policy in (("", None), ("_kept", KEEP_CORE)):
                    measure(
                        f"repo_flash_{layout}_q{bq}_k{bk}{kept}", fn, a, ref, reps, policy,
                        set=label, block_q=bq, block_k=bk, layout=layout, forward_kept=bool(kept),
                    )
        if window is None:
            bq, bk = only[0] if only else blocks[1]
            measure(
                f"repo_flash_untransposed_q{bq}_k{bk}_kept", untransposed_flash(bq, bk),
                tuple(x.transpose(0, 2, 1, 3) for x in a), None, reps, KEEP_CORE,
                set=label, block_q=bq, block_k=bk, layout="untransposed", forward_kept=True, kv_heads=kv,
            )


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--tiny", action="store_true", help="CPU rehearsal at toy sizes")
    ap.add_argument("--reps", type=int, default=10, help="calls in a burst")
    ap.add_argument("--sets", default="wide,heads64", help="comma-separated: wide (PR 31), heads64 (PR 52)")
    ap.add_argument("--blocks", default="", help="heads64: only these tiles of the kernel, as 512x512,1024x512")
    args = ap.parse_args()
    os.makedirs("chiprun_out", exist_ok=True)
    on_tpu = jax.default_backend() == "tpu"
    if not (on_tpu or args.tiny):
        raise SystemExit("no TPU: a CPU time is not a measurement; --tiny rehearses the control flow")
    reps = 1 if args.tiny else args.reps
    sets = args.sets.split(",")
    if "wide" in sets:
        wide_sets(args.tiny, not on_tpu, reps)
    if "heads64" in sets:
        only = [tuple(int(n) for n in t.split("x")) for t in args.blocks.split(",") if t]
        heads64_sets(args.tiny, reps, only or None)


if __name__ == "__main__":
    main()
