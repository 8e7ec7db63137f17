#!/usr/bin/env python3
"""Time the held expert layer's row move alone on the chip, one candidate a row.

``ops/layers.moe_dropless_held`` moves a window's [m, d] rows four times a
layer: ``x[token]`` and ``dy[token]`` by expert (ascending tokens inside each
held expert's run) and two moves into token order (a permutation of the
window's places). This times XLA's gather of all m rows (what the layer did
until PR 68) against ``layers._live_rows`` — the same gather a pass of rows
at a time, on a loop that ends with the live rows — over the rows a pass, at
the two cells' shapes, live share 25 / 50 / 100 %, both index orders, the
indices as ``layers._window_plan`` makes them; ``olmoe`` is ``moe_dropless``'s
``_take_rows`` shape, every row live. Every candidate is held to the gather on
its live rows. One JSON line a candidate on stdout and in
``chiprun_out/row_move_sweep.jsonl`` (PERF.md §6, PR 68).

    python scripts/row_move_sweep.py                 # on a TPU; ~3 minutes
    python scripts/row_move_sweep.py --tiny          # CPU rehearsal of the control flow
    python scripts/row_move_sweep.py --cell sdar-30b-a3b-1g --traffic fused-b2-s8192 --steps 60
        # the share of each layer's window that is live, a step, as the benchmark's worker steps the cell
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax
import jax.numpy as jnp
import numpy as np

from torchft_tpu.ops import layers

OUT = os.path.join("chiprun_out", "row_move_sweep.jsonl")

# name: (tokens, slots a token, the router's experts, experts held, window's slots, row width)
SHAPES = {
    "sdar": (32768, 8, 128, 16, 65536, 2048),
    "smallthinker": (32768, 6, 64, 8, 49152, 2560),
}
TINY = {"sdar": (256, 4, 16, 4, 512, 256), "smallthinker": (256, 4, 16, 4, 384, 384)}


def window(shape, live_share, seed=68):
    """(plan of a window whose first ``live_share`` of m slots are held
    experts' rows, as ``moe_dropless_held`` sorts them; n_live)."""
    t, k, _, held, m, _ = shape
    rng = np.random.default_rng(seed)
    n_live = int(m * live_share)
    a_run = np.diff(np.linspace(0, n_live, held + 1).astype(int))
    # a held expert's run: distinct tokens ascending, one copy each; then the absent experts' slots, ascending
    runs = [np.sort(rng.choice(t, n, replace=False)) * k + rng.integers(0, k) for n in a_run]
    dead = np.sort(rng.choice(t * k, m - n_live, replace=False))
    slots = jnp.asarray(np.concatenate(runs + [dead]), jnp.int32)
    gate = jnp.ones((m,), jnp.bfloat16)
    return jax.jit(lambda s, g, n: layers._window_plan(s, g, n, t, k))(slots, gate, jnp.int32(n_live)), n_live


def measure(name, fn, args, want, reps, **cols):
    row = {"candidate": name, **cols}
    n_live = cols["n_live"]
    try:
        step = jax.jit(fn)
        t0 = time.perf_counter()
        got = jax.block_until_ready(step(*args))
        row["compile_and_first_s"] = round(time.perf_counter() - t0, 2)
        # a burst of calls, one wait: the host enqueues ahead of the device
        times = []
        for _ in range(3):
            t0 = time.perf_counter()
            for _ in range(reps):
                last = step(*args)
            jax.block_until_ready(last)
            times.append((time.perf_counter() - t0) / reps)
        sec = statistics.median(times)
        moved = cols["rows_moved"]
        row.update(
            ms=round(1e3 * sec, 4), ms_min=round(1e3 * min(times), 4),
            ns_a_live_row=round(1e9 * sec / max(n_live, 1), 2), ns_a_moved_row=round(1e9 * sec / max(moved, 1), 2),
            gb_per_s=round(2 * moved * got.shape[1] * got.dtype.itemsize / sec / 1e9, 1),
            equal_on_live_rows=bool(jnp.array_equal(got[:n_live], want[:n_live])),
            device=jax.devices()[0].device_kind,
        )
    except Exception as e:  # a candidate the compiler refuses is a row, not the end
        row["error"] = f"{type(e).__name__}: {str(e)[:400]}"
    line = json.dumps(row)
    print(line, flush=True)
    with open(OUT, "a") as f:
        f.write(line + "\n")


def sweep(tiny, reps, passes):
    gather = lambda src, idx, n: src.at[idx].get(mode="promise_in_bounds")
    for label, shape in (TINY if tiny else SHAPES).items():
        t, _, _, _, m, d = shape
        for order, rows in (("by_expert", t), ("by_token", m)):
            src = jax.random.normal(jax.random.PRNGKey(1), (rows, d), jnp.float32).astype(jnp.bfloat16)
            for share in (0.25, 0.5, 1.0):
                plan, n_live = window(shape, share)
                idx = plan["token"] if order == "by_expert" else plan["from_place"]
                args = (src, idx, jnp.int32(n_live))
                want = jax.jit(gather)(*args)
                cols = dict(shape=label, order=order, src=[rows, d], m=m, live_share=share, n_live=n_live)
                measure("xla_gather_of_m", gather, args, want, reps, rows_moved=m, **cols)
                for step in passes:
                    step = min(step, m)
                    fn = lambda s, i, n, step=step: layers._live_rows(s, i, n, step)
                    measure(f"live_rows_{step}", fn, args, want, reps, rows_moved=min(-(-n_live // step) * step, m), rows_a_pass=step, **cols)
    # moe_dropless's _take_rows: every one of T·k rows is live
    t, k, d = (64, 4, 256) if tiny else (16384, 8, 2048)
    src = jax.random.normal(jax.random.PRNGKey(2), (t, d), jnp.float32).astype(jnp.bfloat16)
    idx = jnp.argsort(jnp.asarray(np.random.default_rng(3).integers(0, 64, t * k), jnp.int32), stable=True) // k
    args = (src, idx.astype(jnp.int32), jnp.int32(t * k))
    want = jax.jit(gather)(*args)
    cols = dict(shape="olmoe", order="by_expert", src=[t, d], m=t * k, live_share=1.0, n_live=t * k, rows_moved=t * k)
    measure("xla_gather_of_m", gather, args, want, reps, **cols)
    for step in passes:
        fn = lambda s, i, n, step=step: layers._live_rows(s, i, n, step)
        measure(f"live_rows_{step}", fn, args, want, reps, rows_a_pass=step, **cols)


def live_share_of_a_cell(config, traffic, seed, steps):
    """The cell as ``benchmark/worker.py`` builds and steps it (seeded weights,
    its batches, its optimizer): rows held over the window's slots, a layer a step."""
    import optax

    from torchft_tpu.models import transformer as T
    from torchft_tpu.parallel.mesh import MeshConfig, make_mesh
    from torchft_tpu.parallel.train_step import TrainStep
    from torchft_tpu.utils.compile_cache import place_compile_cache

    place_compile_cache()
    with open(os.path.join(ROOT, "benchmark", "configs", config + ".json")) as f:
        program = json.load(f)["program"]
    with open(os.path.join(ROOT, "benchmark", "traffic", traffic + ".json")) as f:
        mix = json.load(f)
    batch, seq = int(mix["batch"]), int(mix["seq"])
    tc = program["transformer_config"]
    cfg = T.TransformerConfig(**{**tc, "dtype": jnp.dtype(tc["dtype"])})
    rows = batch * seq * cfg.top_k * (2 if cfg.diffusion_block else 1)
    bound = T._held_row_bound(cfg, rows)
    ts = TrainStep(cfg, optax.adamw(program["learning_rate"]), make_mesh(MeshConfig(), devices=jax.devices()[:1]))
    params = ts.init_params(jax.random.PRNGKey(seed))
    opt = ts.init_opt(params)
    rng = np.random.default_rng(seed * 1000)
    shares = []
    for i in range(steps):
        tokens = ts.shard_batch(jnp.asarray(rng.integers(0, cfg.vocab_size, (batch, seq)), jnp.int32))
        t0 = time.perf_counter()
        loss, params, opt = ts.step(params, opt, tokens)
        loss = float(loss)
        held = np.asarray(ts.last_stats["rows_held"])
        shares.append(held / bound)
        line = {"cell": config, "seed": seed, "step": i, "s": round(time.perf_counter() - t0, 4), "loss": round(loss, 4),
                "row_bound": bound, "rows_held": held.tolist(), "window_live_share": [round(float(x), 4) for x in held / bound]}
        print(json.dumps(line), flush=True)
    shares = np.stack(shares)
    print(json.dumps({
        "cell": config, "seed": seed, "steps": steps, "row_bound": bound, "device": jax.devices()[0].device_kind,
        "window_live_share_mean": round(float(shares.mean()), 4), "min": round(float(shares.min()), 4),
        "max": round(float(shares.max()), 4), "layer_steps_past_the_bound": int((shares > 1).sum()),
    }), flush=True)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--tiny", action="store_true", help="CPU rehearsal at toy sizes")
    ap.add_argument("--reps", type=int, default=20, help="calls in a burst")
    ap.add_argument("--passes", default="512,1024,2048,4096,8192", help="rows a pass of the loop, comma-separated")
    ap.add_argument("--cell", help="a benchmark configuration: read its windows' live share step by step instead")
    ap.add_argument("--traffic", default="fused-b2-s8192")
    ap.add_argument("--seed", type=int, default=2147484801)
    ap.add_argument("--steps", type=int, default=60)
    args = ap.parse_args()
    os.makedirs("chiprun_out", exist_ok=True)
    if not (jax.default_backend() == "tpu" or args.tiny):
        raise SystemExit("no TPU: a CPU time is not a measurement; --tiny rehearses the control flow")
    if args.cell:
        live_share_of_a_cell(args.cell, args.traffic, args.seed, args.steps)
        return
    passes = [int(p) for p in args.passes.split(",")]
    sweep(args.tiny, 1 if args.tiny else args.reps, [64, 128] if args.tiny else passes)


if __name__ == "__main__":
    main()
