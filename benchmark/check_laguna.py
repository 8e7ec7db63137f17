#!/usr/bin/env python3
"""The program against the plain reference on the chip, beyond the loss that
``worker.py`` compares (guide §3.3): the final hidden state and the logits of
ONE sequence of the cell's length at the configuration's real widths.

    python3 benchmark/check_laguna.py [--config laguna-xs2-1g] [--seq 8192] [--seed N]

Two comparisons with the reference (``reference/laguna_decoder.py``, float32
at "highest" matmul precision), each by three distances — |loss − loss_ref|,
and the relative L2 distance (‖a − b‖ / ‖b‖ over the whole array) of the final
hidden state and of the logits:

* ``as_run``: the program as the cell runs it (bfloat16 activations, the
  kernels ``_attention_path`` picks on this backend). Its distance is bfloat16's
  own (0.040 at s8192 over five layers), so only a slip that adds more than that
  shows: the limits are a quarter above the first reading.
* ``float32``: the SAME program — the same kernels, band, group map, tables and
  routing — with float32 activations at "highest" precision. What is left is the
  order of sums (read 5e-7), so the limits are tight (1e-3), and what they hold is the
  structure: which keys a position sees, which head a query reads, the values of
  the tables, who is routed where.

Then the same distances against the reference with ONE thing slipped, each of
which has to fail a limit of at least one comparison: the band one position
wider (window + 1), the rotation tables rounded to bfloat16, the router's scores
computed in bfloat16, the softmax computed in bfloat16. :data:`LIMITS` holds the
limits with their reasons. Prints one JSON line last; exits 1 if the program is
outside a limit or a slip is inside all of them. Not a benchmark cell: nothing
is timed.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(HERE), HERE]

# What the program may differ from the float32 reference by; every slip has to
# exceed one. Readings on the v5e at s8192, widths as published, seeds 5, 7 and
# 2147483659 (my chip runs, PR 41; PERF.md §6 holds the table).
LIMITS = {
    # loss: worker.py's REFERENCE_ATOL, the limit that decides `correct` (read 2.0e-4, 5.1e-4). hidden, logits:
    # read 0.0401 / 0.0402 and 0.0391 / 0.0392 — bfloat16 through five layers; a quarter above. The router's
    # scores in bfloat16 read 0.060-0.062 here, the rotation tables in bfloat16 0.099; the band off by one
    # (0.043-0.044) and the softmax in bfloat16 (0.039-0.041) do NOT show against bfloat16's own distance: the
    # float32 comparison holds them
    "as_run": {"loss": 2e-3, "hidden": 5e-2, "logits": 5e-2},
    # float32 on both sides: the order of sums alone. Read: loss 0 and 1.9e-6, hidden 5.2e-7, logits 5.5e-7.
    # The slips, hidden and logits alike: the softmax in bfloat16 0.015-0.017, the band off by one 0.028-0.031
    # (it moves the loss by 4.7e-4 and 7.4e-4, and by 2.2e-5 at seed 7), the router in bfloat16 0.058-0.059, the tables
    # in bfloat16 0.098. 1e-3 is 1800 times the reading and a fifteenth of the least slip; the loss alone (1e-4,
    # read <= 1.9e-6) would miss the router's slip at one seed (8.3e-5) and at seed 7 every slip but the router's:
    # the hidden state is what holds every slip
    "float32": {"loss": 1e-4, "hidden": 1e-3, "logits": 1e-3},
}
SLIPS = ("band_off_by_one", "rotation_table_bf16", "router_bf16", "softmax_bf16")


def distances(got, want):
    import jax.numpy as jnp

    def rel(a, b):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))

    return {"loss": abs(float(got[0]) - float(want[0])), "hidden": rel(got[1], want[1]), "logits": rel(got[2], want[2])}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="laguna-xs2-1g")
    ap.add_argument("--seq", type=int, default=8192)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true", help="tiny sizes on whatever backend there is")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from common import load_json, load_module
    from torchft_tpu.models import transformer as T

    config = load_json(os.path.join(HERE, "configs", args.config + ".json"))
    tc = dict(config["program"]["transformer_config"])
    if args.rehearse:
        tc.update(load_json(os.path.join(HERE, "tests", "rehearsal.json"))["transformer_config"])
        tc.update((config.get("rehearsal") or {}).get("transformer_config", {}))
        args.seq = min(args.seq, 64)
    ref = load_module(os.path.join(HERE, "reference", config["program"]["reference"] + ".py"), "bench_reference")
    cfg = T.TransformerConfig(**{**tc, "dtype": jnp.dtype(tc["dtype"])})
    params = jax.jit(lambda: T.init_params(jax.random.PRNGKey(args.seed), cfg))()
    tokens = jnp.asarray(np.random.default_rng(args.seed).integers(0, cfg.vocab_size, (1, args.seq)), jnp.int32)
    dev = jax.devices()[0]
    print(f"[check] {args.config}: s{args.seq}, seed {args.seed}, {dev.device_kind} ({dev.platform})", flush=True)

    def program(compute_dtype):
        c = T.TransformerConfig(**{**tc, "dtype": compute_dtype})

        def run(p, t):
            hidden, _ = T._hidden_states(p, t, c)
            logits = (hidden @ p["out"].astype(c.dtype)).astype(jnp.float32)
            return T.loss_fn(p, t, c), hidden, logits

        return jax.block_until_ready(jax.jit(run)(params, tokens))

    def reference(sizes):
        def run(p, t):
            hidden = ref.hidden(p, t, sizes)
            logits = hidden @ p["out"]
            return jnp.mean(ref._nll(logits, t)), hidden, logits

        with jax.default_matmul_precision("highest"):
            return jax.block_until_ready(jax.jit(run)(params, tokens))

    got = {"as_run": program(cfg.dtype)}
    try:
        with jax.default_matmul_precision("highest"):
            got["float32"] = program(jnp.float32)
    except Exception as e:  # noqa: BLE001 — said, and then the check fails: the comparison is part of it
        print(f"[check] the float32 program did not run: {type(e).__name__}: {str(e)[:300]}", flush=True)

    readings = {}

    def against(name, want):
        readings[name] = {which: distances(mine, want) for which, mine in got.items()}

    against("program", reference(tc))

    # -- one thing slipped on the reference's side, each by itself
    against("band_off_by_one", reference(dict(tc, window=tc["window"] + 1)))

    tables = ref.inv_frequencies
    rounded = lambda table: [float(f) for f in np.asarray(table, np.float32).astype(jnp.bfloat16).astype(np.float32)]
    ref.inv_frequencies = lambda sizes, mixer: (rounded(tables(sizes, mixer)[0]), tables(sizes, mixer)[1])
    against("rotation_table_bf16", reference(tc))
    ref.inv_frequencies = tables

    sigmoid = jax.nn.sigmoid
    jax.nn.sigmoid = lambda x: sigmoid(x.astype(jnp.bfloat16)).astype(jnp.float32)
    against("router_bf16", reference(tc))
    jax.nn.sigmoid = sigmoid

    softmax = jax.nn.softmax
    jax.nn.softmax = lambda x, axis=-1: softmax(x.astype(jnp.bfloat16), axis=axis).astype(jnp.float32)
    against("softmax_bf16", reference(tc))
    jax.nn.softmax = softmax

    def outside(name):
        return [f"{which}.{k}" for which, d in readings[name].items() for k in d if d[k] > LIMITS[which][k]]

    for name, by in readings.items():
        text = "; ".join(f"{which}: loss {d['loss']:.3g} hidden {d['hidden']:.3g} logits {d['logits']:.3g}" for which, d in by.items())
        print(f"[check] {name}: {text} -> {'outside ' + ', '.join(outside(name)) if outside(name) else 'inside every limit'}", flush=True)
    ok = set(got) == set(LIMITS) and not outside("program") and all(outside(name) for name in SLIPS)
    if args.rehearse:  # tiny sizes in float32: the as-run program IS the float32 one, and a slip of 8 bits may change nothing
        ok = not outside("program") and bool(outside("band_off_by_one"))
    print(json.dumps({"ok": bool(ok), "limits": LIMITS, "readings": readings, "seq": args.seq, "seed": args.seed,
                      "device": {"platform": dev.platform, "kind": dev.device_kind}}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
