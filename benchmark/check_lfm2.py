#!/usr/bin/env python3
"""The program against the plain reference on the chip, beyond the loss that
``worker.py`` compares (guide §3.3): the final hidden state, the logits and the
gradient's norm for ONE sequence of the cell's length at the configuration's
real widths.

    python3 benchmark/check_lfm2.py [--config lfm2-8b-a1b-1g] [--seq 8192] [--seed N]

The selection bias, which the program initialises 0, is drawn here (0.05 x
normal, the same leaves on both sides): a bias of 0 would hide one that reaches
the weights.

Two comparisons with the reference (``reference/lfm2_moe_decoder.py``, float32
at "highest" matmul precision), each by four distances — |loss − loss_ref|, the
relative L2 distance (‖a − b‖ / ‖b‖ over the whole array) of the final hidden
state and of the logits, and |‖g‖ − ‖g_ref‖| / ‖g_ref‖ of the gradient over
every parameter:

* ``as_run``: the program as the cell runs it (bfloat16 activations, the core
  ``_attention_path`` picks on this backend at 64-wide heads). Its distance is
  bfloat16's own, so only a slip that adds more than that shows.
* ``float32``: the SAME program — the same pad and slices, per-head norm,
  rotation, chunked scan, routing and chunked head — with float32 activations at
  "highest" precision. What is left is the order of sums, so the limits are
  tight, and what they hold is the structure: which positions a tap reads, which
  gate sits where, what is normalised, who is routed where and weighs what.

Then the same distances against the reference with ONE thing slipped, each of
which has to fail a limit of at least one comparison: the convolution made
non-causal by one position (every tap reads one position later), ``B`` and ``C``
swapped, the per-head norm dropped, the bias added to the weights and not only
to the choice, the router's scores computed in bfloat16. :data:`LIMITS` holds
the limits with their reasons. Prints one JSON line last; exits 1 if the program
is outside a limit or a slip is inside all of them. Not a benchmark cell:
nothing is timed.
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
# 2147483659 (my chip runs, PR 51; PERF.md §6 holds the table).
LIMITS = {
    # loss: worker.py's REFERENCE_ATOL, the limit that decides `correct` (read 1.3e-5, 1.9e-4, 3.2e-4). hidden, logits:
    # read 0.0289 / 0.0290 / 0.0299 each — bfloat16 through six layers, five of them a product of three bfloat16 operands
    # between two matmuls; a third above the largest. The convolution one position ahead and B, C swapped read 1.22-1.29
    # here; the per-head norm dropped (0.0310-0.0322), the bias in the weights (0.0313-0.0316) and the router's scores
    # in bfloat16 (0.0294-0.0315) do NOT show against bfloat16's own distance: the float32 comparison holds them.
    # grad_norm: read 7.7e-5, 8.0e-5, 1.16e-4; 6e-4 is five times the largest and half the least the router's scores in
    # bfloat16 read (1.24e-3, 1.32e-3, 1.59e-3): a lower precision than the configuration states where it states
    # float32 fails THIS comparison by this limit
    "as_run": {"loss": 2e-3, "hidden": 4e-2, "logits": 4e-2, "grad_norm": 6e-4},
    # float32 on both sides: the order of sums alone. Read: loss 0, 0, 9.5e-7; hidden 8.5e-7, logits 8.7e-7 at every
    # seed; grad_norm 0, 0, 1.0e-7. The slips, hidden and logits alike: the bias in the weights 0.0139-0.0182, the
    # per-head norm dropped 0.0176-0.0179, the router in bfloat16 0.0187-0.0196, B and C swapped 1.22, the convolution
    # one position ahead 1.29: 1e-3 is 1200 times the reading and a fourteenth of the least slip. loss: the least a
    # slip moves it is 1.9e-6 (the bias, seed 5; 9.4e-5 and 2.6e-4 at the others), so the loss alone would miss it:
    # the arrays hold every slip. grad_norm: the per-head norm dropped reads 4.0e-5-4.3e-5, the bias 8.2e-5-9.5e-5,
    # the others 7.5e-5 and more but for B and C swapped at one seed (1.2e-5): 2e-5 is 200 times the reading
    "float32": {"loss": 2e-5, "hidden": 1e-3, "logits": 1e-3, "grad_norm": 2e-5},
}
SLIPS = ("conv_one_position_ahead", "b_and_c_swapped", "head_norm_dropped", "bias_in_the_weights", "router_bf16")


def distances(got, want):
    import jax.numpy as jnp

    def rel(a, b):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))

    return {
        "loss": abs(float(got[0]) - float(want[0])), "hidden": rel(got[1], want[1]), "logits": rel(got[2], want[2]),
        "grad_norm": abs(float(got[3]) - float(want[3])) / float(want[3]),
    }


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="lfm2-8b-a1b-1g")
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

    def seeded():
        params = T.init_params(jax.random.PRNGKey(args.seed), cfg)
        leaves, treedef = jax.tree_util.tree_flatten_with_path(params)
        drawn = [
            0.05 * jax.random.normal(jax.random.fold_in(jax.random.PRNGKey(args.seed), i), a.shape, a.dtype)
            if path[-1].key == "router_bias" else a
            for i, (path, a) in enumerate(leaves)
        ]
        return jax.tree_util.tree_unflatten(treedef, drawn)

    params = jax.jit(seeded)()
    tokens = jnp.asarray(np.random.default_rng(args.seed).integers(0, cfg.vocab_size, (1, args.seq)), jnp.int32)
    dev = jax.devices()[0]
    print(f"[check] {args.config}: s{args.seq}, seed {args.seed}, {dev.device_kind} ({dev.platform})", flush=True)

    def norm_of(grads):
        return jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32))) for g in jax.tree_util.tree_leaves(grads)))

    def program(compute_dtype):
        c = T.TransformerConfig(**{**tc, "dtype": compute_dtype})

        def run(p, t):
            hidden, _ = T._hidden_states(p, t, c)
            loss, grads = jax.value_and_grad(T.loss_fn)(p, t, c)
            return loss, hidden, (hidden @ p["out"].astype(c.dtype)).astype(jnp.float32), norm_of(grads)

        return jax.block_until_ready(jax.jit(run)(params, tokens))

    # a layer at a time in the reference's backward (its blocks of queries are computed again already): the same numbers
    plain_layer = ref._layer
    ref._layer = lambda w, x, sizes, kind: jax.checkpoint(lambda w, x: plain_layer(w, x, sizes, kind))(w, x)

    def reference(sizes):
        def run(p, t):
            hidden = ref.hidden(p, t, sizes)
            loss, grads = jax.value_and_grad(lambda p: jnp.mean(ref._nll(ref.forward(p, t, sizes), t)))(p)
            return loss, hidden, hidden @ p["out"], norm_of(grads)

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

    def slipped(name, attr, wrong):
        """The reference with ``ref.<attr>`` replaced by ``wrong`` (handed the right one), and put back."""
        right = getattr(ref, attr)
        setattr(ref, attr, wrong(right))
        try:
            against(name, reference(tc))
        finally:
            setattr(ref, attr, right)

    against("program", reference(tc))

    # -- one thing slipped on the reference's side, each by itself
    def one_ahead(shift):  # every tap reads one position later: the last tap the NEXT position (zeros past the end)
        return lambda u, by: shift(u, by - 1) if by else jnp.pad(u, ((0, 0), (0, 1), (0, 0)))[:, 1:]

    slipped("conv_one_position_ahead", "_shift", one_ahead)

    def swapped(gated_conv):
        def wrong(w, h, sizes):
            d, w_in = h.shape[-1], w["conv_in"]
            return gated_conv(dict(w, conv_in=jnp.concatenate([w_in[:, d : 2 * d], w_in[:, :d], w_in[:, 2 * d :]], axis=1)), h, sizes)

        return wrong

    slipped("b_and_c_swapped", "_gated_conv", swapped)
    slipped("head_norm_dropped", "_head_norm", lambda _: lambda x, weight, eps: x * weight)  # no division by the head's root mean square
    slipped("bias_in_the_weights", "_weigh", lambda _: lambda scores, biased: biased)

    sigmoid = jax.nn.sigmoid
    jax.nn.sigmoid = lambda x: sigmoid(x.astype(jnp.bfloat16)).astype(jnp.float32)
    try:
        against("router_bf16", reference(tc))
    finally:
        jax.nn.sigmoid = sigmoid

    def outside(name):
        return [f"{which}.{k}" for which, d in readings[name].items() for k in d if d[k] > LIMITS[which][k]]

    for name, by in readings.items():
        text = "; ".join(f"{which}: " + " ".join(f"{k} {v:.3g}" for k, v in d.items()) for which, d in by.items())
        print(f"[check] {name}: {text} -> {'outside ' + ', '.join(outside(name)) if outside(name) else 'inside every limit'}", flush=True)
    ok = set(got) == set(LIMITS) and not outside("program") and all(outside(name) for name in SLIPS)
    if args.rehearse:  # tiny sizes in float32: the as-run program IS the float32 one, and a slip of 8 bits may change nothing
        ok = not outside("program") and all(outside(name) for name in SLIPS[:4])
    print(json.dumps({"ok": bool(ok), "limits": LIMITS, "readings": readings, "seq": args.seq, "seed": args.seed,
                      "device": {"platform": dev.platform, "kind": dev.device_kind}}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
