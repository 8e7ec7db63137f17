#!/usr/bin/env python3
"""The program against the plain reference on the chip, beyond the loss that
``worker.py`` compares (guide §3.3): the final hidden state, the logits and the
gradient's norm for ONE sequence of the cell's length at the configuration's
real widths.

    python3 benchmark/check_qwen3_next.py [--config qwen3-next-80b-a3b-1g] [--seq 8192] [--seed N]

The norm weights that scale by ``1 + w``, which the program initialises 0, and
the shared expert's gate are drawn here off their start (0.1 x normal on the
norms, 4 x the gate's projection; the same leaves on both sides): a weight of
0 would hide a norm that is not applied, and a gate that sits at a half moves
with nothing.

Two comparisons with the reference (``reference/qwen3_next_decoder.py``, float32
at "highest" matmul precision), each by four distances — |loss − loss_ref|, the
relative L2 distance (‖a − b‖ / ‖b‖ over the whole array) of the final hidden
state and of the logits, and |‖g‖ − ‖g_ref‖| / ‖g_ref‖ of the gradient over
every parameter:

* ``as_run``: the program as the cell runs it (bfloat16 activations, the flash
  kernel at 256-wide heads where the backend is a TPU, the chunked delta rule).
  Its distance is bfloat16's own, so only a slip that adds more than that shows.
* ``float32``: the SAME program — the same chunks of 64 positions, triangular
  inverse, scan over chunks and blocks, per-head norm, partial rotation, routing
  and chunked head — with float32 activations at "highest" precision. What is
  left is the order of sums, so the limits are tight, and what they hold is the
  structure: which key head a value head reads, whose decay a head takes, which
  gate sits where, what is rotated and renormalised.

Then the same distances against the reference with ONE thing slipped, each of
which has to fail a limit of at least one comparison (:data:`SLIPS`): a head's
decay taken from its neighbour, value head j reading key head j mod 16 and not
j // 2, the attention's output gate dropped, all 256 lanes rotated and not 64,
the chosen experts' weights not renormalised, the shared expert's gate dropped,
the router's probabilities computed in bfloat16, the delta rule's state rounded
to bfloat16 after every position. :data:`LIMITS` holds the limits with their
reasons. Prints one JSON line last; exits 1 if the program is outside a limit or
a slip is inside all of them. Not a benchmark cell: nothing is timed.
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
# 2147483659 (my chip runs, PR 54; PERF.md §6 holds the table; the third seed's
# run was cut after four of its eight slips, the two bfloat16 slips were run again
# at all three).
LIMITS = {
    # loss: worker.py's REFERENCE_ATOL, the limit that decides `correct` (read 3.2e-5, 8.4e-5, 2.1e-4). hidden, logits:
    # read 0.0135 / 0.0135 / 0.0130 and 0.0141 / 0.0141 / 0.0136 — bfloat16 through four layers; 0.018 is a third above
    # the largest. The output gate dropped (0.0361-0.0379) and all 256 lanes rotated (0.0198-0.0204) show here; the
    # router's probabilities in bfloat16 (0.0140-0.0148) and the state in bfloat16 (0.0131-0.0143) do NOT show against
    # bfloat16's own distance: the float32 comparison holds them. grad_norm: read 1.25e-4, 1.31e-4, 2.0e-5; 6e-4 is
    # 4.6 times the largest; a head's decay taken from its neighbour reads 3.4e-4-3.8e-3, the weights not
    # renormalised 3.5e-3-3.8e-3, the shared gate dropped 0.074
    "as_run": {"loss": 2e-3, "hidden": 1.8e-2, "logits": 1.8e-2, "grad_norm": 6e-4},
    # float32 on both sides. Read: hidden and logits 4.4e-4, 3.0e-4, 6.8e-4 — five hundred times LFM2's 8.5e-7, with a
    # loss that agrees to 0-1.2e-5: what a few tokens that choose another tenth expert of 512 would read (two
    # probabilities a float32 step apart; not checked token by token), so the floor of this comparison is the router's,
    # not the order of sums. The slips, hidden and logits alike: the delta rule's state rounded to bfloat16 after every
    # position 3.45e-3-4.28e-3, the router's probabilities in bfloat16 8.2e-3-8.8e-3, all lanes rotated 0.0148-0.0150,
    # the output gate dropped 0.0336-0.0356, the weights not renormalised 0.083-0.085, the neighbour's decay
    # 0.19-0.38, the shared gate dropped 0.595, key head j mod 16 0.61: 1.5e-3 is 2.2 times the largest reading and
    # 2.3 times under the least slip — a lower precision than the configuration states, in the state or in the
    # router, fails THIS comparison by this limit. loss: read 9.5e-7, 0, 1.24e-5; the state in bfloat16 moves it by
    # 2.9e-6-1.9e-5, so the loss alone would miss it: the arrays hold every slip. grad_norm: read 3.7e-6, 2.2e-6,
    # 8.3e-7; 2e-5 is five times the largest
    "float32": {"loss": 4e-5, "hidden": 1.5e-3, "logits": 1.5e-3, "grad_norm": 2e-5},
}
SLIPS = (
    "decay_of_the_neighbouring_head", "value_head_reads_key_head_j", "output_gate_dropped", "all_lanes_rotated",
    "weights_not_renormalised", "shared_gate_dropped", "router_bf16", "state_bf16",
)


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
    ap.add_argument("--config", default="qwen3-next-80b-a3b-1g")
    ap.add_argument("--seq", type=int, default=8192)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--slips", default=",".join(SLIPS), help="which of the slips to plant (all of them decide `ok`)")
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
        args.seq = min(args.seq, 256)
    ref = load_module(os.path.join(HERE, "reference", config["program"]["reference"] + ".py"), "bench_reference")
    cfg = T.TransformerConfig(**{**tc, "dtype": jnp.dtype(tc["dtype"])})
    zero_centred = ("ln1", "ln2", "q_norm", "k_norm", "final_norm")

    def seeded():
        params = T.init_params(jax.random.PRNGKey(args.seed), cfg)
        leaves, treedef = jax.tree_util.tree_flatten_with_path(params)

        def drawn(i, path, a):
            if path[-1].key in zero_centred:
                return 0.1 * jax.random.normal(jax.random.fold_in(jax.random.PRNGKey(args.seed), i), a.shape, a.dtype)
            return 4.0 * a if path[-1].key == "shared_scale" else a

        return jax.tree_util.tree_unflatten(treedef, [drawn(i, path, a) for i, (path, a) in enumerate(leaves)])

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

    # a layer at a time in the reference's backward (its blocks of queries and of positions are computed again already)
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
        text = "; ".join(f"{which}: " + " ".join(f"{k} {v:.3g}" for k, v in d.items()) for which, d in readings[name].items())
        print(f"[check] {name}: {text} -> {'outside ' + ', '.join(outside(name)) if outside(name) else 'inside every limit'}", flush=True)

    def outside(name):
        return [f"{which}.{k}" for which, d in readings[name].items() for k in d if d[k] > LIMITS[which][k]]

    def slipped(name, attr, wrong):
        """The reference with ``ref.<attr>`` replaced by ``wrong`` (handed the right one), and put back."""
        if name not in args.slips.split(","):
            return
        right = getattr(ref, attr)
        setattr(ref, attr, wrong(right))
        try:
            against(name, reference(tc))
        finally:
            setattr(ref, attr, right)

    against("program", reference(tc))

    # -- one thing slipped on the reference's side, each by itself
    # bfloat16's eight exponent and seven mantissa bits by an op of its own: a cast there and back is one the chip's
    # compiler may drop (it keeps excess precision), and the state rounded so read the program's own numbers (PR 54)
    bf16 = lambda x: jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
    slipped("decay_of_the_neighbouring_head", "_decay", lambda right: lambda w, a: jnp.roll(right(w, a), 1, axis=-1))
    slipped("value_head_reads_key_head_j", "_key_head_of", lambda _: lambda value_heads, key_heads: jnp.arange(value_heads) % key_heads)
    slipped("output_gate_dropped", "_gated", lambda _: lambda o, gate: o)
    slipped("all_lanes_rotated", "_rotate", lambda right: lambda x, sizes: right(x, {**sizes, "rotary_dim": 0}))
    slipped("weights_not_renormalised", "_weigh", lambda _: lambda probs, chosen: jnp.where(chosen, probs, 0.0))
    slipped("shared_gate_dropped", "_shared_gate", lambda _: lambda w, h: 1.0)
    slipped("router_bf16", "_router_probs", lambda _: lambda w, h: bf16(jax.nn.softmax(bf16(bf16(h) @ bf16(w["router"])), axis=-1)))

    def rounded_state(right):
        def positions(S, xs):
            def position(S, x):
                q_t, k_t, v_t, g_t, b_t = x
                S = jnp.exp(g_t)[..., None, None] * S
                S = bf16(S + jnp.einsum("bhk,bhd->bhkd", k_t, b_t[..., None] * (v_t - jnp.einsum("bhkd,bhk->bhd", S, k_t))))
                return S, jnp.einsum("bhkd,bhk->bhd", S, q_t)

            return jax.lax.scan(position, S, xs)

        return positions

    slipped("state_bf16", "_positions", rounded_state)

    ok = set(got) == set(LIMITS) and not outside("program") and all(name in readings and outside(name) for name in SLIPS)
    if args.rehearse:  # tiny sizes in float32: the as-run program IS the float32 one, and a slip of 8 bits may change nothing
        ok = not outside("program") and all(outside(name) for name in SLIPS[:6] if name in readings)
    print(json.dumps({"ok": bool(ok), "limits": LIMITS, "readings": readings, "seq": args.seq, "seed": args.seed,
                      "device": {"platform": dev.platform, "kind": dev.device_kind}}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
