#!/usr/bin/env python3
"""The program against the plain reference on the chip, beyond the loss that
``worker.py`` compares (guide §3.3): the main stack's final hidden state, BOTH
heads' logits and the gradient's norm for ONE sequence of the cell's length at
the configuration's real widths.

    python3 benchmark/check_joyai.py [--config joyai-flash-1g] [--seq 8192] [--seed N]

Two comparisons with the reference (``reference/joyai_flash_decoder.py``,
float32 at "highest" matmul precision), each by five distances — |loss −
loss_ref| (the training loss: main + weight x the module's), the relative L2
distance (‖a − b‖ / ‖b‖ over the whole array) of the final hidden state, of the
main head's logits and of the module's logits, and |‖g‖ − ‖g_ref‖| / ‖g_ref‖ of
the gradient over every parameter:

* ``as_run``: the program as the cell runs it (bfloat16 activations, the kernel
  ``_attention_path`` picks on this backend). Its distance is bfloat16's own,
  so only a slip that adds more than that shows.
* ``float32``: the SAME program — the same rotation, norms, routing, module and
  chunked heads — with float32 activations at "highest" precision, and the
  chunked scan as its causal core (``attention_impl`` "chunked": the chip's
  compiler refuses the kernel's float32 backward at keys 256 wide for VMEM
  inside this program, at 512, 256 and 128 tiles alike; the kernel itself is
  held by ``as_run`` and by ``tests/test_flash_attention.py``). What is left is
  the order of sums, so the limits are tight, and what they hold is the
  structure: what is rotated, what is normalised, which token a position is
  scored against, who is routed where.

Then the same distances against the reference with ONE thing slipped, each of
which has to fail a limit of at least one comparison: the shared key part not
rotated, the query's norm dropped, the module's targets shifted by one (it is
scored against the NEXT token), the rotation tables rounded to bfloat16, the
router's scores computed in bfloat16. :data:`LIMITS` holds the limits with
their reasons. Prints one JSON line last; exits 1 if the program is outside a
limit or a slip is inside all of them. Not a benchmark cell: nothing is timed.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(HERE), HERE]

# What the program may differ from the float32 reference by; every slip has to
# exceed one. Readings on the v5e at s8192, widths as published, seeds 5 and
# 2147483659 (my chip runs, PR 46; PERF.md §6 holds the table).
LIMITS = {
    # loss: worker.py's REFERENCE_ATOL, the limit that decides `correct` (read 2.7e-5, 1.6e-4). hidden, logits,
    # mtp_logits: bfloat16 through five layers (and a sixth for the module), twice the readings (0.0191-0.0199,
    # 0.0192-0.0200, 0.0197-0.0205; the shared key left unrotated reads 0.064-0.069); grad_norm: read 4.9e-5, 5.4e-5
    "as_run": {"loss": 2e-3, "hidden": 4e-2, "logits": 4e-2, "mtp_logits": 4e-2, "grad_norm": 2e-3},
    # float32 on both sides: the order of sums alone (read: loss 0, arrays <= 4.9e-7, grad_norm 0 to float32's last
    # bit). A slip that reaches the arrays reads 1e-2 or more there; the module's targets shifted by one reach
    # neither hidden state nor logits and move the loss by whatever two targets of an untrained model differ by
    # (9.4e-5, 3.8e-4), so what holds that slip is the gradient's norm (5.0e-4, 7.4e-4)
    "float32": {"loss": 2e-5, "hidden": 1e-3, "logits": 1e-3, "mtp_logits": 1e-3, "grad_norm": 1e-4},
}
SLIPS = ("shared_key_not_rotated", "query_norm_dropped", "mtp_targets_off_by_one", "rotation_table_bf16", "router_bf16")


def distances(got, want):
    import jax.numpy as jnp

    def rel(a, b):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))

    return {
        "loss": abs(float(got[0]) - float(want[0])), "hidden": rel(got[1], want[1]), "logits": rel(got[2], want[2]),
        "mtp_logits": rel(got[3], want[3]), "grad_norm": abs(float(got[4]) - float(want[4])) / float(want[4]),
    }


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="joyai-flash-1g")
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

    def norm_of(grads):
        return jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32))) for g in jax.tree_util.tree_leaves(grads)))

    def program(compute_dtype, **changes):
        c = T.TransformerConfig(**{**tc, "dtype": compute_dtype, **changes})

        def run(p, t):
            hidden, _ = T._hidden_states(p, t, c)
            second, _ = T._mtp_hidden(p, hidden, t, c)
            out = p["out"].astype(c.dtype)
            loss, grads = jax.value_and_grad(T.loss_fn)(p, t, c)
            return loss, hidden, (hidden @ out).astype(jnp.float32), (second @ out).astype(jnp.float32), norm_of(grads)

        return jax.block_until_ready(jax.jit(run)(params, tokens))

    # a layer at a time in the reference's backward (its blocks of queries are computed again already): the same numbers
    plain_layer = ref._layer
    ref._layer = lambda w, x, sizes, ff: jax.checkpoint(lambda w, x: plain_layer(w, x, sizes, ff))(w, x)

    def reference(sizes):
        def run(p, t):
            hidden = ref.hidden(p, t, sizes)
            loss, grads = jax.value_and_grad(ref._total)(p, t, sizes)
            return loss, hidden, hidden @ p["out"], ref.mtp_hidden(p, hidden, t, sizes) @ p["out"], norm_of(grads)

        with jax.default_matmul_precision("highest"):
            return jax.block_until_ready(jax.jit(run)(params, tokens))

    got = {"as_run": program(cfg.dtype)}
    try:
        with jax.default_matmul_precision("highest"):
            got["float32"] = program(jnp.float32, attention_impl="chunked")
    except Exception as e:  # noqa: BLE001 — said, and then the check fails: the comparison is part of it
        print(f"[check] the float32 program did not run: {type(e).__name__}: {str(e)[:300]}", flush=True)

    readings = {}

    def against(name, want):
        readings[name] = {which: distances(mine, want) for which, mine in got.items()}

    against("program", reference(tc))

    # -- one thing slipped on the reference's side, each by itself
    rotate = ref._rotate
    ref._rotate = lambda x, table: x if x.shape[2] == 1 else rotate(x, table)  # the one shared key part passes as it is
    against("shared_key_not_rotated", reference(tc))
    ref._rotate = rotate

    rms_norm = ref._rms_norm  # the latent's weight alone: what is dropped is the division by the root mean square
    ref._rms_norm = lambda x, weight, eps: x * weight if x.shape[-1] == tc["q_lora_rank"] else rms_norm(x, weight, eps)
    against("query_norm_dropped", reference(tc))
    ref._rms_norm = rms_norm

    nll = ref._nll
    ref._nll = lambda logits, toks, ahead: nll(logits, toks, 1)
    against("mtp_targets_off_by_one", reference(tc))
    ref._nll = nll

    tables = ref.inv_frequencies
    ref.inv_frequencies = lambda sizes: [float(f) for f in np.asarray(tables(sizes), np.float32).astype(jnp.bfloat16).astype(np.float32)]
    against("rotation_table_bf16", reference(tc))
    ref.inv_frequencies = tables

    sigmoid = jax.nn.sigmoid
    jax.nn.sigmoid = lambda x: sigmoid(x.astype(jnp.bfloat16)).astype(jnp.float32)
    against("router_bf16", reference(tc))
    jax.nn.sigmoid = sigmoid

    def outside(name):
        return [f"{which}.{k}" for which, d in readings[name].items() for k in d if d[k] > LIMITS[which][k]]

    for name, by in readings.items():
        text = "; ".join(
            f"{which}: " + " ".join(f"{k} {v:.3g}" for k, v in d.items()) for which, d in by.items()
        )
        print(f"[check] {name}: {text} -> {'outside ' + ', '.join(outside(name)) if outside(name) else 'inside every limit'}", flush=True)
    ok = set(got) == set(LIMITS) and not outside("program") and all(outside(name) for name in SLIPS)
    if args.rehearse:  # tiny sizes in float32: the as-run program IS the float32 one, and a slip of 8 bits may change nothing
        ok = not outside("program") and all(outside(name) for name in SLIPS[:3])
    print(json.dumps({"ok": bool(ok), "limits": LIMITS, "readings": readings, "seq": args.seq, "seed": args.seed,
                      "device": {"platform": dev.platform, "kind": dev.device_kind}}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
