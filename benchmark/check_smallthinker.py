#!/usr/bin/env python3
"""The comparison that decides the SmallThinker cell's ``correct``
(``worker.py``: ``loss_fn`` against ``reference/smallthinker_decoder.py`` on two
sequences of the seeded first batch, |difference| a sequence within
``REFERENCE_ATOL``), made on MANY seeds in one process, at the configuration's
real widths and the traffic's real length (guide §3.3):

    python3 benchmark/check_smallthinker.py [--config smallthinker-21b-a3b-1g] [--traffic fused-b2-s16384] [--seeds 9] [--first N]

The weights are ``init_params(PRNGKey(seed))`` and the two sequences the first
two of ``default_rng(seed * 1000).integers(...)``'s first batch, as the worker
of group 0 draws them; the program runs as the cell runs it (bfloat16, the
kernels ``_attention_path`` picks on this backend: on a TPU the flash kernel
for both kinds of layer, 16 384 keys of 128 lanes resident, groups of 7, the
band of 4096), the reference in float32 at "highest" precision. A sequence's
loss is said beside the ReLU's share of zeroed gate lanes and the rows held.

Beside the loss, each comparison reads the relative L2 distance of the final
hidden state of the two sequences (‖a − b‖ / ‖b‖ over ``[2, S, d]``: the
program's ``_hidden_states`` in bfloat16 against the reference's ``hidden``):
with every weight at its initial scale the next-token loss is close to
ln(18 992) whatever the layers do, so the LOSS at the harness's tolerance
tells no slip of the mathematics from the program (read on the v5e, PR 60:
5.8e-4 to 1.75e-3 of 2e-3) — the hidden state does.

Then, at the first seed, the reference with ONE thing slipped, each by itself
(:data:`SLIPS`): the router reading ``N2(x')`` and not ``N1(x)``; the global
layer rotated like the window layers; SiLU for ReLU; a band of 2048; the
chosen experts' weights not renormalised over the six. Each has to fall
outside a limit of :data:`LIMITS` (by the hidden state, where the loss cannot
tell it); one that falls inside both is SAID (``slips_inside_the_limits``) and
fails the check unless :data:`SAID_NOT_REQUIRED` names it with the reason —
every one of the five is held besides in float32 on the CPU
(``tests/test_early_router_nope.py``: loss to 2e-6 and every gradient leaf,
each slip failing by 20 times that). The reference with its leaves and
products in bfloat16 is read and said too: the nearest precision below float32
IS the program's own, so the limits cannot and should not tell it from the
program. The loss's limit is the harness's (``worker.REFERENCE_ATOL``, read
from that file, not restated here).

Prints one JSON line last; exits 1 if a seed is outside a limit or a required
slip is inside both. Not a benchmark cell: nothing is timed.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(HERE), HERE]

SLIPS = ("router_reads_the_ffn_input", "global_layer_rotated", "silu_for_relu", "band_of_2048", "not_renormalised")
# The hidden state's limit (the loss's is worker.REFERENCE_ATOL). Readings on the v5e at b2 x s16384, widths as
# published, seeds 2147483700 ... 2147642080 (my chip runs, PR 60; PERF.md §6 holds the table). The program against the
# float32 reference: 0.01228, 0.01264, 0.01266, 0.01297, 0.01245 — bfloat16 through four layers (Qwen3-Next's four read
# 0.0130-0.0135) — and the reference itself in bfloat16 0.0103: the program's own precision, inside, as it should be.
# The slips: the router reading N2(x') 0.0442, the global layer rotated 0.0440, a band of 2048 0.0617, SiLU for ReLU
# 0.0696, the weights not renormalised 0.1388. 1.6e-2 is a quarter above the program's largest reading and 2.7 times
# under the least slip. By the LOSS every one of the five is inside 2e-3 (5.8e-4 to 1.75e-3: at a random init the loss
# sits at ln V whatever the layers do), so the hidden state is what holds them here
LIMITS = {"hidden": 1.6e-2}
# slips that the limits here cannot tell from the program, each with its reason: none
SAID_NOT_REQUIRED = {}


def reference_atol() -> float:
    with open(os.path.join(HERE, "worker.py")) as f:
        return float(re.search(r"^REFERENCE_ATOL = (\S+)", f.read(), re.M).group(1))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="smallthinker-21b-a3b-1g")
    ap.add_argument("--traffic", default="fused-b2-s16384")
    ap.add_argument("--seeds", type=int, default=9)
    ap.add_argument("--first", type=int, default=2147483700, help="the first seed; the others follow at odd strides")
    ap.add_argument("--rehearse", action="store_true", help="tiny sizes on whatever backend there is")
    args = ap.parse_args()

    from torchft_tpu.utils.compile_cache import place_compile_cache

    place_compile_cache()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from common import load_json, load_module
    from torchft_tpu.models import transformer as T

    config = load_json(os.path.join(HERE, "configs", args.config + ".json"))
    traffic = load_json(os.path.join(HERE, "traffic", args.traffic + ".json"))
    tc = dict(config["program"]["transformer_config"])
    batch, seq = int(traffic["batch"]), int(traffic["seq"])
    if args.rehearse:
        rehearsal = load_json(os.path.join(HERE, "tests", "rehearsal.json"))
        tc.update(rehearsal["transformer_config"])
        tc.update((config.get("rehearsal") or {}).get("transformer_config", {}))
        batch, seq = rehearsal["traffic"]["batch"], rehearsal["traffic"]["seq"]
    ref = load_module(os.path.join(HERE, "reference", config["program"]["reference"] + ".py"), "bench_reference")
    cfg = T.TransformerConfig(**{**tc, "dtype": jnp.dtype(tc["dtype"])})
    atol = reference_atol()
    dev = jax.devices()[0]
    print(f"[check] {args.config}: b{batch} x s{seq}, {args.seeds} seeds from {args.first}, {dev.device_kind} ({dev.platform}), atol {atol}", flush=True)

    init = jax.jit(lambda key: T.init_params(key, cfg))
    sys_loss = jax.jit(lambda p, t: T.loss_and_stats(p, t, cfg))
    sys_hidden = jax.jit(lambda p, t: T._hidden_states(p, t, cfg)[0])

    def reference(sizes):
        """(each sequence's loss [2], each sequence's final hidden state [2, S, d]), one sequence at a time."""
        def one(p, seq):
            hidden = ref.hidden(p, seq[None], sizes)
            return jnp.mean(ref._nll(hidden @ jnp.asarray(p["out"], jnp.float32), seq[None])), hidden[0]

        return jax.jit(lambda p, t: jax.lax.map(lambda seq: one(p, seq), t))

    ref_loss = reference(tc)

    def distance(a, b):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))

    def case(seed):
        params = init(jax.random.PRNGKey(seed))
        first = np.random.default_rng(seed * 1000).integers(0, cfg.vocab_size, (batch, seq))
        return params, jnp.asarray(first[:2], jnp.int32)

    readings = []
    for i in range(args.seeds):
        seed = args.first + 7919 * i * (i + 1)  # an odd stride that grows, past 2**31 from the start
        params, two = case(seed)
        got, said = [], {}
        for j in range(2):
            loss, stats = sys_loss(params, two[j : j + 1])
            got.append(float(loss))
            said = {"rows_held": np.asarray(stats["rows_held"]).tolist(), "gate_zero_share": [round(float(z), 4) for z in stats["gate_zero_share"]]}
        hidden = jnp.concatenate([sys_hidden(params, two[j : j + 1]) for j in range(2)])
        with jax.default_matmul_precision("highest"):
            want, want_hidden = ref_loss(params, two)
        want = [float(x) for x in want]
        err, far = max(abs(a - b) for a, b in zip(got, want)), distance(hidden, want_hidden)
        readings.append({"seed": seed, "system": got, "reference": want, "max_abs_diff": err, "hidden": far, **said})
        print(f"[check] seed {seed}: system {got} reference {want} max|diff| {err:.3g} hidden {far:.4g}; {said}", flush=True)
        if i == 0:
            kept = (params, two, got, hidden)  # the slips are read against the first seed's program

    # -- one thing slipped on the reference's side, each by itself, at the first seed
    params, two, got, hidden = kept
    slipped = {}

    def against(name, fn, precision="highest", p=params):
        with jax.default_matmul_precision(precision):
            want, want_hidden = fn(p, two)
        want = [float(x) for x in want]
        slipped[name] = {"loss": max(abs(a - b) for a, b in zip(got, want)), "hidden": distance(hidden, want_hidden)}
        print(f"[check] {name}: reference {want} max|diff| {slipped[name]['loss']:.3g} hidden {slipped[name]['hidden']:.4g}", flush=True)

    against("router_reads_the_ffn_input", reference(dict(tc, router_input="ffn")))
    unrotated, ref.UNROTATED = ref.UNROTATED, ()  # the same leaves, layer 1's q and k rotated like the window layers'
    against("global_layer_rotated", reference(tc))
    ref.UNROTATED = unrotated
    against("silu_for_relu", reference(dict(tc, expert_activation="silu")))
    against("band_of_2048", reference(dict(tc, window=tc["window"] // 2)))
    against("not_renormalised", reference(dict(tc, router_renormalize=False)))

    rounded = jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16).astype(jnp.float32), params)
    against("reference_in_bfloat16", ref_loss, precision="bfloat16", p=rounded)

    worst, farthest = max(r["max_abs_diff"] for r in readings), max(r["hidden"] for r in readings)
    inside = [name for name in SLIPS if slipped[name]["loss"] <= atol and slipped[name]["hidden"] <= LIMITS["hidden"]]
    ok = worst <= atol and farthest <= LIMITS["hidden"] and (args.rehearse or not set(inside) - set(SAID_NOT_REQUIRED))
    print(json.dumps({
        "ok": bool(ok), "atol": atol, "limits": LIMITS, "worst": worst, "room": atol / worst if worst else None,
        "farthest_hidden": farthest, "readings": readings, "slips": slipped, "slips_inside_the_limits": inside,
        "said_not_required": SAID_NOT_REQUIRED, "batch": batch, "seq": seq,
        "device": {"platform": dev.platform, "kind": dev.device_kind},
    }))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
