#!/usr/bin/env python3
"""The comparison that decides the looped cell's ``correct`` (``worker.py``:
``loss_fn`` against ``reference/ouro_decoder.py`` on two sequences of the
seeded first batch, |difference| a sequence within ``REFERENCE_ATOL``), made
on MANY seeds in one process, at the configuration's real widths and the
traffic's real length (guide §3.3):

    python3 benchmark/check_ouro.py [--config ouro-2_6b-1g] [--traffic fused-b4-s4096] [--seeds 13] [--first N]

The weights are ``init_params(PRNGKey(seed))`` and the two sequences the first
two of ``default_rng(seed * 1000).integers(...)``'s first batch, as the worker
of group 0 draws them; the program runs as the cell runs it (bfloat16, the
kernels ``_attention_path`` picks on this backend), the reference in float32 at
"highest" precision.

Then, at the first seed, the reference with ONE thing slipped. Three have to
fall outside the limit: no entropy term (β = 0), a loop of three, the layers'
output norms left out (read on the v5e, PR 58: 0.101, 0.0131, 0.0059 of 0.002).
Two are read and SAID, not required: the final norm not carried into the next
turn (0.00197: with every norm's weight at its initial one, a turn's first
norm undoes most of it — ``tests/test_looped_train.py`` holds the carried
state itself, in float32, where the loss at this tolerance barely can), and
the reference with its leaves and products in bfloat16 (5.3e-5: the nearest
precision below float32 IS the program's own, so the limit cannot and should
not tell it from the program). The limit is the harness's
(``worker.REFERENCE_ATOL``, read from that file, not restated here).

Prints one JSON line last; exits 1 if a seed is outside the limit or a slip of
the mathematics is inside it. Not a benchmark cell: nothing is timed.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(HERE), HERE]

SLIPS = ("no_entropy_term", "a_loop_of_three", "no_output_norms")  # these have to be outside the limit


def reference_atol() -> float:
    with open(os.path.join(HERE, "worker.py")) as f:
        return float(re.search(r"^REFERENCE_ATOL = (\S+)", f.read(), re.M).group(1))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="ouro-2_6b-1g")
    ap.add_argument("--traffic", default="fused-b4-s4096")
    ap.add_argument("--seeds", type=int, default=13)
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
        batch, seq = rehearsal["traffic"]["batch"], rehearsal["traffic"]["seq"]
    ref = load_module(os.path.join(HERE, "reference", config["program"]["reference"] + ".py"), "bench_reference")
    cfg = T.TransformerConfig(**{**tc, "dtype": jnp.dtype(tc["dtype"])})
    atol = reference_atol()
    dev = jax.devices()[0]
    print(f"[check] {args.config}: b{batch} x s{seq}, {args.seeds} seeds from {args.first}, {dev.device_kind} ({dev.platform}), atol {atol}", flush=True)

    init = jax.jit(lambda key: T.init_params(key, cfg))
    sys_loss = jax.jit(lambda p, t: T.loss_fn(p, t, cfg))

    def reference(sizes, module=ref):
        return jax.jit(lambda p, t: module.per_sequence_loss(p, t, sizes))

    ref_loss = reference(tc)

    def case(seed):
        params = init(jax.random.PRNGKey(seed))
        first = np.random.default_rng(seed * 1000).integers(0, cfg.vocab_size, (batch, seq))
        return params, jnp.asarray(first[:2], jnp.int32)

    readings = []
    for i in range(args.seeds):
        seed = args.first + 7919 * i * (i + 1)  # an odd stride that grows, past 2**31 from the start
        params, two = case(seed)
        got = [float(sys_loss(params, two[j : j + 1])) for j in range(2)]
        with jax.default_matmul_precision("highest"):
            want = [float(x) for x in ref_loss(params, two)]
        err = max(abs(a - b) for a, b in zip(got, want))
        readings.append({"seed": seed, "system": got, "reference": want, "max_abs_diff": err})
        print(f"[check] seed {seed}: system {got} reference {want} max|diff| {err:.3g}", flush=True)
        del params

    # -- one thing slipped on the reference's side, each by itself, at the first seed
    params, two = case(args.first)
    got = readings[0]["system"]
    slipped = {}

    def against(name, fn, precision="highest", p=params):
        with jax.default_matmul_precision(precision):
            want = [float(x) for x in fn(p, two)]
        slipped[name] = max(abs(a - b) for a, b in zip(got, want))
        print(f"[check] {name}: reference {want} max|diff| {slipped[name]:.3g}", flush=True)

    against("no_entropy_term", reference(dict(tc, exit_entropy_coef=0.0)))
    against("a_loop_of_three", reference(dict(tc, ut_steps=3)))

    other = load_module(os.path.join(HERE, "reference", config["program"]["reference"] + ".py"), "bench_reference_slipped")
    norm, layer = other._rms_norm, other._layer
    # the layers' two output norms as the identity: a weight of None passes its input on
    other._rms_norm = lambda x, weight, eps: x if weight is None else norm(x, weight, eps)
    other._layer = lambda w, u, sizes: layer({**w, "post_ln1": None, "post_ln2": None}, u, sizes)
    against("no_output_norms", reference(tc, other))
    other._layer = layer

    def hidden_states_not_carried(p, tokens, sizes):
        f32 = lambda a: jnp.asarray(a, jnp.float32)
        u = f32(p["embed"])[tokens]
        states = []
        for _ in range(sizes["ut_steps"]):
            for i in range(sizes["n_layers"]):
                u = other._layer({name: f32(leaf[0, i]) for name, leaf in p["layers"].items()}, u, sizes)
            states.append(other._rms_norm(u, f32(p["final_norm"]), sizes.get("norm_eps", 1e-6)))  # u itself goes on
        return jnp.stack(states)

    other.hidden_states = hidden_states_not_carried
    against("final_norm_not_carried", reference(tc, other))

    rounded = jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16).astype(jnp.float32), params)
    against("reference_in_bfloat16", ref_loss, precision="bfloat16", p=rounded)

    worst = max(r["max_abs_diff"] for r in readings)
    inside = [name for name in SLIPS if slipped[name] <= atol]
    ok = worst <= atol and (args.rehearse or not inside)
    print(json.dumps({
        "ok": bool(ok), "atol": atol, "worst": worst, "room": atol / worst if worst else None, "readings": readings,
        "slips": slipped, "slips_inside_the_limit": inside, "batch": batch, "seq": seq,
        "device": {"platform": dev.platform, "kind": dev.device_kind},
    }))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
