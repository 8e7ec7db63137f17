#!/usr/bin/env python3
"""The comparison that decides the SDAR cell's ``correct`` (``worker.py``:
``loss_fn`` against ``reference/sdar_moe_decoder.py`` on two sequences of the
seeded first batch, |difference| a sequence within ``REFERENCE_ATOL``), made on
MANY seeds in one process, at the configuration's real widths and the
traffic's real length (guide §3.3):

    python3 benchmark/check_sdar.py [--config sdar-30b-a3b-1g] [--traffic fused-b2-s8192] [--seeds 9] [--first N]

The weights are ``init_params(PRNGKey(seed))`` and the two sequences the first
two of ``default_rng(seed * 1000).integers(...)``'s first batch, as the worker
of group 0 draws them; the program runs as the cell runs it (bfloat16, the
core ``_attention_path`` picks on this backend: on a TPU the flash kernel under
the staircase, 16 384 rows of 128 lanes resident, groups of 8), the reference
in float32 at "highest" precision with its OWN noise from the stated
derivation. A sequence's loss is said beside the share of positions masked,
the mean of m/t and the rows held.

Beside the loss, each comparison reads the relative L2 distance of the NOISED
half's final hidden state of the two sequences (‖a − b‖ / ‖b‖ over
``[2, S, d]``: the program's ``_hidden_states`` in bfloat16 against the
reference's ``hidden``): with every weight at its initial scale a position's
cross entropy is close to ln(18 992) whatever the layers do, so the LOSS at the
harness's tolerance tells a slip of the objective (a weight, a target) and the
hidden state a slip of the layers (the mask, the positions, the router).

Then, at the first seed, the reference with ONE thing slipped, each by itself
(``sdar_moe_decoder.SLIPS``): a noised row also seeing its OWN clean block (the
answer leaks); the noised rows rotated at S + p; weight 1 for 1/t; causal
inside the block; targets shifted by one; the chosen experts' weights not
renormalised. Each has to fall outside a limit — the loss's
(``worker.REFERENCE_ATOL``, read from that file, not restated here) or the
hidden state's (:data:`LIMITS`); one that falls inside both is SAID
(``slips_inside_the_limits``) and fails the check unless
:data:`SAID_NOT_REQUIRED` names it with the reason — every one of the six is
held besides in float32 on the CPU (``tests/test_block_diffusion.py``: loss to
2e-6 and every gradient leaf, each slip failing by 20 times that). The
reference with its leaves and products in bfloat16 is read and said too: the
nearest precision below float32 IS the program's own, so the limits cannot and
should not tell it from the program.

Last, the noise: (t, m) of the first seed's two sequences drawn by the
program's ``_diffusion_noise`` compiled for THIS backend against the same
compiled for the host's CPU, bit for bit, and against the reference's.

Prints one JSON line last; exits 1 if a seed is outside a limit, a required
slip is inside both, or the noise differs. Not a benchmark cell: nothing is timed.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(HERE), HERE]

# The hidden state's limit (the loss's is worker.REFERENCE_ATOL). Readings on the v5e at b2 x s8192, widths as published,
# six layers, nine seeds from 2147483700 (my chip run, PR 65; PERF.md §6 holds the table). The program against the
# float32 reference: 0.0164, 0.0129, 0.0144, 0.0121, 0.0110, 0.0125, 0.0124, 0.0190, 0.0119 — bfloat16 through six layers
# over 2·S rows, and from seed to seed by a factor of 1.7 — and the
# reference itself in bfloat16 0.0155 at the first seed, where the program reads 0.0164: the program's own precision,
# inside, as it should be. The four slips that move the LAYERS, at the first seed: causal inside the block 0.0254, the
# own clean block seen 0.0291, the noised rows rotated at S + p 0.1014, the weights not renormalised 0.1330. 2.2e-2 is
# 1.16 times the program's largest reading and 1.15 times under the least slip: little room on either side, since at a
# random init a block's own four clean keys among ~4 100 move little (PERF.md §7 asks for a reading that divides a
# slip's distance by the SAME seed's: 1.55 and 1.77 for the two near ones). The two slips of the OBJECTIVE leave the
# hidden state where it is (0.0164) and move the loss: targets shifted by 0.0272, weight 1 by 5.29, of 2e-3. By the
# LOSS alone the own clean block (4.5e-4) and the causal block (4.3e-4) are inside 2e-3: the hidden state holds them
LIMITS = {"hidden": 2.2e-2}
# slips that the limits here cannot tell from the program, each with its reason: none
SAID_NOT_REQUIRED = {}


def reference_atol() -> float:
    with open(os.path.join(HERE, "worker.py")) as f:
        return float(re.search(r"^REFERENCE_ATOL = (\S+)", f.read(), re.M).group(1))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="sdar-30b-a3b-1g")
    ap.add_argument("--traffic", default="fused-b2-s8192")
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

    def reference(slip=None):
        """(each sequence's loss [2], each sequence's noised rows' final state [2, S, d]), one sequence at a time."""
        def one(p, seq):
            hidden, t, m = ref.hidden(p, seq, tc, slip)
            return ref.loss_of_hidden(p, hidden, t, m, seq, tc, slip), hidden

        return jax.jit(lambda p, t: jax.lax.map(lambda seq: one(p, seq), t))

    ref_loss = reference()

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
            said = {
                "masked_share": round(float(stats["masked_share"]), 4), "noise_weight_mean": round(float(stats["noise_weight_mean"]), 4),
                "rows_held": np.asarray(stats["rows_held"]).tolist(),
            }
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

    for slip in ref.SLIPS:
        against(slip, reference(slip))
    rounded = jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16).astype(jnp.float32), params)
    against("reference_in_bfloat16", ref_loss, precision="bfloat16", p=rounded)

    # -- the noise: this backend against the host's CPU, and against the reference's own
    noise = {"backend": dev.platform}
    here = jax.jit(lambda t: T._diffusion_noise(t, cfg))(two)
    theirs = jax.jit(lambda t: jax.lax.map(lambda seq: ref.noise(seq, tc), t))(two)
    noise["program_is_reference"] = all(bool(jnp.array_equal(a, b)) for a, b in zip(here, theirs))
    try:
        cpu = jax.devices("cpu")[0]
        on_cpu = jax.jit(lambda t: T._diffusion_noise(t, cfg), device=cpu)(jax.device_put(two, cpu))
        noise["this_backend_is_cpu"] = all(bool(np.array_equal(np.asarray(a), np.asarray(b))) for a, b in zip(here, on_cpu))
    except RuntimeError as e:  # no CPU backend beside the chip's: said, and the check fails
        noise["this_backend_is_cpu"] = f"no cpu backend: {e}"
    noise["t_min_max"] = [float(here[0].min()), float(here[0].max())]
    print(f"[check] noise: {noise}", flush=True)

    worst, farthest = max(r["max_abs_diff"] for r in readings), max(r["hidden"] for r in readings)
    inside = [name for name in ref.SLIPS if slipped[name]["loss"] <= atol and slipped[name]["hidden"] <= LIMITS["hidden"]]
    same_noise = noise["program_is_reference"] is True and noise["this_backend_is_cpu"] is True
    ok = (
        worst <= atol and farthest <= LIMITS["hidden"] and same_noise
        and (args.rehearse or not set(inside) - set(SAID_NOT_REQUIRED))
    )
    print(json.dumps({
        "ok": bool(ok), "atol": atol, "limits": LIMITS, "worst": worst, "room": atol / worst if worst else None,
        "farthest_hidden": farthest, "readings": readings, "slips": slipped, "slips_inside_the_limits": inside,
        "said_not_required": SAID_NOT_REQUIRED, "noise": noise, "batch": batch, "seq": seq,
        "device": {"platform": dev.platform, "kind": dev.device_kind},
    }))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
