#!/usr/bin/env python3
"""The Keye cell's program against ``reference/keye_sparse_decoder.py`` at the
configuration's real widths and the traffic's real length, on MANY seeds in one
process, on the timed path's own program (bfloat16, the kernels
``_attention_path`` picks on this backend) (guide §3.3):

    python3 benchmark/check_keye.py [--seeds 3] [--first N] [--phases compare,grads,slips,steps] [--steps 48]

A selection flips on rounding — two keys whose scores differ by less than the
bfloat16 indexer's error change places at a row's threshold — so the comparison
has two parts, each seed:

(a) THE SETS, layer by layer: the reference computes its float32 scores from
    what the PROGRAM's selector of that layer read (the layer's normed input, as
    the program had it: ``selections(..., with_inputs=True)``), so what differs
    is the selector's own rounding — its three projections, the rotation and
    the products in bfloat16 — and not the six layers' drift, which (b) holds;
    every pair the program selects and the reference does not, or the reverse,
    has to have a reference score within :data:`EPS` of its row's spread of
    that row's reference threshold. Printed: the share of selected pairs that
    differ at all, the pairs outside the band (0 required), the band the
    farthest differing pair needed a layer, ``selected_over_k``.
(b) THE VALUES GIVEN THE SETS: with the program's sets handed to the reference
    (``selection=``): |next-token loss − reference|, |each layer's term −
    reference| and the total against ``worker.REFERENCE_ATOL`` (read from that
    file), the logits of a block of positions (relative L2), and — phase
    ``grads``, first seed — the gradient of every leaf against the reference's
    ``jax.grad`` (relative L2 a leaf; the reference computes a block of queries
    again in its backward), limits :data:`LIMITS`.

Also ``worker.py``'s own comparison (the reference under its OWN sets, no
hand-over) — what decides the cell's ``correct`` — is read on every seed.

Phase ``slips`` (first seed): the reference with ONE thing slipped
(``keye_sparse_decoder.SLIPS``) has to fall outside a limit — the sets', a
loss part's, the logits' or, for the two that only move where a gradient goes,
a gradient leaf's (phase ``grads`` on). Phase ``steps``: ``TrainStep.step`` for
``--steps`` steps at the configuration's learning rate, ``lm_loss``,
``indexer_loss``, ``window_live_share`` (max over layers) and
``sparse_tiles_live_share`` step by step, the fused program's memory by the
compiler and the device's ``memory_stats`` after.

Prints one JSON line last (and writes it under ``chiprun_out/``); exits 1 if a
seed is outside a limit or a slip is inside all. Not a benchmark cell: nothing
here is a timing the ledger reads.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(HERE), HERE]

# (a) the band, as a share of a row's spread of reference scores. Readings on the v5e at b1 x s16384, widths as
# published, six layers, three seeds from 2147483700 (my chip run, PR 71; PERF.md §6 holds the table): of 31.5 M selected
# pairs a layer 150 068-151 763 differ (0.48 %), and the farthest of them lies 0.00318-0.00405 of its row's spread from
# the row's reference threshold, layer by layer — 1.6 to 2.1 times 2^-9, the bfloat16 rounding of the selector's
# projections. 8e-3 = 4 x 2^-9 is twice the largest reading; a slip that changes the sets puts most of its differing pairs
# outside any such band (tests/test_sparse_attention.py counts them)
EPS = 8.0e-3
# (b) beside the loss's limit (worker.REFERENCE_ATOL): a layer's term, the logits' and a gradient leaf's relative L2
# distance. Readings (my chip runs, PR 71): |term - reference| 4.5e-5 to 8.4e-5 over three seeds at six layers, the
# logits 0.0139-0.0146 (bfloat16 through six layers; twice that is the limit; the float32 reference's own bfloat16
# form reads 0.0221 from it). The gradients at TWO layers (``--layers 2``: the reference's backward at six does not fit
# the chip beside both gradient trees): the selector's three 0.0062-0.0230, the core's projections and norms
# 0.012-0.017, the tables 0.0074-0.0121, and what lies behind the router — ln2 0.0586, router 0.0655, the held experts
# 0.0567-0.0571 — where a row whose k-th and (k+1)-th expert change places in bfloat16 moves whole rows of a gradient;
# ``indexer_sees_gradient`` and ``target_not_detached`` read 0.997 and 0.998 there. 0.15 is 2.3 times the largest
# reading and a sixth of the slips'
LIMITS = {"logits": 3.0e-2, "grad": 1.5e-1, "term": 2.0e-3}


def reference_atol() -> float:
    with open(os.path.join(HERE, "worker.py")) as f:
        return float(re.search(r"^REFERENCE_ATOL = (\S+)", f.read(), re.M).group(1))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="keye-vl-2_0-30b-a3b-1g")
    ap.add_argument("--traffic", default="fused-b1-s16384")
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--first", type=int, default=2147483700, help="the first seed; the others follow at odd strides")
    ap.add_argument("--phases", default="compare,grads,slips,steps")
    ap.add_argument("--steps", type=int, default=48)
    ap.add_argument("--layers", type=int, default=0, help="another depth than the configuration's (memory)")
    ap.add_argument("--rehearse", action="store_true", help="tiny sizes on whatever backend there is")
    args = ap.parse_args()
    phases = set(args.phases.split(","))

    from torchft_tpu.utils.compile_cache import place_compile_cache

    place_compile_cache()
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from common import load_json, load_module
    from torchft_tpu.models import transformer as T
    from torchft_tpu.parallel.mesh import MeshConfig, make_mesh
    from torchft_tpu.parallel.train_step import TrainStep

    config = load_json(os.path.join(HERE, "configs", args.config + ".json"))
    traffic = load_json(os.path.join(HERE, "traffic", args.traffic + ".json"))
    tc = dict(config["program"]["transformer_config"])
    batch, seq = int(traffic["batch"]), int(traffic["seq"])
    if args.rehearse:
        rehearsal = load_json(os.path.join(HERE, "tests", "rehearsal.json"))
        tc.update(rehearsal["transformer_config"])
        tc.update((config.get("rehearsal") or {}).get("transformer_config", {}))
        batch, seq = rehearsal["traffic"]["batch"], rehearsal["traffic"]["seq"]
    if args.layers:
        tc["n_layers"] = args.layers
    ref = load_module(os.path.join(HERE, "reference", config["program"]["reference"] + ".py"), "bench_reference")
    cfg = T.TransformerConfig(**{**tc, "dtype": jnp.dtype(tc["dtype"])})
    layers, top = cfg.n_layers, cfg.sparse_topk
    atol = reference_atol()
    dev = jax.devices()[0]
    print(f"[check] {args.config}: b{batch} x s{seq} x {layers} layers, {args.seeds} seeds from {args.first}, {dev.device_kind} ({dev.platform}), atol {atol}, eps {EPS}", flush=True)
    f32 = lambda a: jnp.asarray(a, jnp.float32)

    init = jax.jit(lambda key: T.init_params(key, cfg))
    sys_loss = jax.jit(lambda p, t: T.loss_and_stats(p, t, cfg))
    sys_sets = jax.jit(lambda p, t: tuple(a[:, 0] for a in T.selections(p, t, cfg, with_inputs=True)))  # [L, S, S], [L, S, d]
    sys_logits = jax.jit(lambda p, t: T.forward(p, t, cfg)[0, -512:])

    @jax.jit
    def sets_apart(w, read, theirs):
        """(a) of one layer: the reference's own sets from what the program's selector ``read`` against ``theirs``."""
        q_i, k_i, weight = ref._indexer(w, f32(read), tc, None)
        block = ref.QUERY_BLOCK if seq % ref.QUERY_BLOCK == 0 else seq

        def some(start):
            rows = lambda a: jax.lax.dynamic_slice_in_dim(a, start, block, axis=0)
            causal = jnp.arange(seq)[None, :] <= (start + jnp.arange(block))[:, None]
            scores = ref.indexer_scores(rows(q_i), k_i, rows(weight))
            own = ref.selection(scores, causal, top)
            tau = jnp.min(jnp.where(own, scores, jnp.inf), axis=-1, keepdims=True)
            spread = jnp.max(jnp.where(causal, scores, -jnp.inf), axis=-1, keepdims=True) - jnp.min(jnp.where(causal, scores, jnp.inf), axis=-1, keepdims=True)
            differ = own != rows(theirs)
            off = jnp.where(differ, jnp.abs(scores - tau) / jnp.maximum(spread, 1e-30), 0.0)
            return jnp.sum(differ), jnp.sum(off > EPS), jnp.max(off), jnp.sum(off > 0.5 * EPS)

        differ, outside, needed, half = jax.lax.map(some, jnp.arange(0, seq, block))
        return dict(differ=jnp.sum(differ), outside=jnp.sum(outside), needed=jnp.max(needed), past_half=jnp.sum(half), selected=jnp.sum(theirs))

    @jax.jit
    def ref_layer(w, x, theirs):
        """One reference layer under the PROGRAM's sets ``theirs``: (its output, its term)."""
        x_next, term, _, _ = ref.layer(w, x, tc, None, theirs)
        return x_next, term

    @jax.jit
    def ref_head(p, x, seq_ids):
        h = ref._rms_norm(x, f32(p["final_norm"]), tc.get("norm_eps", 1e-6))
        logits = h @ f32(p["out"])
        logp = jax.nn.log_softmax(logits[:-1], axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, seq_ids[1:, None], axis=-1)), logits[-512:]

    def under_their_sets(params, ids, sets, read):
        """The reference, layer by layer, under the program's sets: (next-token loss, terms [L], logits of the last 512 positions, (a) a layer)."""
        x, terms, said = f32(params["embed"])[ids], [], []
        with jax.default_matmul_precision("highest"):
            for i in range(layers):
                w = {k: f32(v[0, i]) for k, v in params["layers"].items()}
                said.append({k: float(v) for k, v in sets_apart(w, read[i], sets[i]).items()})
                x, term = ref_layer(w, x, sets[i])
                terms.append(float(term))
            lm, logits = ref_head(params, x, ids)
        return float(lm), terms, logits, said

    ref_own = jax.jit(lambda p, t, slip=None: ref.per_sequence_loss(p, t, tc, slip), static_argnums=2)

    def distance(a, b):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        return float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-30))

    def case(seed):
        params = init(jax.random.PRNGKey(seed))
        first = np.random.default_rng(seed * 1000).integers(0, cfg.vocab_size, (batch, seq))
        return params, jnp.asarray(first[:1], jnp.int32)

    # -- the step itself, step by step: FIRST, on an empty chip — state 7.9 GB and the fused program's 6.1 GiB of
    # temporaries leave no room beside the comparisons' arrays
    steps = {}
    if "steps" in phases:
        mesh = make_mesh(MeshConfig(**config["layout"]["mesh"]), devices=jax.devices()[:1])
        ts = TrainStep(cfg, optax.adamw(config["program"]["learning_rate"]), mesh)
        seed = args.first
        params = ts.init_params(jax.random.PRNGKey(seed))
        opt = ts.init_opt(params)
        rng = np.random.default_rng(seed * 1000)
        rows, times = [], []
        for n in range(args.steps):
            tokens = ts.shard_batch(jnp.asarray(rng.integers(0, cfg.vocab_size, (batch, seq)), jnp.int32))
            t0 = time.monotonic()
            loss, params, opt = ts.step(params, opt, tokens)
            loss = float(loss)
            times.append(time.monotonic() - t0)
            s = ts.last_stats
            rows.append(dict(
                step=n, loss=loss, lm=float(s["lm_loss"]), term=float(jnp.sum(s["indexer_loss"])),
                window_live_max=float(jnp.max(s["window_live_share"])), tiles_live=float(jnp.mean(s["sparse_tiles_live_share"])),
                over_k=float(s["selected_over_k"]), selected_mean=float(s["selected_mean"]),
            ))
            print(f"[check] step {json.dumps(rows[-1])} {times[-1]:.3f}s", flush=True)
        stats = dev.memory_stats() or {}
        steps = dict(
            rows=rows, step_s_median=float(np.median(times[2:])) if len(times) > 2 else None,
            memory_peak_bytes=stats.get("peak_bytes_in_use"), bytes_limit=stats.get("bytes_limit"),
        )
        print(f"[check] steps: median {steps['step_s_median']} s, peak {steps['memory_peak_bytes']} of {steps['bytes_limit']}", flush=True)
        del ts, params, opt, tokens  # the state and the fused program leave the chip before the comparisons take it
        jax.clear_caches()

    readings, kept = [], None
    for i in range(args.seeds if "compare" in phases else 0):
        seed = args.first + 7919 * i * (i + 1)  # an odd stride that grows, past 2**31 from the start
        params, one = case(seed)
        t0 = time.monotonic()
        loss, stats = sys_loss(params, one)
        sets, read = sys_sets(params, one)
        got = dict(loss=float(loss), lm=float(stats["lm_loss"]), terms=[float(x) for x in stats["indexer_loss"]])
        lm, terms, ref_logits, a = under_their_sets(params, one[0], sets, read)
        del read
        with jax.default_matmul_precision("highest"):
            own = float(ref_own(params, one)[0])  # worker.py's comparison: the reference under its OWN sets
        selected = sum(x["selected"] for x in a)
        r = {
            "seed": seed, "system": got, "reference": dict(loss=lm + sum(terms), lm=lm, terms=terms, own_sets_loss=own),
            "lm_diff": abs(got["lm"] - lm), "term_diff": max(abs(x - y) for x, y in zip(got["terms"], terms)),
            "loss_diff": abs(got["loss"] - lm - sum(terms)), "worker_diff": abs(got["loss"] - own),
            "logits": distance(sys_logits(params, one), ref_logits),
            "differ_share": sum(x["differ"] for x in a) / max(selected, 1), "outside": sum(x["outside"] for x in a),
            "needed_eps": max(x["needed"] for x in a), "needed_by_layer": [round(x["needed"], 5) for x in a],
            "past_half_eps": sum(x["past_half"] for x in a), "differ_by_layer": [x["differ"] for x in a],
            "selected_mean": float(stats["selected_mean"]), "selected_over_k": float(stats["selected_over_k"]),
            "tiles_live": [float(x) for x in stats["sparse_tiles_live_share"]],
            "window_live_share": [float(x) for x in stats["window_live_share"]], "seconds": round(time.monotonic() - t0, 1),
        }
        readings.append(r)
        print(f"[check] seed {seed}: {json.dumps(r)}", flush=True)
        if i == 0:
            kept = (params, one, sets, got)

    # -- the gradient of every leaf against the reference's jax.grad under the program's sets, first seed
    grads = {}
    if "grads" in phases and kept:
        params, one, sets, got = kept

        def ref_total(p, slip=None):
            """The reference's loss under the program's sets, a ``jax.checkpoint`` a layer: its backward holds one layer's arrays."""
            x, total = f32(p["embed"])[one[0]], 0.0
            for i in range(layers):
                w = {k: f32(v[0, i]) for k, v in p["layers"].items()}
                x, term = jax.checkpoint(lambda w, x, given: ref.layer(w, x, tc, slip, given)[:2])(w, x, sets[i])
                total = total + term
            h = ref._rms_norm(x, f32(p["final_norm"]), tc.get("norm_eps", 1e-6))
            logp = jax.nn.log_softmax(h[:-1] @ f32(p["out"]), axis=-1)
            return total - jnp.mean(jnp.take_along_axis(logp, one[0][1:, None], axis=-1))

        g_sys = jax.jit(jax.grad(lambda p: T.loss_fn(p, one, cfg)))(params)

        def grad_errors(slip=None):
            with jax.default_matmul_precision("highest"):
                g_ref = jax.jit(jax.grad(lambda p: ref_total(p, slip)))(params)
            errs = jax.tree_util.tree_map(distance, g_sys, g_ref)
            return {**{k: v for k, v in errs.items() if k != "layers"}, **errs["layers"]}

        grads["program"] = grad_errors()
        print(f"[check] grads: {json.dumps(grads['program'])}", flush=True)
        if "slips" in phases:
            for slip in ("indexer_sees_gradient", "target_not_detached"):
                grads[slip] = grad_errors(slip)
                print(f"[check] grads under {slip}: {json.dumps(grads[slip])}", flush=True)
        del g_sys

    # -- one thing slipped on the reference's side, each by itself, at the first seed
    slipped = {}
    if "slips" in phases and kept:
        params, one, sets, got = kept
        program_logits = sys_logits(params, one)

        def slipped_reference(p, slip, given):
            h, terms, _, _ = ref.hidden(p, one[0], tc, slip, given)
            logp = jax.nn.log_softmax(h[:-1] @ f32(p["out"]), axis=-1)
            return -jnp.mean(jnp.take_along_axis(logp, one[0][1:, None], axis=-1)), terms, h[-512:] @ f32(p["out"])

        for slip in ref.SLIPS:
            with jax.default_matmul_precision("highest"):
                own_rule = slip in ("no_selection", "topk_by_head", "indexer_unrotated")  # another rule for the sets: nothing to hand over
                lm, terms, logits = jax.jit(lambda p, s=slip, given=None if own_rule else sets: slipped_reference(p, s, given))(params)
            slipped[slip] = {
                "lm": abs(got["lm"] - float(lm)), "term": max(abs(x - float(y)) for x, y in zip(got["terms"], terms)),
                "logits": distance(program_logits, logits),
            }
            if slip in grads:
                slipped[slip]["grad"] = max(grads[slip].values())
            print(f"[check] {slip}: {json.dumps(slipped[slip])}", flush=True)
        # the reference itself in the program's precision (leaves and products in bfloat16, its own sets): said, not held —
        # the nearest precision below float32 IS the program's own, so the limits cannot and should not tell the two apart
        rounded = jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16).astype(jnp.float32), params)
        with jax.default_matmul_precision("bfloat16"):
            lm, terms, logits = jax.jit(lambda p: slipped_reference(p, None, None))(rounded)
        in_bf16 = {"lm": abs(got["lm"] - float(lm)), "term": max(abs(x - float(y)) for x, y in zip(got["terms"], terms)), "logits": distance(program_logits, logits)}
        print(f"[check] reference_in_bfloat16: {json.dumps(in_bf16)}", flush=True)
        slipped["reference_in_bfloat16"] = in_bf16

    worst = {k: max((r[k] for r in readings), default=0.0) for k in ("lm_diff", "term_diff", "loss_diff", "worker_diff", "logits", "outside", "needed_eps", "differ_share")}
    worst_grad = max(grads.get("program", {"": 0.0}).values())
    inside = [
        name for name, s in slipped.items()
        if name in ref.SLIPS and s["lm"] <= atol and s["term"] <= LIMITS["term"] and s["logits"] <= LIMITS["logits"] and s.get("grad", 0.0) <= LIMITS["grad"]
        and not (name in ("indexer_sees_gradient", "target_not_detached") and "grads" not in phases)
    ]
    ok = (
        worst["outside"] == 0 and worst["loss_diff"] <= atol and worst["worker_diff"] <= atol and worst["term_diff"] <= LIMITS["term"]
        and worst["logits"] <= LIMITS["logits"] and worst_grad <= LIMITS["grad"] and (args.rehearse or not inside)
    )
    out = {
        "ok": bool(ok), "atol": atol, "eps": EPS, "limits": LIMITS, "worst": worst, "worst_grad": worst_grad, "readings": readings,
        "grads": grads, "slips": slipped, "slips_inside_the_limits": inside, "steps": steps, "batch": batch, "seq": seq,
        "layers": layers, "device": {"platform": dev.platform, "kind": dev.device_kind},
    }
    os.makedirs(os.path.join(os.path.dirname(HERE), "chiprun_out"), exist_ok=True)
    with open(os.path.join(os.path.dirname(HERE), "chiprun_out", "check_keye.json"), "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps({k: v for k, v in out.items() if k != "steps"} | {"steps": {k: v for k, v in steps.items() if k != "rows"}}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
