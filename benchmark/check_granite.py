#!/usr/bin/env python3
"""The program against the plain reference on the chip, beyond the one loss
that ``worker.py`` compares (guide §3.3), for a hybrid of state-space mixers
and unrotated softmax layers at the configuration's real widths and the cell's
batch and length.

    python3 benchmark/check_granite.py [--config granite-4_0-h-micro-1g] [--seq 8192] [--seeds 1,2,...]

Three parts, each printed as it ends, one JSON line last; exits 1 if any fails.

1. **The harness's own comparison on every seed** (nine unless told): the
   weights ``init_params`` draws from the seed, ONE sequence of ``--seq`` ids,
   the program as the cell runs it (bfloat16 activations, the flash kernel at
   64 lanes where the backend is a TPU, the chunked state-space rule) against
   ``reference/granite_hybrid_decoder.py`` (float32 at "highest" matmul
   precision, the recurrence one position after another) by two distances:
   |loss − loss_ref| and the relative L2 distance ‖a − b‖ / ‖b‖ of the final
   hidden state. :data:`LIMITS` holds the limits with the readings they came from.
2. **The reference with ONE thing slipped** (:data:`SLIPS`, on the first seed),
   each by itself, each required to fall OUTSIDE a limit: the decay without the
   step (``exp(A)``), the skip left out, the gate applied AFTER the norm, the
   norm a head and not over all channels, the convolution's bias left out, the
   residual multiplier 1, the scores scaled by 1/√64 = 1/8, the attention layer
   rotated (:data:`HELD_ON_CPU`: random weights cannot show it), the logits not
   divided, B and C exchanged. Beside them, said and not
   held to a limit: the reference with its state rounded to bfloat16 after
   every position.
3. **``ssd_chunked`` against ``ssd_recurrent``** at the real head shapes (64
   heads x 64 over a state of 128, one B and C) in float32 over 2048 positions:
   with decays drawn as the assumed initial values give them, AND with ``A`` 16
   and ``Δ`` 1 everywhere — 16 nats a position, the regime that breaks a
   factored form (``exp(+Λ)`` overflows float32 after six positions).

Not a benchmark cell: nothing is timed.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(HERE), HERE]

# What the program may differ from the float32 reference by; every slip has to exceed one. Readings on the v5e at
# b1 x s8192, widths as published (my chip run, PR 69; PERF.md §6 holds the table).
LIMITS = {
    # worker.py's REFERENCE_ATOL, the limit that decides `correct`: read 0 - 4.67e-05 over nine seeds. Of the slips only
    # the logits not divided (5.11) shows here: on seeded random weights the loss is log(V) whatever the stack does
    "loss": 2e-3,
    # relative L2 of the final hidden state, bfloat16 through ten layers: read 0.0171 - 0.0175 over nine seeds; the
    # least slip that shows, the scores scaled by 1/8, reads 0.0250, the reference with its state rounded to bfloat16
    # after every position 0.0255, every other slip 0.53 - 1.04: 0.021 is a fifth above the largest reading and a
    # sixth under the least slip
    "hidden": 2.1e-2,
    # ssd_chunked against ssd_recurrent in float32 over 2048 positions, output and final state, relative L2: read
    # 2.95e-05 / 3.89e-05 at the initial values' decays and 1.29e-05 / 3.7e-08 at 16 nats a position (float32 sums of
    # 2048 positions in another order); a factored form reads inf at 16 nats, bfloat16 operands ~4e-3
    "core": 1e-4,
}
# A slip the chip's comparison cannot hold, with the reason; tests/test_ssd_hybrid.py holds it on the CPU by the
# gradient leaves (wq, wk of the attention layer move by far more than 20 times the agreement)
HELD_ON_CPU = {
    "attention_rotated": "read loss 3.81e-05, hidden 0.0174 — the program's own distance: on seeded random weights the "
    "scores q.k / 64 are ~N(0, 1/64), the softmax is near uniform whatever q and k are turned by, and one layer of ten "
    "joins the stream at 0.22",
}

# name -> ("sizes", {overrides of the reference's sizes}) or ("attr", the reference's function, wrong(right) -> its stand-in)
SLIPS = {
    "decay_without_the_step": ("attr", "_log_decay", lambda right: lambda delta, a: a + 0.0 * delta),
    "skip_left_out": ("attr", "_skip", lambda right: lambda w, x: 0.0 * x),
    "gate_after_the_norm": (
        "attr", "_gated_norm",
        lambda right: lambda y, z, weight, eps, heads: _rms(y, eps) * weight * _silu(z),
    ),
    "norm_a_head": (
        "attr", "_gated_norm",
        lambda right: lambda y, z, weight, eps, heads: (
            _rms((y * _silu(z)).reshape(y.shape[:-1] + (heads, -1)), eps).reshape(y.shape) * weight
        ),
    ),
    "conv_bias_left_out": ("attr", "_conv", lambda right: lambda w, u, taps: right({**w, "conv_b": 0.0 * w["conv_b"]}, u, taps)),
    "residual_multiplier_one": ("sizes", {"residual_scale": 1.0}),
    "scores_scaled_by_an_eighth": ("sizes", {"attn_scale": 0.0}),
    "attention_rotated": ("attr", "_placed", lambda right: lambda q, k, sizes: (_rotated(q), _rotated(k))),
    "logits_not_divided": ("sizes", {"logits_scale": 1.0}),
    "b_and_c_exchanged": ("attr", "_split", lambda right: lambda xbc, inner, n: (lambda x, b, c: (x, c, b))(*right(xbc, inner, n))),
}


def _silu(z):
    import jax

    return jax.nn.silu(z)


def _rms(x, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _rotated(x, theta: float = 10000.0):
    """x [B, S, H, D] rotated over the whole head, lane i with lane i + D/2, at the published ``rope_theta``."""
    import jax.numpy as jnp

    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(angle)[None, :, None, :], jnp.sin(angle)[None, :, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


@contextlib.contextmanager
def slipped(ref, name: str, sizes):
    """The reference module ``ref`` with the slip ``name`` planted, and put back; yields the sizes to hand it."""
    how, *what = SLIPS[name]
    if how == "sizes":
        yield {**sizes, **what[0]}
        return
    attr, wrong = what
    right = getattr(ref, attr)
    setattr(ref, attr, wrong(right))
    try:
        yield sizes
    finally:
        setattr(ref, attr, right)


def core_check(heads: int, head_dim: int, state: int, positions: int, chunk: int):
    """``ssd_chunked`` against ``ssd_recurrent`` in float32 at these head shapes: {regime: {output, state}} relative L2."""
    import jax
    import jax.numpy as jnp

    from torchft_tpu.ops.kda import ssd_chunked, ssd_recurrent

    keys = jax.random.split(jax.random.PRNGKey(0), 6)
    q, k = (jax.random.normal(key, (1, positions, state), jnp.float32) for key in keys[:2])
    x = jax.random.normal(keys[2], (1, positions, heads, head_dim), jnp.float32)
    a = jax.random.uniform(keys[3], (heads,), jnp.float32, 1.0, 16.0)
    step = jnp.exp(jax.random.uniform(keys[4], (heads,), jnp.float32, jnp.log(1e-3), jnp.log(1e-1)))
    delta = step * jnp.exp(jax.random.normal(keys[5], (1, positions, heads), jnp.float32))  # the step's input is about N(0, 1)
    regimes = {"initial_values": (-a * delta, x * delta[..., None]), "sixteen_nats": (jnp.full_like(delta, -16.0), x)}
    rel = lambda got, want: float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))
    out = {}
    with jax.default_matmul_precision("highest"):
        for name, (g, v) in regimes.items():
            o, s_end = jax.jit(lambda q, k, v, g: ssd_chunked(q, k, v, g, chunk=chunk))(q, k, v, g)
            o_ref, s_ref = jax.jit(ssd_recurrent)(q, k, v, g)
            out[name] = {"output": rel(o, o_ref), "state": rel(s_end, s_ref)}
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="granite-4_0-h-micro-1g")
    ap.add_argument("--seq", type=int, default=8192)
    ap.add_argument("--seeds", default="1,2,3,5,7,11,2147483659,3000000019,4000000007")
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
        args.seq = min(args.seq, 96)
    ref = load_module(os.path.join(HERE, "reference", config["program"]["reference"] + ".py"), "bench_reference")
    cfg = T.TransformerConfig(**{**tc, "dtype": jnp.dtype(tc["dtype"])})
    seeds = [int(s) for s in args.seeds.split(",")]
    dev = jax.devices()[0]
    print(f"[check] {args.config}: b1 x s{args.seq}, seeds {seeds}, {dev.device_kind} ({dev.platform})", flush=True)

    @jax.jit
    def program(p, t):
        return T.loss_fn(p, t, cfg), T._hidden_states(p, t, cfg)[0].astype(jnp.float32)

    def reference(sizes):
        def run(p, t):
            h = ref.hidden(p, t, sizes)
            table = p["embed"].T if sizes.get("tie_embeddings") else p["out"]
            return jnp.mean(ref._nll(h @ table / sizes.get("logits_scale", 1.0), t)), h

        with jax.default_matmul_precision("highest"):
            return jax.block_until_ready(jax.jit(run)(params, tokens))

    def distances(got, want):
        return {
            "loss": abs(float(got[0]) - float(want[0])),
            "hidden": float(jnp.linalg.norm(got[1] - want[1]) / jnp.linalg.norm(want[1])),
        }

    outside = lambda d: [k for k in d if not d[k] <= LIMITS[k]]
    said = lambda d: " ".join(f"{k} {v:.3g}" for k, v in d.items())

    # -- 1: every seed
    by_seed, first = {}, None
    for seed in seeds:
        params = jax.jit(lambda: T.init_params(jax.random.PRNGKey(seed), cfg))()
        tokens = jnp.asarray(np.random.default_rng(seed).integers(0, cfg.vocab_size, (1, args.seq)), jnp.int32)
        got = jax.block_until_ready(program(params, tokens))
        by_seed[seed] = distances(got, reference(tc))
        print(f"[check] seed {seed}: {said(by_seed[seed])} -> {'OUTSIDE ' + ', '.join(outside(by_seed[seed])) if outside(by_seed[seed]) else 'inside every limit'}", flush=True)
        if first is None:
            first = (params, tokens, got)
    worst = {k: max(d[k] for d in by_seed.values()) for k in ("loss", "hidden")}
    print(f"[check] worst over {len(seeds)} seeds: {said(worst)}", flush=True)

    # -- 2: the reference with one thing slipped, on the first seed
    params, tokens, got = first
    slips = {}
    for name in args.slips.split(","):
        with slipped(ref, name, tc) as sizes:
            slips[name] = distances(got, reference(sizes))
        verdict = "outside " + ", ".join(outside(slips[name])) if outside(slips[name]) else "INSIDE every limit"
        print(f"[check] {name}: {said(slips[name])} -> {verdict}{' (held on the CPU)' if name in HELD_ON_CPU else ''}", flush=True)

    bf16 = lambda x: jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)

    def rounded_state(S, xs):
        def position(S, x):
            c_t, b_t, v_t, g_t = x
            S = bf16(jnp.exp(g_t)[..., None, None] * S + b_t[:, None, :, None] * v_t[:, :, None, :])
            return S, jnp.einsum("bhnp,bn->bhp", S, c_t)

        return jax.lax.scan(position, S, xs)

    right, ref._positions = ref._positions, rounded_state
    try:
        state_bf16 = distances(got, reference(tc))
    finally:
        ref._positions = right
    print(f"[check] state_bf16 (said, not held): {said(state_bf16)}", flush=True)

    # -- 3: the chunked rule against the recurrence at the real head shapes
    positions = 64 if args.rehearse else 2048
    core = core_check(cfg.ssd_n_heads, cfg.ssd_head_dim, cfg.ssd_state_dim, positions, min(T._SSD_CHUNK, positions))
    core_worst = max(v for d in core.values() for v in d.values())
    print(f"[check] ssd_chunked against ssd_recurrent over {positions} positions: {core} -> {'inside' if core_worst <= LIMITS['core'] else 'OUTSIDE'} {LIMITS['core']}", flush=True)

    ok = not any(outside(d) for d in by_seed.values()) and core_worst <= LIMITS["core"]
    if not args.rehearse:  # at tiny sizes in float32 the limits, which are bfloat16's, hold no slip: tests/test_ssd_hybrid.py does
        ok = ok and set(slips) == set(SLIPS) and all(outside(d) for name, d in slips.items() if name not in HELD_ON_CPU)
    print(json.dumps({
        "ok": bool(ok), "limits": LIMITS, "by_seed": {str(k): v for k, v in by_seed.items()}, "worst": worst, "slips": slips,
        "state_bf16": state_bf16, "core": core, "seq": args.seq, "device": {"platform": dev.platform, "kind": dev.device_kind},
    }))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
