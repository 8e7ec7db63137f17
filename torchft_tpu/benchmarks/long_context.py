"""Long-context + scale bench variants, subprocess-isolated.

Round 5 found the same suite-interference that hit the resnet row
(resnet_ft.py post-mortem) depressing the in-process long-context rows:
s=8192 measured 9.07 steps/s when run after the headline's six
measurement runs inside bench.py's process vs 9.9-10.0 in a fresh
process. This module runs the s=4k/8k/16k/32k variants and the 647M
scale variant in their OWN process, first touch of the chip.

Run: ``python -m torchft_tpu.benchmarks.long_context`` — prints one
JSON line with a row per variant.
"""

import json
import sys


def run() -> dict:
    import jax

    from bench import (
        _model_flops_per_step,
        _peak_flops,
        headline_config,
        train_bench,
    )
    from torchft_tpu.models.transformer import PRESETS, TransformerConfig
    from torchft_tpu.utils.compile_cache import place_compile_cache

    place_compile_cache()

    # the long-context rows ARE the headline model at longer S — import
    # the config so the two can never silently diverge
    cfg = headline_config()
    peak = _peak_flops(jax.devices()[0])
    attn_note = (
        "tiered chunked-scan attention (pure XLA; see "
        "ops/attention.chunked_attention + transformer._use_chunked); "
        "OWN process (round-5 interference post-mortem in this module)"
    )
    out = {}
    n_params = 0
    # DESCENDING sequence length: the s=32k config is the HBM-ceiling one
    # and collapses 4x (0.88 -> 0.23 steps/s) when it runs after the
    # smaller variants' leftover allocations; largest-first measured
    # clean for every row (0.91/3.32/10.0/15.8 in one process)
    for s, b, steps, warmup in (
        (32768, 1, 3, 1), (16384, 1, 4, 2), (8192, 1, 6, 2), (4096, 2, 10, 2)
    ):
        try:
            sps, n_params = train_bench(cfg, b, s, steps, warmup, averaging=True)
            flops = _model_flops_per_step(cfg, n_params, b, s)
            out[f"long_context_s{s}"] = {
                "steps_per_sec": round(sps, 4),
                "tokens_per_sec": round(sps * b * s),
                "mfu_pct": round(sps * flops / peak * 100.0, 2),
                "attention": attn_note,
            }
        except Exception as e:  # noqa: BLE001
            out[f"long_context_s{s}"] = {"error": str(e)}

    # remat=False in the preset, measured round 5 (FT loop, fresh process,
    # noremat leg FIRST): 6.17 vs 5.80 steps/s — at 647M recompute costs
    # more than the activation spill, the OPPOSITE of the d512 headline
    big = TransformerConfig(**PRESETS["scale_647M"])
    try:
        big_sps, big_n = train_bench(big, 4, 1024, 8, 2, averaging=True)
        big_flops = _model_flops_per_step(big, big_n, 4, 1024)
        out["scale_647M"] = {
            "steps_per_sec": round(big_sps, 4),
            "tokens_per_sec": round(big_sps * 4 * 1024),
            "n_params": big_n,
            "mfu_pct": round(big_sps * big_flops / peak * 100.0, 2),
            "config": "d2048 L12 b4 s1024 bf16, remat=False (measured "
            "faster than remat at this size); OWN process",
        }
    except Exception as e:  # noqa: BLE001
        out["scale_647M"] = {"error": str(e)}
    return out


if __name__ == "__main__":
    import os

    sys.path.insert(
        0,
        os.path.dirname(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        ),
    )
    print(json.dumps(run()))
