"""Peak device memory: ``memory_stats()["peak_bytes_in_use"]`` after the
window, the fullest chip, in GB (1e9 bytes). On this runtime (libtpu 0.0.34)
the counter holds the buffers JAX keeps — state, gradients, batches — and
not the temporaries of a program while it runs: the fused cell reads 7.4 GB
where ``compile_check.py`` counts 13.1 GB while ``fused`` runs."""

NAME, UNIT, SOURCE = "hbm_peak_gb", "GB", "program_counter"
LAYER = "device"
MOVES = "step_p50_s"


def compute(run):
    peaks = [
        m["peak_bytes_in_use"] for r in run.results for m in r["memory_stats"]
        if "peak_bytes_in_use" in m
    ]
    return max(peaks) / 1e9 if peaks else None
