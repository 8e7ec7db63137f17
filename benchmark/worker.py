#!/usr/bin/env python3
"""One replica group of a benchmark run — started by ``run.py`` through
``python -m torchft_tpu.launcher``, one process per group, chips handed out
by the launcher.

Builds what ``examples/train_hsdp.py`` builds (``make_mesh`` → ``TrainStep``
→ the loop named by the traffic file) from the cell's configuration file,
warms up, checks the seeded weights against the plain reference, reports
ready, and then runs units of the loop until the parent's window closes.
Everything it learns goes to ``<run_dir>/result.<group>.json``; a failure
goes to ``<run_dir>/error.<group>`` with its reason and a non-zero exit.

Group 0 leads: before unit k it writes ``go.<k>`` (``run``, ``trace`` or
``stop``) and the other groups wait for that file, so that every group
stops, and traces, at the same unit — a group that started one more quorum
than its peers would wait for them for ever.
"""

from __future__ import annotations

import argparse
import json
import logging
import os
import sys
import time
import traceback
import types

T_START = time.monotonic()  # CLOCK_MONOTONIC: one clock for every process here
HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
for p in (REPO, HERE):
    if p not in sys.path:
        sys.path.insert(0, p)

# |system loss - reference loss| per sequence, seeded weights, two sequences.
# The system computes in bfloat16 (8 mantissa bits: each matmul output is off
# by ~2^-9 relative), the reference in float32 at "highest" precision; the
# per-position errors are of random sign and a sequence's loss averages
# seq-1 of them. Measured on the v5e at the OLMo-1B widths, s2048, 38
# sequences over 30 seeds: 4e-6 to 5.5e-4, rms 2.4e-4 (PR 22). The tolerance is
# about four times the worst of those. With seeded random weights a sequence's
# loss is ln(vocab) + ~0.5 whatever the model computes, and what tells two models
# apart is the sequence's own deviation, ~0.02 (one over the root of 2047
# positions): a term left out (a residual, RoPE, a norm weight) moves the loss
# by that order, ten times the tolerance, and so does a narrower type than
# bfloat16 in the matmuls.
REFERENCE_ATOL = 2e-3


class CompileLog(logging.Handler):
    """Keeps JAX's compile chatter (JAX_LOG_COMPILES) with this clock."""

    def __init__(self) -> None:
        super().__init__(level=logging.DEBUG)
        self.records = []

    def emit(self, record: logging.LogRecord) -> None:
        msg = record.getMessage()
        if msg.startswith(("Compiling ", "Finished XLA compilation", "PERSISTENT COMPILATION CACHE MISS", "Persistent compilation cache hit")):
            self.records.append([time.monotonic(), msg[:160]])


class Spans:
    """Host spans around the calls into each layer, on the host clock and —
    through ``TraceAnnotation`` — in the profiler's trace."""

    def __init__(self) -> None:
        self.rows = []  # [name, t0, t1]

    def wrap(self, name, fn, block: bool = False):
        import jax

        def wrapped(*a, **kw):
            t0 = time.monotonic()
            with jax.profiler.TraceAnnotation(name):
                out = fn(*a, **kw)
                if block:
                    jax.block_until_ready(out)
            self.rows.append([name, t0, time.monotonic()])
            return out

        return wrapped


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--run-dir", required=True)
    args = ap.parse_args()
    run_dir = args.run_dir
    group = int(os.environ.get("REPLICA_GROUP_ID", 0))
    logging.basicConfig(
        level=logging.INFO,
        format=f"%(asctime)s g{group}/%(process)d %(name)s: %(message)s",
    )
    log = logging.getLogger("bench_worker")
    stamps = {"start": T_START}

    def fail(reason: str) -> int:
        with open(os.path.join(run_dir, f"error.{group}"), "w") as f:
            f.write(reason)
        log.error("FAILED: %s", reason)
        return 3

    try:
        return run(run_dir, group, log, stamps, fail)
    except Exception:  # noqa: BLE001 — the reason has to reach the parent
        return fail(traceback.format_exc()[-3000:])


def run(run_dir, group, log, stamps, fail) -> int:
    from common import load_json, load_module, write_atomic
    from host_probe import HostProbe

    cell = load_json(os.path.join(run_dir, "cell.json"))
    config, traffic = cell["config"], cell["traffic"]
    layout, program = config["layout"], config["program"]
    rehearsal = cell.get("rehearsal")
    log.info("start: group %d of %d, cell %s", group, layout["groups"], cell["workload"])

    from torchft_tpu.utils.compile_cache import place_compile_cache

    place_compile_cache()
    compile_log = CompileLog()
    logging.getLogger("jax").addHandler(compile_log)
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from torchft_tpu.models.transformer import TransformerConfig, init_params, loss_fn
    from torchft_tpu.parallel.mesh import MeshConfig, make_mesh
    from torchft_tpu.parallel.multihost import initialize_group
    from torchft_tpu.parallel.train_step import TrainStep

    stamps["imports"] = time.monotonic()
    probe = HostProbe()  # its buffers fill while the backend comes up
    host_probe = {}
    initialize_group()
    devices = jax.devices()
    stamps["devices"] = time.monotonic()
    dev = devices[0]
    log.info("devices: %d x %s (%s), jax %s", len(devices), dev.device_kind, dev.platform, jax.__version__)
    peaks = load_json(os.path.join(HERE, "peaks.json"))
    if rehearsal is None:
        if dev.platform != "tpu":
            return fail(f"no accelerator: JAX reports platform {dev.platform!r} ({dev.device_kind}); the benchmark measures on the chip only")
        if dev.device_kind not in peaks:
            return fail(f"device_kind {dev.device_kind!r} is not in benchmark/peaks.json; add its published peaks with their source")
    mesh_cfg = MeshConfig(**layout["mesh"])
    if len(devices) < mesh_cfg.total or mesh_cfg.total != layout["chips_per_group"]:
        return fail(f"group {group} needs {layout['chips_per_group']} chip(s) for mesh {layout['mesh']}, JAX sees {len(devices)}")
    mesh = make_mesh(mesh_cfg, devices=devices)

    tc = dict(program["transformer_config"])
    cfg = TransformerConfig(pp=mesh_cfg.pp, **{**tc, "dtype": jnp.dtype(tc["dtype"])})
    ctx = types.SimpleNamespace(
        group=group, groups=layout["groups"], seed=int(cell["seed"]),
        ts=TrainStep(cfg, optax.adamw(program["learning_rate"]), mesh),
        n_params=sum(
            int(np.prod(l.shape))
            for l in jax.tree_util.tree_leaves(
                jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
            )
        ),
    )
    loop_mod = load_module(os.path.join(HERE, "loops", traffic["loop"] + ".py"), "bench_loop")
    loop = loop_mod.Loop(ctx)
    jax.block_until_ready(loop.params())
    stamps["state"] = time.monotonic()
    log.info("state: %.1fM parameters placed, loop=%s", ctx.n_params / 1e6, traffic["loop"])

    batch, seq = int(traffic["batch"]), int(traffic["seq"])
    per_unit = int(traffic["steps_per_unit"])
    rng = np.random.default_rng(ctx.seed * 1000 + group)

    def next_batches():
        return [
            jnp.asarray(rng.integers(0, cfg.vocab_size, (batch, seq)), jnp.int32)
            for _ in range(per_unit)
        ]

    # -- correct: the seeded weights against the plain reference, on two
    # sequences of this group's first batch (outside the window)
    first = next_batches()
    ref_mod = load_module(os.path.join(HERE, "reference", program["reference"] + ".py"), "bench_reference")
    two = first[0][:2]
    sys_loss = jax.jit(lambda p, t: loss_fn(p, t, cfg, mesh))
    ref_loss = jax.jit(lambda p, t: ref_mod.per_sequence_loss(p, t, tc))
    with jax.set_mesh(mesh):
        sys_losses = [float(sys_loss(loop.params(), two[i : i + 1])) for i in range(2)]
    with jax.default_matmul_precision("highest"):
        ref_losses = [float(x) for x in ref_loss(loop.params(), two)]
    ref_err = max(abs(a - b) for a, b in zip(sys_losses, ref_losses))
    stamps["reference"] = time.monotonic()
    log.info("reference: system %s reference %s max|diff| %.3g (atol %.3g)", sys_losses, ref_losses, ref_err, REFERENCE_ATOL)
    if not (ref_err <= REFERENCE_ATOL):
        return fail(f"loss_fn disagrees with the plain reference on the seeded weights: system {sys_losses}, reference {ref_losses}, atol {REFERENCE_ATOL}")

    spans = Spans()
    traced = bool(cell["trace"])
    if traced:
        loop.instrument(spans)

    def go_path(k):
        return os.path.join(run_dir, f"go.{k}")

    window = None
    window_path = os.path.join(run_dir, "window.json")
    trace_dir = os.path.join(run_dir, f"trace.{group}")
    trace_units = int(traffic["trace_units"]) if traced else 0
    trace_state = {"first": None, "t0": None, "t1": None}
    units = []
    full_commits, ready_at = 0, None
    deadline = time.monotonic() + float(cell["setup_deadline_s"])
    k = 0
    batches = first
    while True:
        # -- what to do with unit k: the leader decides, the others follow
        if group == 0:
            if window is None and os.path.exists(window_path):
                window = load_json(window_path)
            now = time.monotonic()
            if window is not None and now >= window["close"]:
                verdict = "stop"
            elif (
                window is not None and now >= window["open"] and trace_units
                and (trace_state["first"] is None or k < trace_state["first"] + trace_units)
            ):
                verdict = "trace"
            else:
                verdict = "run"
            if window is None and now > deadline:
                return fail(f"set-up did not finish within {cell['setup_deadline_s']} s: {full_commits} commits with all {ctx.groups} groups so far")
            write_atomic(go_path(k), verdict)
        else:
            while not os.path.exists(go_path(k)):
                if time.monotonic() > deadline + float(cell["seconds"]) + 600:
                    return fail(f"group 0 never decided unit {k}")
                time.sleep(0.0005)
            with open(go_path(k)) as f:
                verdict = f.read().strip()
        if verdict == "stop":
            break
        tracing_this = verdict == "trace"
        if tracing_this and trace_state["first"] is None:
            trace_state["first"] = k
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            # op names and their scopes (xplane_meta.py) are in the trace without it
            opts.enable_hlo_proto = False
            trace_state["t0"] = time.monotonic()
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        elif not tracing_this and trace_state["first"] is not None and trace_state["t1"] is None:
            jax.profiler.stop_trace()
            trace_state["t1"] = time.monotonic()

        if batches is None:
            batches = next_batches()
        t0 = time.monotonic()
        with jax.profiler.TraceAnnotation("bench_unit"):
            results = loop.run_unit(batches)
        t1 = time.monotonic()
        batches = None
        units.append({"k": k, "t_start": t0, "t_end": t1, "traced": tracing_this, "steps": results})
        if k == 0:
            log.info("step-1 loss %.6f (seed %d, group %d)", results[0]["loss"], ctx.seed, group)
        if all(r["committed"] and r["participants"] == ctx.groups for r in results):
            full_commits += 1
        elif not all(r["committed"] for r in results):
            time.sleep(0.2)  # back off while the quorum is short, as train_hsdp
        if ready_at is None and full_commits >= int(traffic["warmup_full_commits"]):
            host_probe["open"] = probe.take()
            ready_at = time.monotonic()
            stamps["ready"] = ready_at
            write_atomic(os.path.join(run_dir, f"ready.{group}"), str(ready_at))
            log.info("ready: %d units with all %d groups committed", full_commits, ctx.groups)
        k += 1

    if trace_state["first"] is not None and trace_state["t1"] is None:
        jax.profiler.stop_trace()
        trace_state["t1"] = time.monotonic()
    stamps["loop_end"] = time.monotonic()
    host_probe["close"] = probe.take()
    window = load_json(window_path)

    # -- after the window: what the checks and the per-layer metrics read
    params = loop.params()
    checksum = sum(float(jnp.sum(l.astype(jnp.float32))) for l in jax.tree_util.tree_leaves(params))
    mem = [
        (d.memory_stats() or {}) for d in mesh.devices.flat
        if d.process_index == jax.process_index()
    ]
    extras = loop.close()
    result = {
        "group": group,
        "device": {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices), "used": mesh_cfg.total},
        "stamps": stamps,
        "host_probe": host_probe,
        "window": window,
        "units": units,
        "tokens_per_step": batch * seq,
        "steps_per_unit": per_unit,
        "reference": {"system": sys_losses, "reference": ref_losses, "max_abs_diff": ref_err, "atol": REFERENCE_ATOL},
        "param_checksum": repr(checksum),
        "final_step": units[-1]["steps"][-1]["step"] if units else 0,
        "memory_stats": [{k2: v for k2, v in m.items() if isinstance(v, (int, float))} for m in mem],
        "compile_log": compile_log.records,
        "spans": spans.rows,
        "trace_window": trace_state,
        "n_params": ctx.n_params,
        **extras,
    }
    if trace_state["first"] is not None:
        import reduce_trace

        path = reduce_trace.find_xplane(trace_dir)
        if path is None:
            return fail(f"the profiler left no .xplane.pb under {trace_dir}")
        result["trace"] = reduce_trace.reduce_file(
            path, loop_mod.SPAN_NAMES, cpu_rehearsal=rehearsal is not None and dev.platform == "cpu"
        )
        result["trace"]["xplane"] = path
    write_atomic(os.path.join(run_dir, f"result.{group}.json"), json.dumps(result))
    log.info("done: step=%d param_checksum=%r", result["final_step"], checksum)
    return 0


if __name__ == "__main__":
    sys.exit(main())
