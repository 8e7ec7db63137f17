"""Cross-process gradient-plane benchmark.

The round-2 review's top gap: nothing measured what the host data plane
(separate-process replica groups → D2H + TCP ring + H2D per step, the
topology of the BASELINE north-star 4×8-chip job) costs at 7B scale, and
the serial path left D2H, wire and H2D time additive.

This tool runs the REAL path: two replica groups as separate OS processes,
each with a full Manager (C++ lighthouse + quorum + commit) and a
``CollectivesTcp`` ring, averaging a synthetic gradient pytree through
``allreduce_gradients`` — once with the round-3 per-bucket pipeline, once
with the round-2 serial schedule (all transfers, then one wire op), with
and without bf16 wire compression. From the measured bytes/s it derives
the per-step averaging cost of the llama2-7b preset (the number the
review asked for), labeled as derived, not measured.

Usage::

    python -m torchft_tpu.benchmarks.crossgroup [--total-mb 256]

(Workers force ``JAX_PLATFORMS=cpu`` so the bench never competes with a
training job for the local chip; the wire path is identical either way —
only the D2H/H2D legs differ, and those are measured separately by the
headline bench on real HBM.)
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time
from datetime import timedelta
from typing import Dict, List, Optional

# llama2-7b preset (examples/train_hsdp.py PRESETS) parameter count:
# embeddings + 32 × (4·d² attn + 3·d·d_ff mlp + 2·d norms) + final norm
# + (tied) output head — matches models/transformer.py's layout.
_7B_D, _7B_FF, _7B_L, _7B_V = 4096, 11008, 32, 32000
LLAMA2_7B_PARAMS = (
    _7B_V * _7B_D
    + _7B_L * (4 * _7B_D * _7B_D + 3 * _7B_D * _7B_FF + 2 * _7B_D)
    + _7B_D
    + _7B_V * _7B_D
)


def _raw_worker_main(argv: List[str]) -> None:
    """Pure data-plane rate: two processes, one big f32 allreduce, no
    Manager/quorum/JAX in the loop — isolates what the transport itself
    moves (the number comparable to a NCCL busbw measurement)."""
    parser = argparse.ArgumentParser()
    parser.add_argument("--gid", type=int, required=True)
    parser.add_argument("--store", required=True)
    parser.add_argument("--total-mb", type=float, required=True)
    parser.add_argument("--rounds", type=int, required=True)
    parser.add_argument("--wire-dtype", default="")
    args = parser.parse_args(argv)

    import numpy as np

    from torchft_tpu.collectives import CollectivesTcp, ReduceOp

    c = CollectivesTcp(
        timeout=timedelta(seconds=120),
        hostname="localhost",
        wire_dtype=args.wire_dtype or None,
    )
    c.configure(args.store, args.gid, 2)
    n = int(args.total_mb * 1024 * 1024 / 4)
    arr = np.full(n, float(args.gid + 1), dtype=np.float32)
    c.allreduce([arr], ReduceOp.AVG).wait(timedelta(seconds=120))  # warmup
    t0 = time.perf_counter()
    for _ in range(args.rounds):
        c.allreduce([arr], ReduceOp.AVG).wait(timedelta(seconds=120))
    elapsed = (time.perf_counter() - t0) / args.rounds
    print(
        json.dumps(
            {
                "gid": args.gid,
                "seconds_per_round": elapsed,
                "total_bytes": n * 4,
                "plane": c.plane_info(),
            }
        ),
        flush=True,
    )
    c.shutdown()


def _heal_worker_main(argv: List[str]) -> None:
    """Checkpoint-heal throughput: rank 0 serves a 256MB-class state over
    CollectivesTransport, rank 1 receives (the live-heal data path). With
    the p2p CMA fast path the payload is pulled at memcpy-class speed."""
    parser = argparse.ArgumentParser()
    parser.add_argument("--gid", type=int, required=True)
    parser.add_argument("--store", required=True)
    parser.add_argument("--total-mb", type=float, required=True)
    args = parser.parse_args(argv)

    from datetime import timedelta

    import numpy as np

    from torchft_tpu.checkpointing.collectives_transport import (
        CollectivesTransport,
    )
    from torchft_tpu.collectives import CollectivesTcp

    n = int(args.total_mb * 1024 * 1024 / 4 / 8)
    state = {
        f"w{i}": np.random.default_rng(i).standard_normal(n).astype(np.float32)
        for i in range(8)
    }
    c = CollectivesTcp(timeout=timedelta(seconds=120), hostname="localhost")
    c.configure(args.store, args.gid, 2)
    t = CollectivesTransport(c, timeout=timedelta(seconds=120))
    if args.gid == 0:
        t.send_checkpoint([1], 0, state, timedelta(seconds=120))
        print(json.dumps({"gid": 0, "plane": c.plane_info()}), flush=True)
    else:
        t0 = time.perf_counter()
        got = t.recv_checkpoint(0, t.metadata(), 0, timedelta(seconds=120))
        dt = time.perf_counter() - t0
        ok = bool(
            np.array_equal(np.asarray(got["w0"]), state["w0"])
        )
        print(
            json.dumps(
                {
                    "gid": 1,
                    "seconds": dt,
                    "total_bytes": n * 8 * 4,
                    "ok": ok,
                    "plane": c.plane_info(),
                }
            ),
            flush=True,
        )
    c.shutdown()


def _heal_state(total_mb: float) -> Dict[str, object]:
    """Deterministic 8-leaf state tree of ``total_mb`` (shared by the
    striped-heal server processes and the in-parent verifier)."""
    import numpy as np

    n = int(total_mb * 1024 * 1024 / 4 / 8)
    return {
        f"w{i}": np.random.default_rng(i).standard_normal(n).astype(np.float32)
        for i in range(8)
    }


def _striped_heal_server_main(argv: List[str]) -> None:
    """One striped-heal source: stage the deterministic state on an
    HTTPTransport (native blob plane included) and serve until the
    parent closes stdin."""
    parser = argparse.ArgumentParser()
    parser.add_argument("--total-mb", type=float, required=True)
    args = parser.parse_args(argv)

    from datetime import timedelta

    from torchft_tpu.checkpointing.http_transport import HTTPTransport

    t = HTTPTransport(timeout=timedelta(seconds=300), hostname="localhost")
    t.send_checkpoint(
        [1], 0, _heal_state(args.total_mb), timedelta(seconds=300)
    )
    print(json.dumps({"metadata": t.metadata()}), flush=True)
    sys.stdin.readline()  # parent closes stdin when the client is done
    t.shutdown()


def _run_striped_heal(total_mb: float, nsources: int) -> Dict[str, object]:
    """The ``heal_striped_{n}src`` rows: N server processes stage the
    identical state; the healer (this process) pulls byte-balanced
    stripes from all of them in parallel over the native blob plane
    (docs/heal_plane.md). ``gb_per_sec`` is the aggregate; per-source
    throughput rides along so a slow stripe is attributable."""
    import numpy as np

    from datetime import timedelta

    from torchft_tpu.checkpointing.http_transport import HTTPTransport

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    procs = []
    urls: List[str] = []
    try:
        for _ in range(nsources):
            procs.append(
                subprocess.Popen(
                    [
                        sys.executable,
                        "-m",
                        "torchft_tpu.benchmarks.crossgroup",
                        "--striped-heal-server",
                        "--total-mb",
                        str(total_mb),
                    ],
                    stdin=subprocess.PIPE,
                    stdout=subprocess.PIPE,
                    stderr=subprocess.PIPE,
                    env=env,
                )
            )
        for p in procs:
            line = p.stdout.readline().decode().strip()
            if not line:
                raise RuntimeError(
                    f"striped-heal server died: {p.stderr.read().decode()[-2000:]}"
                )
            urls.append(json.loads(line)["metadata"])
        rx = HTTPTransport(timeout=timedelta(seconds=300), hostname="localhost")
        try:
            t0 = time.perf_counter()
            got = rx.recv_checkpoint_multi(
                urls, 0, timedelta(seconds=300)
            )
            dt = time.perf_counter() - t0
            stats = dict(rx.last_heal_stats)
        finally:
            rx.shutdown()
        expect = _heal_state(total_mb)
        assert bool(
            np.array_equal(np.asarray(got["w0"]), expect["w0"])
            and np.array_equal(np.asarray(got["w7"]), expect["w7"])
        ), "striped heal payload corrupted"
        total_bytes = sum(int(np.asarray(v).nbytes) for v in expect.values())
        return {
            "seconds": round(dt, 4),
            "gb_per_sec": round(total_bytes / dt / 1e9, 3),
            "nsources": stats.get("nsources", nsources),
            "per_source_gbps": {
                src: s.get("gb_per_sec")
                for src, s in (stats.get("sources") or {}).items()
            },
            "stages_s": stats.get("stages"),
        }
    finally:
        for p in procs:
            try:
                if p.stdin:
                    p.stdin.close()
            except OSError:
                pass
        for p in procs:
            if p.poll() is None:
                try:
                    p.wait(timeout=10)
                except subprocess.TimeoutExpired:
                    p.kill()


def _run_heal_pair(total_mb: float, env_extra: Dict[str, str]) -> Dict[str, object]:
    from torchft_tpu.store import StoreServer

    store = StoreServer()
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    env.update(env_extra)
    procs = []
    try:
        for gid in range(2):
            procs.append(
                subprocess.Popen(
                    [
                        sys.executable,
                        "-m",
                        "torchft_tpu.benchmarks.crossgroup",
                        "--heal-worker",
                        "--gid",
                        str(gid),
                        "--store",
                        store.address(),
                        "--total-mb",
                        str(total_mb),
                    ],
                    stdout=subprocess.PIPE,
                    stderr=subprocess.PIPE,
                    env=env,
                )
            )
        results = []
        for p in procs:
            out, err = p.communicate(timeout=500)
            if p.returncode != 0:
                raise RuntimeError(
                    f"heal worker rc={p.returncode}: {err.decode()[-2000:]}"
                )
            results.append(json.loads(out.decode().strip().splitlines()[-1]))
    finally:
        store.shutdown()
    r = next(r for r in results if r["gid"] == 1)
    assert r["ok"], "heal payload corrupted"
    return {
        "seconds": round(r["seconds"], 4),
        "gb_per_sec": round(r["total_bytes"] / r["seconds"] / 1e9, 3),
        "plane": r["plane"],
    }


def _worker_main(argv: List[str]) -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--gid", type=int, required=True)
    parser.add_argument("--lighthouse", required=True)
    parser.add_argument("--total-mb", type=float, required=True)
    parser.add_argument("--rounds", type=int, required=True)
    parser.add_argument("--wire-dtype", default="")
    parser.add_argument("--serial", action="store_true")
    args = parser.parse_args(argv)

    import numpy as np

    from torchft_tpu.collectives import CollectivesTcp
    from torchft_tpu.ddp import allreduce_gradients, flatten_buckets
    from torchft_tpu.manager import Manager
    from torchft_tpu.store import StoreServer

    import jax
    import jax.numpy as jnp

    # the worker must NEVER occupy the chip (the docstring guarantee): a
    # chip belongs to one process, and this row measures the host plane
    jax.config.update("jax_platforms", "cpu")

    store = StoreServer()
    coll = CollectivesTcp(
        timeout=timedelta(seconds=120),
        hostname="localhost",
        wire_dtype=args.wire_dtype or None,
    )
    manager = Manager(
        collectives=coll,
        load_state_dict=lambda s: None,
        state_dict=lambda: {},
        min_replica_size=2,
        replica_id=f"xg{args.gid}",
        store_addr=store.address(),
        rank=0,
        world_size=1,
        lighthouse_addr=args.lighthouse,
        timeout=timedelta(seconds=120),
        quorum_timeout=timedelta(seconds=120),
        use_async_quorum=False,
    )
    try:
        # ~4 MB leaves → ~25 MB buckets hold ~6 each; jnp so the full
        # leaf→host→ring→device path runs
        leaf_elems = 1 << 20
        n_leaves = max(1, int(args.total_mb * 1024 * 1024 / 4 / leaf_elems))
        rng = np.random.default_rng(args.gid)
        grads = {
            f"g{i}": jnp.asarray(
                rng.standard_normal(leaf_elems).astype(np.float32)
            )
            for i in range(n_leaves)
        }
        total_bytes = n_leaves * leaf_elems * 4

        def serial_round() -> None:
            # the round-2 schedule: every leaf to host first, then ONE
            # managed op over all buckets, then back
            host = [np.ascontiguousarray(np.asarray(v)) for v in grads.values()]
            buckets = flatten_buckets(host)
            manager.allreduce_many([b for b, _ in buckets]).wait()
            for b, _ in buckets:
                jnp.asarray(b)

        def pipelined_round() -> None:
            allreduce_gradients(manager, grads)

        run = serial_round if args.serial else pipelined_round

        # warmup (also forms the quorum)
        manager.start_quorum()
        run()
        assert manager.should_commit(), "warmup step failed to commit"

        # per-stage attribution (host-copy / quantize / wire /
        # dequantize-reduce, docs/wire_plane.md): reset AFTER warmup so
        # the breakdown covers exactly the timed rounds — this is what
        # explains a wire-row delta instead of leaving it a mystery
        from torchft_tpu.collectives import wire_stage_snapshot

        wire_stage_snapshot(reset=True)
        t0 = time.perf_counter()
        for _ in range(args.rounds):
            manager.start_quorum()
            run()
            assert manager.should_commit(), "bench step failed to commit"
        elapsed = (time.perf_counter() - t0) / args.rounds
        stages = {
            k: round(v / args.rounds, 4)
            for k, v in wire_stage_snapshot().items()
        }

        print(
            json.dumps(
                {
                    "gid": args.gid,
                    "seconds_per_round": elapsed,
                    "total_bytes": total_bytes,
                    "plane": coll.plane_info(),
                    "wire_codec": coll.wire_codec(),
                    "stages_per_round_s": stages,
                }
            ),
            flush=True,
        )
    finally:
        manager.shutdown(wait=False)
        store.shutdown()


def _run_pair(
    lighthouse_addr: str,
    total_mb: float,
    rounds: int,
    wire_dtype: str,
    serial: bool,
    env_extra: Optional[Dict[str, str]] = None,
) -> Dict[str, object]:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    if env_extra:
        env.update(env_extra)
    procs = []
    for gid in range(2):
        cmd = [
            sys.executable,
            "-m",
            "torchft_tpu.benchmarks.crossgroup",
            "--worker",
            "--gid",
            str(gid),
            "--lighthouse",
            lighthouse_addr,
            "--total-mb",
            str(total_mb),
            "--rounds",
            str(rounds),
            "--wire-dtype",
            wire_dtype,
        ]
        if serial:
            cmd.append("--serial")
        procs.append(
            subprocess.Popen(
                cmd, stdout=subprocess.PIPE, stderr=subprocess.PIPE, env=env
            )
        )
    results = []
    for p in procs:
        out, err = p.communicate(timeout=600)
        if p.returncode != 0:
            raise RuntimeError(
                f"crossgroup worker failed rc={p.returncode}: "
                f"{err.decode()[-2000:]}"
            )
        results.append(json.loads(out.decode().strip().splitlines()[-1]))
    slow = max(results, key=lambda r: r["seconds_per_round"])
    secs = slow["seconds_per_round"]
    total_bytes = results[0]["total_bytes"]
    return {
        "seconds_per_round": secs,
        "gb_per_sec": total_bytes / secs / 1e9,
        "total_bytes": total_bytes,
        "plane": slow.get("plane", "?"),
        "wire_codec": slow.get("wire_codec", "f32"),
        # the slower worker's breakdown: that is the rank the row's
        # seconds_per_round actually measures
        "stages_per_round_s": slow.get("stages_per_round_s", {}),
    }


def _run_raw_pair(
    total_mb: float, rounds: int, wire_dtype: str, env_extra: Dict[str, str]
) -> Dict[str, object]:
    from torchft_tpu.store import StoreServer

    store = StoreServer()
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    env.update(env_extra)
    procs = []
    try:
        for gid in range(2):
            cmd = [
                sys.executable,
                "-m",
                "torchft_tpu.benchmarks.crossgroup",
                "--raw-worker",
                "--gid",
                str(gid),
                "--store",
                store.address(),
                "--total-mb",
                str(total_mb),
                "--rounds",
                str(rounds),
                "--wire-dtype",
                wire_dtype,
            ]
            procs.append(
                subprocess.Popen(
                    cmd, stdout=subprocess.PIPE, stderr=subprocess.PIPE, env=env
                )
            )
        results = []
        for p in procs:
            out, err = p.communicate(timeout=600)
            if p.returncode != 0:
                raise RuntimeError(
                    f"raw worker failed rc={p.returncode}: {err.decode()[-2000:]}"
                )
            results.append(json.loads(out.decode().strip().splitlines()[-1]))
    finally:
        store.shutdown()
    secs = max(r["seconds_per_round"] for r in results)
    return {
        "seconds_per_round": round(secs, 4),
        "gb_per_sec": round(results[0]["total_bytes"] / secs / 1e9, 3),
        "total_bytes": results[0]["total_bytes"],
        "plane": results[0]["plane"],
    }


def measure_crossgroup(
    total_mb: float = 256.0, rounds: int = 3
) -> Dict[str, object]:
    """Run the 2-process averaging matrix; returns the bench dict."""
    from torchft_tpu.coordination import LighthouseServer

    out: Dict[str, object] = {
        "topology": "2 replica groups, separate OS processes, native "
        "striped data plane (CMA same-host / multi-socket TCP), e2e "
        "variants through full Manager quorum+commit",
        "tree_mb": total_mb,
    }
    grad_bytes_7b = LLAMA2_7B_PARAMS * 4  # f32 gradient tree

    # RAW transport matrix: what the plane itself moves (busbw analogue).
    # CMA = one-copy process_vm_readv pulls (same-host; NCCL SHM/P2P
    # analogue); tcp-striped = the cross-host path, forced here via env;
    # python-ring = the pre-round-4 interpreter path, kept for comparison.
    raw_variants = {
        "raw_cma": dict(wire_dtype="", env_extra={}),
        "raw_tcp_striped": dict(
            wire_dtype="", env_extra={"TORCHFT_DP_CMA": "0"}
        ),
        "raw_tcp_striped_bf16": dict(
            wire_dtype="bfloat16", env_extra={"TORCHFT_DP_CMA": "0"}
        ),
        "raw_python_ring": dict(
            wire_dtype="", env_extra={"TORCHFT_NATIVE_PLANE": "0"}
        ),
    }
    for name, kw in raw_variants.items():
        try:
            res = _run_raw_pair(total_mb, rounds, **kw)  # type: ignore[arg-type]
        except Exception as e:  # noqa: BLE001 — best-effort matrix row
            out[name] = {"error": str(e)}
            continue
        res["derived_llama2_7b_avg_s"] = round(
            grad_bytes_7b * res["seconds_per_round"] / res["total_bytes"], 2
        )
        del res["total_bytes"]
        out[name] = res

    # live-heal throughput (the rejoin data path) with and without the
    # p2p CMA fast path
    for name, env_extra in (
        ("heal_cma", {}),
        ("heal_tcp", {"TORCHFT_DP_CMA": "0"}),
    ):
        try:
            out[name] = _run_heal_pair(total_mb, env_extra)
        except Exception as e:  # noqa: BLE001 — best-effort matrix row
            out[name] = {"error": str(e)}

    # striped multi-source heal (ISSUE 9): same bytes pulled from 1 vs 2
    # sources over the native blob plane; the speedup row is the
    # per-source parallel scaling the sub-second-heal acceptance reads
    for name, nsrc in (("heal_striped_1src", 1), ("heal_striped_2src", 2)):
        try:
            out[name] = _run_striped_heal(total_mb, nsrc)
        except Exception as e:  # noqa: BLE001 — best-effort matrix row
            out[name] = {"error": str(e)}
    try:
        s1 = out["heal_striped_1src"]["gb_per_sec"]  # type: ignore[index]
        s2 = out["heal_striped_2src"]["gb_per_sec"]  # type: ignore[index]
        out["heal_striped_speedup"] = round(s2 / s1, 3) if s1 else None
    except (KeyError, TypeError):
        out["heal_striped_speedup"] = None

    variants = {
        "serial_r2": dict(wire_dtype="", serial=True),
        "pipelined": dict(wire_dtype="", serial=False),
        "pipelined_bf16_wire": dict(wire_dtype="bfloat16", serial=False),
    }
    for name, kw in variants.items():
        lighthouse = LighthouseServer(bind="[::]:0", min_replicas=2)
        try:
            res = _run_pair(
                lighthouse.address(), total_mb, rounds, **kw
            )
        finally:
            lighthouse.shutdown()
        res["derived_llama2_7b_avg_s"] = round(
            grad_bytes_7b * res["seconds_per_round"] / res["total_bytes"], 2
        )
        res["seconds_per_round"] = round(res["seconds_per_round"], 4)
        res["gb_per_sec"] = round(res["gb_per_sec"], 3)
        del res["total_bytes"]
        out[name] = res

    ser = out["serial_r2"]["seconds_per_round"]  # type: ignore[index]
    pipe = out["pipelined"]["seconds_per_round"]  # type: ignore[index]
    out["pipeline_speedup"] = round(ser / pipe, 3) if pipe else None
    out["note"] = (
        "raw_* rows isolate the transport (one allreduce, no Manager); "
        "e2e rows include full per-round quorum+commit and JAX<->host "
        "copies. derived_llama2_7b_avg_s extrapolates measured bytes/s to "
        "the 7B preset's f32 gradient tree; workers run on CPU so the "
        "wire path is measured without occupying the chip"
    )
    return out


def measure_compressed(
    total_mb: float = 128.0, rounds: int = 2
) -> Dict[str, object]:
    """The ``crossgroup_compressed`` bench row: the int8-quantized wire
    (4x fewer bytes per hop, per-chunk scale factors, error feedback
    handled one level up) over the forced tcp-striped native plane —
    ``TORCHFT_DP_CMA=0`` models the cross-host link, where CMA does not
    exist and compression is the whole point. ``serial`` is the
    round-2 schedule; ``streamed`` is the per-bucket pipeline that
    overlaps host-copy / wire / H2D per bucket. ``gb_per_sec`` counts
    APPLICATION bytes (the f32 gradient tree), so the row composes with
    derived_llama2_7b_avg_s and the uncompressed rows directly."""
    from torchft_tpu.coordination import LighthouseServer

    out: Dict[str, object] = {
        "topology": "2 replica groups, separate OS processes, int8 wire "
        "codec on the forced tcp-striped native plane (TORCHFT_DP_CMA=0 "
        "— the cross-host model); gb_per_sec counts f32 tree bytes",
        "tree_mb": total_mb,
        "codec": "int8",
    }
    grad_bytes_7b = LLAMA2_7B_PARAMS * 4
    for name, serial in (("serial", True), ("streamed", False)):
        lighthouse = LighthouseServer(bind="[::]:0", min_replicas=2)
        try:
            res = _run_pair(
                lighthouse.address(), total_mb, rounds,
                wire_dtype="int8", serial=serial,
                env_extra={"TORCHFT_DP_CMA": "0"},
            )
        except Exception as e:  # noqa: BLE001 — best-effort matrix row
            out[name] = {"error": str(e)}
            continue
        finally:
            lighthouse.shutdown()
        res["derived_llama2_7b_avg_s"] = round(
            grad_bytes_7b * res["seconds_per_round"] / res["total_bytes"], 2
        )
        res["seconds_per_round"] = round(res["seconds_per_round"], 4)
        res["gb_per_sec"] = round(res["gb_per_sec"], 3)
        del res["total_bytes"]
        out[name] = res
    return out


def main() -> None:
    if "--striped-heal-server" in sys.argv:
        argv = [a for a in sys.argv[1:] if a != "--striped-heal-server"]
        _striped_heal_server_main(argv)
        return
    if "--heal-worker" in sys.argv:
        argv = [a for a in sys.argv[1:] if a != "--heal-worker"]
        _heal_worker_main(argv)
        return
    if "--raw-worker" in sys.argv:
        argv = [a for a in sys.argv[1:] if a != "--raw-worker"]
        _raw_worker_main(argv)
        return
    if "--worker" in sys.argv:
        argv = [a for a in sys.argv[1:] if a != "--worker"]
        _worker_main(argv)
        return
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--total-mb", type=float, default=256.0)
    parser.add_argument("--rounds", type=int, default=3)
    parser.add_argument(
        "--compressed", action="store_true",
        help="run only the crossgroup_compressed matrix (int8 wire, "
        "serial + streamed)",
    )
    args = parser.parse_args()
    # ONE line: callers (bench.py) parse the last stdout line as JSON
    fn = measure_compressed if args.compressed else measure_crossgroup
    print(json.dumps(fn(args.total_mb, args.rounds)), flush=True)


if __name__ == "__main__":
    main()
