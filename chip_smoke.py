#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that torchft_tpu still starts on the chip.

Drives the main path once, through the entry points a user calls —
``python -m torchft_tpu.launcher`` (which owns the lighthouse) →
``examples/train_hsdp.py`` → FTTrainer → TrainStep → Manager →
CollectivesTcp → checkpoint transport — at the full width of the
``scale_647M`` preset, and checks what comes out by the repo's own means.

A TPU chip belongs to one process at a time. This parent therefore never
initialises a JAX backend: every phase runs in child processes started one
after another, each under a timeout, and any failure is fatal (non-zero
exit, the child's log tail printed, no result line). After the probe it
rebuilds the native core from native/*.cc, so the binary under test comes
from the files git would commit, and places the compile cache
(utils/compile_cache.py) for its children.

Phases:
  1 probe    a short-lived child names the platform; anything but "tpu" fails
  2 steady   launcher --groups 1, scale_647M b4 s1024, a few committed steps
  3 kernels  Pallas flash + chunked attention, compiled, against ops.attention;
             at the hybrid cell's widths the latent attention's core (keys 192,
             values 128) and chunked KDA against the recurrence
  4 cache    phase 2 again on the same compile cache: zero grads/apply misses
  5 four chips, only when the probe counted >= 4:
    5a one group on all four chips (FSDP=2 TP=2): shards + memory everywhere
    5b two groups x two chips, SIGKILL one trainer, respawn, heal, same checksum

Takes no flags and reads no variable that would weaken it. The timings it
prints are observations for the log, not metrics. Logs: chip_smoke_out/.
The last stdout line is ``{"ok": true, "device": {...}}``.
"""

from __future__ import annotations

import json
import math
import os
import re
import signal
import subprocess
import sys
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

REPO = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(REPO, "chip_smoke_out")

MODEL = "scale_647M"  # models.transformer.PRESETS; widths are not negotiable
BATCH, SEQ = 4, 1024  # `auto` routes s>=1024 to chunked_attention (head_dim 64: the kernel from s2048 on)


class PhaseFailed(Exception):
    pass


def say(msg: str) -> None:
    print(f"[smoke {time.strftime('%H:%M:%S')}] {msg}", flush=True)


def _tail(text: str, n: int = 60) -> str:
    """The end of a child's log, without JAX's compile chatter (which
    JAX_LOG_COMPILES turns on and which would fill the tail)."""
    return "\n".join(
        [l[:400] for l in text.splitlines() if "jax._src" not in l][-n:]
    )


def _child_env(extra: Optional[Dict[str, str]] = None) -> Dict[str, str]:
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["PYTHONUNBUFFERED"] = "1"
    env.update(extra or {})
    return env


def _kill_group(proc: subprocess.Popen) -> None:
    """Stop a child and everything it started (it leads its own session)."""
    try:
        os.killpg(proc.pid, signal.SIGKILL)
    except OSError:
        pass
    proc.wait()


def run_child(
    tag: str, cmd: List[str], env: Dict[str, str], timeout: float
) -> str:
    """Run one child to completion; returns its combined output. Raises
    PhaseFailed (with the log tail) on a non-zero exit or the timeout."""
    os.makedirs(OUT, exist_ok=True)
    log_path = os.path.join(OUT, f"{tag}.log")
    with open(log_path, "w") as log:
        proc = subprocess.Popen(
            cmd, env=env, cwd=REPO, stdout=log, stderr=subprocess.STDOUT,
            start_new_session=True,
        )
        try:
            rc: Optional[int] = proc.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            rc = None
        finally:
            _kill_group(proc)
    with open(log_path) as f:
        text = f.read()
    if rc != 0:
        why = f"timed out after {timeout:.0f}s" if rc is None else f"rc={rc}"
        raise PhaseFailed(f"{tag}: {why}\n--- {log_path} (tail)\n{_tail(text)}")
    return text


# ---------------------------------------------------------------------------
# set-up: the binary under test comes from the files git would commit
# ---------------------------------------------------------------------------


def build_native() -> None:
    t0 = time.monotonic()
    proc = subprocess.run(
        ["make", "-s", "-B", f"-j{os.cpu_count() or 1}", "-C", os.path.join(REPO, "native")],
        capture_output=True, text=True,
    )
    if proc.returncode != 0:
        raise PhaseFailed(
            f"native core build failed (rc={proc.returncode}):\n"
            f"{proc.stdout}{proc.stderr}"
        )
    say(f"native core rebuilt from native/*.cc in {time.monotonic() - t0:.1f}s")


# ---------------------------------------------------------------------------
# phase 1: probe
# ---------------------------------------------------------------------------

_PROBE = """
import json, jax
d = jax.devices()
print(json.dumps({"jax": jax.__version__, "platform": d[0].platform,
                  "kind": d[0].device_kind, "count": len(d)}))
"""


def probe(platform: str) -> Dict[str, Any]:
    """What JAX finds, from a child that exits and so releases the chip."""
    try:
        text = run_child(
            "1_probe", [sys.executable, "-c", _PROBE], _child_env(), 180
        )
    except PhaseFailed as e:
        raise PhaseFailed(f"no {platform} found: JAX could not start\n{e}")
    info = json.loads(text.strip().splitlines()[-1])
    if info["platform"] != platform:
        raise PhaseFailed(
            f"no {platform} found: JAX reports platform "
            f"{info['platform']!r} ({info['count']} x {info['kind']}; "
            f"JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r})"
        )
    try:
        import libtpu

        info["libtpu"] = libtpu.__version__
    except (ImportError, AttributeError):
        info["libtpu"] = None
    say(f"probe: {info}")
    return info


# ---------------------------------------------------------------------------
# launcher runs: phases 2, 4, 5a, 5b share one driver and one log parser
# ---------------------------------------------------------------------------

_LINE = re.compile(r" g(\d+)/(\d+) (\S+): (.*)$")
_STEP = re.compile(r"step=(\d+) committed=(True|False) participants=(\d+) loss=(\S+)")
_CACHE = re.compile(
    r"(Persistent compilation cache hit|PERSISTENT COMPILATION CACHE MISS) for '([^']+)'"
)
_SPAWN = re.compile(r"group (\d+) rank (\d+): pid (\d+), TPU chips (.*)$")


class Trainer:
    """One trainer process (one incarnation of one group), from its log."""

    def __init__(self, gid: int, pid: int) -> None:
        self.gid, self.pid = gid, pid
        self.t_spawn: Optional[float] = None  # launcher's spawn line
        self.chips = ""
        self.t_first: Optional[float] = None  # first log line: imports done
        self.t_devices: Optional[float] = None  # backend up
        self.t_model: Optional[float] = None  # params + opt state placed
        self.t_heal: Optional[float] = None
        self.t_healed: Optional[float] = None
        self.devices = ""
        self.placement = ""
        self.steps: List[Tuple[float, int, bool, float]] = []
        self.done: Optional[str] = None  # the done: line
        self.cache: Dict[str, List[int]] = {}  # jit name -> [hits, misses]

    def committed(self) -> List[Tuple[float, int, bool, float]]:
        return [s for s in self.steps if s[2]]

    def aborted(self) -> int:
        return sum(1 for s in self.steps if not s[2])

    def checksum(self) -> Optional[str]:
        m = re.search(r"param_checksum=(\S+)", self.done or "")
        return m.group(1) if m else None

    def t0(self) -> float:
        """When the launcher spawned it (else its first line)."""
        return self.t_spawn if self.t_spawn is not None else (self.t_first or 0.0)

    def exec_to_first_commit(self) -> float:
        return self.committed()[0][0] - self.t0()


def parse_run(lines: List[Tuple[float, str]]) -> Dict[int, Trainer]:
    """(arrival time, line) pairs of one launcher run → trainers by pid."""
    trainers: Dict[int, Trainer] = {}
    spawns: Dict[int, Tuple[float, str]] = {}
    for t, line in lines:
        m = _SPAWN.search(line)
        if m:
            spawns[int(m.group(3))] = (t, m.group(4))
            continue
        m = _LINE.search(line)
        if not m:
            continue
        gid, pid, name, msg = int(m.group(1)), int(m.group(2)), m.group(3), m.group(4)
        tr = trainers.get(pid)
        if tr is None:
            tr = trainers[pid] = Trainer(gid, pid)
            tr.t_first = t
        c = _CACHE.search(msg)
        if c:
            tr.cache.setdefault(c.group(2), [0, 0])[0 if "hit" in c.group(1) else 1] += 1
        elif name == "train_hsdp":
            s = _STEP.search(msg)
            if s:
                tr.steps.append(
                    (t, int(s.group(1)), s.group(2) == "True", float(s.group(4)))
                )
            elif msg.startswith("devices:"):
                tr.t_devices, tr.devices = t, msg
            elif msg.startswith("placement:"):
                tr.placement = msg
            elif msg.startswith("model="):
                tr.t_model = t
            elif msg.startswith("done:"):
                tr.done = msg
        elif "healing: fetching checkpoint metadata" in msg:
            tr.t_heal = t
        elif "applying pending state dict" in msg:
            tr.t_healed = t
    for pid, (t, chips) in spawns.items():
        if pid in trainers:
            trainers[pid].t_spawn, trainers[pid].chips = t, chips
    return trainers


def launcher_run(
    tag: str,
    groups: int,
    env: Dict[str, str],
    timeout: float,
    max_aborted: int,
    on_line: Optional[Callable[[str], None]] = None,
) -> Tuple[Dict[int, Trainer], str]:
    """``python -m torchft_tpu.launcher --groups N -- python
    examples/train_hsdp.py`` to completion. Every output line is stamped
    on arrival (this process's clock) and passed to ``on_line``.

    train_hsdp loops until ``current_step()`` reaches STEPS, so a path on
    which no step can commit never ends: the run is stopped as soon as
    more than ``max_aborted`` ``committed=False`` lines have been seen,
    not at the timeout."""
    os.makedirs(OUT, exist_ok=True)
    log_path = os.path.join(OUT, f"{tag}.log")
    cmd = [
        sys.executable, "-m", "torchft_tpu.launcher", "--groups", str(groups),
        "--", sys.executable, os.path.join(REPO, "examples", "train_hsdp.py"),
    ]
    # hits and misses of the persistent compile cache, by jit name, at
    # WARNING level in the trainers' logs
    env = _child_env({"JAX_LOG_COMPILES": "1", "JAX_EXPLAIN_CACHE_MISSES": "1", **env})
    lines: List[Tuple[float, str]] = []
    aborted = [0]
    proc = subprocess.Popen(
        cmd, env=env, cwd=REPO, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True, start_new_session=True,
    )

    def pump() -> None:
        assert proc.stdout is not None
        with open(log_path, "w") as log:
            for line in proc.stdout:
                lines.append((time.monotonic(), line.rstrip("\n")))
                log.write(line)
                log.flush()
                if on_line is not None:
                    on_line(line)
                if " committed=False " in line:
                    aborted[0] += 1
                    if aborted[0] == max_aborted + 1:
                        os.killpg(proc.pid, signal.SIGKILL)  # ends wait()

    reader = threading.Thread(target=pump, name="smoke_pump", daemon=True)
    reader.start()
    try:
        rc: Optional[int] = proc.wait(timeout=timeout)
    except subprocess.TimeoutExpired:
        rc = None
    finally:
        _kill_group(proc)
        reader.join(timeout=10)
    text = "\n".join(l for _, l in lines)
    if rc != 0:
        why = (
            f"stopped: {aborted[0]} aborted steps (committed=False), more "
            f"than the {max_aborted} allowed" if aborted[0] > max_aborted
            else f"timed out after {timeout:.0f}s" if rc is None
            else f"rc={rc}"
        )
        raise PhaseFailed(f"{tag}: launcher {why}\n--- {log_path} (tail)\n{_tail(text)}")
    return parse_run(lines), text


def _check_finished(
    tag: str, tr: Trainer, platform: str, n_devices: int, steps: int
) -> None:
    if platform not in tr.devices or not tr.devices.startswith(f"devices: {n_devices} x"):
        raise PhaseFailed(
            f"{tag}: g{tr.gid} expected {n_devices} {platform} device(s), "
            f"its log says {tr.devices!r}"
        )
    if tr.done is None or f"done: step={steps} " not in tr.done:
        raise PhaseFailed(f"{tag}: g{tr.gid}/{tr.pid} has no 'done: step={steps}' line")
    bad = [s for s in tr.steps if not math.isfinite(s[3])]
    if bad:
        raise PhaseFailed(f"{tag}: g{tr.gid} non-finite loss at {bad[0]}")


def _obs_start(tag: str, tr: Trainer, device: str) -> None:
    """Print the observed start-up split of one trainer (seconds)."""
    first_commit = tr.committed()[0][0]
    t0 = tr.t0()
    obs = {
        "exec_import_s": (tr.t_first or t0) - t0,
        "backend_s": (tr.t_devices or t0) - (tr.t_first or t0),
        "init_state_s": (tr.t_model or t0) - (tr.t_devices or t0),
        "first_commit_s": first_commit - (tr.t_model or t0),
        "exec_to_first_commit_s": tr.exec_to_first_commit(),
    }
    commits = tr.committed()
    if len(commits) > 2:
        dts = sorted(b[0] - a[0] for a, b in zip(commits[1:], commits[2:]))
        obs["step_s_median_after_first"] = dts[len(dts) // 2]
    say(
        f"{tag}: observation ({device}) g{tr.gid}/{tr.pid}: "
        + " ".join(f"{k}={v:.2f}" for k, v in obs.items())
    )


def _cache_counts(tr: Trainer) -> Dict[str, List[int]]:
    """Persistent-cache [hits, misses] of the two step programs."""
    out = {"grads": [0, 0], "apply": [0, 0], "other": [0, 0]}
    for name, (h, m) in tr.cache.items():
        key = (
            "grads" if "tft_grads" in name
            else "apply" if "tft_apply" in name
            else "other"
        )
        out[key][0] += h
        out[key][1] += m
    return out


def steady(
    tag: str,
    platform: str,
    n_devices: int,
    device: str,
    model: str = MODEL,
    batch: int = BATCH,
    seq: int = SEQ,
    steps: int = 4,
    mesh: Optional[Dict[str, int]] = None,
    timeout: float = 600,
) -> Trainer:
    """One group through launcher + train_hsdp. Pass = every step
    committed, losses finite, the ``done:`` line present."""
    mesh = mesh or {"FSDP": 1, "TP": 1}
    env = {
        "MODEL": model, "BATCH": str(batch), "SEQ": str(seq),
        "STEPS": str(steps), **{k: str(v) for k, v in mesh.items()},
    }
    trainers, _ = launcher_run(tag, 1, env, timeout, max_aborted=0)
    if len(trainers) != 1:
        raise PhaseFailed(f"{tag}: expected one trainer, saw pids {list(trainers)}")
    (tr,) = trainers.values()
    _check_finished(tag, tr, platform, n_devices, steps)
    if len(tr.committed()) != steps:
        raise PhaseFailed(
            f"{tag}: {len(tr.committed())} committed steps, want {steps}: {tr.steps}"
        )
    say(
        f"{tag}: pass — {steps}/{steps} steps committed, losses "
        f"{[round(s[3], 4) for s in tr.steps]}, {tr.done}"
    )
    _obs_start(tag, tr, device)
    return tr


# ---------------------------------------------------------------------------
# phase 3: kernels (one child, on the chip)
# ---------------------------------------------------------------------------


def kernels_child(
    platform: str, flash_shapes: list, chunked_shape: list, cells_shape: list,
    mla_shape: Optional[list] = None, kda_shape: Optional[list] = None, gdn_shape: Optional[list] = None,
    ssd_shape: Optional[list] = None,
) -> None:
    """Runs in the child. Flash forward+backward per (b, s, h, d) at its
    default blocks — a shape that goes on to (…, value width, key/value heads)
    is a cell's grouped one, at the blocks ``attention_impl`` "auto" picks for
    it — and, at the benchmark cells' sequence and heads, at the
    blocks "auto" picks there; chunked attention at the
    smoke's shape; all in bf16 against ops.attention in f32 (matmuls at
    highest precision). ``mla_shape`` (b, s, h, key width, value width):
    the latent attention's core as "auto" takes it (``mla_cells``);
    ``kda_shape`` (b, s, h, d): chunked KDA in bf16 against the recurrence in
    f32 (``kda_cells``) — both at the hybrid cell's widths and a short length;
    ``gdn_shape`` (b, s, key heads, value heads, d): the same rule with one
    decay a head, key heads shared by value heads (``gdn_cells``);
    ``ssd_shape`` (b, s, heads, head width, state): the scalar decay without a
    correction, one q and one k a position for all heads (``ssd_cells``)."""
    from torchft_tpu.utils.compile_cache import place_compile_cache

    place_compile_cache()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from torchft_tpu.models.transformer import _flash_blocks
    from torchft_tpu.ops.attention import attention, chunked_attention
    from torchft_tpu.ops.pallas.flash_attention import flash_attention

    dev = jax.devices()[0]
    if dev.platform != platform:
        raise SystemExit(f"kernels: want platform {platform}, JAX has {dev.platform}")
    # |got - ref| <= atol + rtol*|ref|. The reference sees the same
    # bf16-rounded inputs, so what differs is the kernel's own bf16: p (and
    # ds) are rounded to 8 mantissa bits before their matmuls and results
    # are stored in bf16 (half an ulp = 2^-9 relative, 0.016 at |x| in
    # [4, 8)). 3e-2 + 3e-2*|ref| is a few ulps; a wrong mask, scale or
    # block index is O(1) off on unit-normal data.
    atol = rtol = 3e-2

    def check(name, fn, shape, must_be_mosaic):
        b, s, h, d = shape[:4]
        dv = shape[4] if len(shape) > 4 else d  # values of another width than keys
        hkv = shape[5] if len(shape) > 5 else h  # grouped heads: fewer key/value heads
        ks = jax.random.split(jax.random.PRNGKey(s + d), 4)
        q, k, v, w = (
            jax.random.normal(kk, (b, s, heads, width), jnp.float32)
            for kk, heads, width in zip(ks, (h, hkv, hkv, h), (d, d, dv, dv))
        )
        qb, kb, vb = (x.astype(jnp.bfloat16) for x in (q, k, v))

        def loss(f, q, k, v):
            o = f(q, k, v)
            return jnp.sum(o.astype(jnp.float32) * w), o

        got_fn = jax.jit(jax.value_and_grad(lambda q, k, v: loss(fn, q, k, v), argnums=(0, 1, 2), has_aux=True))
        with jax.default_matmul_precision("highest"):
            ref_fn = jax.jit(jax.value_and_grad(
                lambda q, k, v: loss(lambda q, k, v: attention(q, k, v, causal=True), q, k, v),
                argnums=(0, 1, 2), has_aux=True))
            # the reference sees the SAME bf16-rounded inputs, in f32
            (_, o_ref), g_ref = ref_fn(*(x.astype(jnp.float32) for x in (qb, kb, vb)))
        lowered = got_fn.lower(qb, kb, vb).as_text()
        mosaic = "tpu_custom_call" in lowered
        t0 = time.perf_counter()
        (_, o), g = jax.block_until_ready(got_fn(qb, kb, vb))
        t_first = time.perf_counter() - t0
        # worst excess over the relative part, to compare with atol
        errs = {
            n: float(jnp.max(jnp.abs(a.astype(jnp.float32) - r) - rtol * jnp.abs(r)))
            for n, a, r in zip(("o", "dq", "dk", "dv"), (o, *g), (o_ref, *g_ref))
        }
        finite = all(bool(jnp.all(jnp.isfinite(x.astype(jnp.float32)))) for x in (o, *g))
        ok = finite and max(errs.values()) <= atol and (mosaic or not must_be_mosaic)
        print(json.dumps({
            "check": name, "shape": shape, "ok": ok, "mosaic_custom_call": mosaic,
            "finite": finite, "err_minus_rtol_ref": {k: round(v, 5) for k, v in errs.items()},
            "atol": atol, "rtol": rtol, "compile_and_first_run_s": round(t_first, 2),
            "device": dev.device_kind,
        }), flush=True)
        return ok

    def flash_check(shape):
        grouped = len(shape) > 5
        tiles = (_flash_blocks(shape[1], shape[3]) if grouped else None) or (128, 128)
        return check(
            f"flash_d{shape[3]}" + ("_grouped" if grouped else ""),
            lambda q, k, v: flash_attention(q, k, v, causal=True, block_q=tiles[0], block_k=tiles[1]),
            shape, must_be_mosaic=platform == "tpu",
        )

    oks = [flash_check(shape) for shape in flash_shapes]
    block_q, block_k = _flash_blocks(cells_shape[1], cells_shape[3]) or (128, 128)
    oks.append(check(
        "flash_cells",
        lambda q, k, v: flash_attention(
            q, k, v, causal=True, block_q=block_q, block_k=block_k
        ),
        cells_shape, must_be_mosaic=platform == "tpu",
    ))
    oks.append(check(
        "chunked",
        lambda q, k, v: chunked_attention(q, k, v, causal=True, chunk=128),
        chunked_shape, must_be_mosaic=False,
    ))
    if mla_shape:
        blocks = _flash_blocks(mla_shape[1], mla_shape[4])
        oks.append(check(
            "mla_cells",
            (lambda q, k, v: flash_attention(q, k, v, causal=True, block_q=blocks[0], block_k=blocks[1]))
            if blocks and platform == "tpu" else (lambda q, k, v: chunked_attention(q, k, v, causal=True, chunk=128)),
            mla_shape, must_be_mosaic=bool(blocks) and platform == "tpu",
        ))
    if kda_shape:
        oks.append(_kda_cells(kda_shape, dev))
    if gdn_shape:
        oks.append(_gdn_cells(gdn_shape, dev))
    if ssd_shape:
        oks.append(_ssd_cells(ssd_shape, dev))
    sys.exit(0 if all(oks) else 1)


def _kda_cells(shape: list, dev) -> bool:
    """Chunked KDA, forward and backward, bf16 operands, against the
    recurrence position by position in f32. Outputs are of order 0.3 and
    bf16 operands put them 2e-3 off (tests/test_kda.py): 2e-2 + 2e-2 |ref|,
    the 2e-2 in units of the array's largest entry where that is over 1 — q's
    gradient reaches 11.7 here and bf16 operands put it 0.028 off past the
    relative part, in the kernels and in the jax.numpy form alike (PR 36, on
    the CPU and on the chip: the absolute 2e-2 failed both)."""
    import jax
    import jax.numpy as jnp

    from torchft_tpu.ops.kda import kda_chunked, kda_recurrent

    b, s, h, d = shape
    ks = jax.random.split(jax.random.PRNGKey(s + d), 6)
    q, k, v, w = (jax.random.normal(kk, (b, s, h, d), jnp.float32) for kk in ks[:4])
    g = -jax.nn.softplus(jax.random.normal(ks[4], (b, s, h, d))) * 0.1
    beta = jax.nn.sigmoid(jax.random.normal(ks[5], (b, s, h)))
    return _held_to_the_recurrence("kda_cells", shape, dev, kda_chunked, kda_recurrent, (q, k, v, g, beta), w)


def _gdn_cells(shape: list, dev) -> bool:
    """The same for the rule with ONE decay a head and key heads shared by
    value heads (``ops/kda.gdn_chunked``; shape b, s, key heads, value heads,
    d): on a TPU at heads 128 wide the Pallas kernel pair with the scalar
    decay, at the decays of the published initial values — ``-A softplus(a +
    1)`` with A up to 16 a head, ~20 nats a POSITION at the fastest — where the
    per-channel kernels would not serve."""
    import jax
    import jax.numpy as jnp

    from torchft_tpu.ops.kda import gdn_chunked, kda_recurrent

    b, s, hk, hv, d = shape
    ks = jax.random.split(jax.random.PRNGKey(s + d + hv), 6)
    q, k = (jax.random.normal(kk, (b, s, hk, d), jnp.float32) for kk in ks[:2])
    v, w = (jax.random.normal(kk, (b, s, hv, d), jnp.float32) for kk in ks[2:4])
    g = -jnp.linspace(0.5, 16.0, hv) * jax.nn.softplus(jax.random.normal(ks[4], (b, s, hv)) + 1.0)
    beta = jax.nn.sigmoid(jax.random.normal(ks[5], (b, s, hv)))

    def by_position(q, k, v, g, beta):
        q, k = (jnp.repeat(x, hv // hk, axis=2) for x in (q, k))
        return kda_recurrent(q, k, v, jnp.broadcast_to(g[..., None], v.shape), beta)

    return _held_to_the_recurrence("gdn_cells", shape, dev, gdn_chunked, by_position, (q, k, v, g, beta), w)


def _ssd_cells(shape: list, dev) -> bool:
    """The same for the scalar decay WITHOUT a correction (``ops/kda.ssd_chunked``;
    shape b, s, heads, head width, state): ONE q and ONE k a position for all
    heads, no ``beta``. On a TPU at a state 128 wide under heads of 64 the
    Pallas kernel pair ``ssd_fwd`` / ``ssd_bwd`` in the mixer's chunk of 128, at
    decays from mild to 16 nats a POSITION."""
    import jax
    import jax.numpy as jnp

    from torchft_tpu.ops.kda import ssd_chunked, ssd_recurrent

    b, s, h, p, n = shape
    ks = jax.random.split(jax.random.PRNGKey(s + p + h), 5)
    q, k = (jax.random.normal(kk, (b, s, n), jnp.float32) for kk in ks[:2])
    v, w = (jax.random.normal(kk, (b, s, h, p), jnp.float32) for kk in ks[2:4])
    g = -jnp.linspace(0.05, 16.0, h) * jax.nn.softplus(jax.random.normal(ks[4], (b, s, h)))
    chunked = lambda q, k, v, g: ssd_chunked(q, k, v, g, chunk=128)  # noqa: E731 — the mixer's chunk
    return _held_to_the_recurrence("ssd_cells", shape, dev, chunked, ssd_recurrent, (q, k, v, g), w)


def _held_to_the_recurrence(name: str, shape: list, dev, chunked, recurrent, inputs, w) -> bool:
    """One check line: ``chunked`` on bf16 q, k, v — value and the gradients of
    every input (q, k, v, g and, where the rule has one, beta) under the probe
    ``w`` — against ``recurrent`` in f32 on the same rounded operands; q and k
    enter L2-normalised, q scaled."""
    import jax
    import jax.numpy as jnp

    q, k, v, *rest = inputs
    d = q.shape[-1]
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * d**-0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    qb, kb, vb = (x.astype(jnp.bfloat16) for x in (q, k, v))

    def loss(f, *a):
        o = f(*a)[0]
        return jnp.sum(o.astype(jnp.float32) * w), o

    every = tuple(range(len(inputs)))
    got_fn = jax.jit(jax.value_and_grad(lambda *a: loss(chunked, *a), argnums=every, has_aux=True))
    with jax.default_matmul_precision("highest"):
        ref_fn = jax.jit(jax.value_and_grad(lambda *a: loss(recurrent, *a), argnums=every, has_aux=True))
        (_, o_ref), g_ref = ref_fn(*(x.astype(jnp.float32) for x in (qb, kb, vb)), *rest)
    # on a TPU, keys 128 wide are the Pallas kernels' (ops/pallas/kda.py): a
    # silent fall back to the jax.numpy form fails here and not only a metric
    must_be_mosaic = dev.platform == "tpu" and d == 128
    mosaic = "tpu_custom_call" in got_fn.lower(qb, kb, vb, *rest).as_text()
    t0 = time.perf_counter()
    (_, o), grads = jax.block_until_ready(got_fn(qb, kb, vb, *rest))
    t_first = time.perf_counter() - t0
    atol = rtol = 2e-2
    errs = {  # past the relative part, in units of the array's scale
        n: float(jnp.max(jnp.abs(a.astype(jnp.float32) - r) - rtol * jnp.abs(r)) / jnp.maximum(1.0, jnp.max(jnp.abs(r))))
        for n, a, r in zip(("o", "dq", "dk", "dv", "dg", "dbeta"), (o, *grads), (o_ref, *g_ref))
    }
    finite = all(bool(jnp.all(jnp.isfinite(x.astype(jnp.float32)))) for x in (o, *grads))
    ok = finite and max(errs.values()) <= atol and (mosaic or not must_be_mosaic)
    print(json.dumps({
        "check": name, "shape": shape, "ok": ok, "mosaic_custom_call": mosaic, "finite": finite,
        "err_minus_rtol_ref": {k: round(v, 5) for k, v in errs.items()}, "atol": atol, "rtol": rtol,
        "compile_and_first_run_s": round(t_first, 2), "device": dev.device_kind,
    }), flush=True)
    return ok


def kernels(
    platform: str,
    flash_shapes: Optional[list] = None,
    chunked_shape: Optional[list] = None,
    cells_shape: Optional[list] = None,
    mla_shape: Optional[list] = None,
    kda_shape: Optional[list] = None,
    gdn_shape: Optional[list] = None,
    ssd_shape: Optional[list] = None,
    timeout: float = 600,
) -> List[Dict[str, Any]]:
    # head_dim 64 and 128 are the two the presets use; S >= 2048. The last is
    # lfm2-8b-a1b-1g's attention layer — s8192, 64 lanes, four query heads a
    # key/value head — at 4 heads over 1 of its 32 over 8 (the f32 reference's
    # [B, H, S, S] scores at 1 GB), at the tiles "auto" picks there (PR 52)
    flash_shapes = flash_shapes or [[1, 2048, 4, 64], [1, 2048, 4, 128], [1, 8192, 4, 64, 64, 1]]
    chunked_shape = chunked_shape or [BATCH, SEQ, 16, 64]  # scale_647M's
    # the benchmark cells' s2048 x 16 heads x 128 (b8 there; 2 keeps the
    # f32 reference's [B,H,S,S] scores at 0.5 GB)
    cells_shape = cells_shape or [2, 2048, 16, 128]
    # the hybrid cell's widths at a short length: MLA's keys 192 and values 128
    # wide, KDA's heads of 128 (8 of the 32: the recurrence's reference is slow)
    mla_shape = mla_shape or [1, 2048, 8, 192, 128]
    kda_shape = kda_shape or [1, 1024, 8, 128]
    # the Gated DeltaNet cell's heads, 16 key heads under 32 value heads of 128
    gdn_shape = gdn_shape or [1, 1024, 16, 32, 128]
    # the state-space cell's widths: 64 heads of 64 under ONE key 128 wide, a block of its mixer
    ssd_shape = ssd_shape or [1, 1024, 64, 64, 128]
    code = (
        "import chip_smoke; chip_smoke.kernels_child("
        f"{platform!r}, {flash_shapes!r}, {chunked_shape!r}, {cells_shape!r}, {mla_shape!r}, {kda_shape!r}, {gdn_shape!r}, "
        f"{ssd_shape!r})"
    )
    try:
        text = run_child("3_kernels", [sys.executable, "-c", code], _child_env(), timeout)
    finally:
        _say_checks("3_kernels")
    return [json.loads(l) for l in text.splitlines() if l.startswith('{"check"')]


def _say_checks(tag: str) -> None:
    try:
        with open(os.path.join(OUT, f"{tag}.log")) as f:
            for line in f:
                if line.startswith('{"check"'):
                    say(f"{tag}: {line.strip()}")
    except OSError:
        pass


# ---------------------------------------------------------------------------
# phase 4: the compile cache stays put
# ---------------------------------------------------------------------------


def _cache_entries(cache_dir: str) -> int:
    try:
        return sum(1 for n in os.listdir(cache_dir) if not n.startswith("."))
    except OSError:
        return 0


def cache_phase(first: Trainer, cache_dir: str, device: str, **steady_kw: Any) -> Trainer:
    """Start phase 2's trainer again on the same cache directory. Pass =
    the first start populated it and the second misses neither step
    program."""
    n = _cache_entries(cache_dir)
    if n == 0:
        raise PhaseFailed(f"4_cache: the first start left {cache_dir} empty")
    second = steady("4_cache", device=device, **steady_kw)
    c1, c2 = _cache_counts(first), _cache_counts(second)
    say(
        f"4_cache: observation ({device}): exec→first committed step "
        f"cold {first.exec_to_first_commit():.1f}s, warm "
        f"{second.exec_to_first_commit():.1f}s; persistent cache [hits, misses] "
        f"cold {c1} warm {c2}; {n} entries in {cache_dir}"
    )
    for prog in ("grads", "apply"):
        hits, misses = c2[prog]
        if misses or not hits:
            raise PhaseFailed(
                f"4_cache: second start of the {prog} program: {hits} hit(s), "
                f"{misses} miss(es) in {cache_dir} — want >=1 and 0"
            )
    say("4_cache: pass — zero misses for grads/apply on the second start")
    return second


# ---------------------------------------------------------------------------
# phase 5a / 5b: four chips
# ---------------------------------------------------------------------------


def one_group_all_chips(platform: str, n_devices: int, device: str, **kw: Any) -> Trainer:
    tr = steady(
        "5a_one_group", platform, n_devices, device,
        mesh={"FSDP": 2, "TP": n_devices // 2}, **kw,
    )
    m = re.search(
        r"sharded_leaves=(\d+) min_shard_devices=(\d+) bytes_in_use=\[([^\]]*)\]",
        tr.placement,
    )
    if not m:
        raise PhaseFailed(f"5a_one_group: no placement line: {tr.placement!r}")
    say(f"5a_one_group: {tr.placement}")
    if int(m.group(1)) == 0 or int(m.group(2)) != n_devices:
        raise PhaseFailed(
            f"5a_one_group: sharded leaves must span {n_devices} distinct "
            f"devices: {tr.placement}"
        )
    used = [int(x) for x in m.group(3).split(",") if x.strip().isdigit()]
    if platform == "tpu" and (len(used) != n_devices or min(used) < (64 << 20)):
        raise PhaseFailed(
            f"5a_one_group: every device should hold >64 MiB of state: {used}"
        )
    say("5a_one_group: pass — shards and memory in use on every device")
    return tr


def kill_and_heal(
    platform: str,
    devices_per_group: int,
    device: str,
    model: str = MODEL,
    batch: int = BATCH,
    seq: int = SEQ,
    steps: int = 8,
    kill_after: int = 3,
    max_aborted: int = 8,
    timeout: float = 900,
) -> Dict[str, Any]:
    """Two groups, two processes; after step ``kill_after`` commits,
    SIGKILL group 1's trainer. The launcher respawns it on the same chips
    and it heals from the survivor."""
    tag = "5b_kill_heal"
    os.makedirs(OUT, exist_ok=True)
    trail = os.path.join(OUT, f"{tag}.trail.jsonl")
    if os.path.exists(trail):
        os.unlink(trail)
    env = {
        "MODEL": model, "BATCH": str(batch), "SEQ": str(seq),
        "STEPS": str(steps), "FSDP": str(devices_per_group), "TP": "1",
        "TORCHFT_EVENT_TRAIL": trail,
    }
    killed: List[Tuple[int, float]] = []
    pat = re.compile(rf" g1/(\d+) train_hsdp: step={kill_after} committed=True")

    def on_line(line: str) -> None:
        m = pat.search(line)
        if m and not killed:
            pid = int(m.group(1))
            os.kill(pid, signal.SIGKILL)
            killed.append((pid, time.monotonic()))
            say(f"{tag}: SIGKILL g1 trainer pid {pid} after step {kill_after} committed")

    # both groups' aborted steps count towards stopping a run that cannot
    # commit; the survivor's own count is checked against the bound below
    trainers, text = launcher_run(tag, 2, env, timeout, 2 * max_aborted, on_line)
    if not killed:
        raise PhaseFailed(f"{tag}: group 1 never committed step {kill_after}; nothing was killed")
    victim_pid, t_kill = killed[0]
    g0 = [t for t in trainers.values() if t.gid == 0]
    g1_new = [t for t in trainers.values() if t.gid == 1 and t.pid != victim_pid]
    if len(g0) != 1 or len(g1_new) != 1:
        raise PhaseFailed(
            f"{tag}: want one survivor and one respawn, saw "
            f"{[(t.gid, t.pid) for t in trainers.values()]}\n{_tail(text)}"
        )
    survivor, reborn = g0[0], g1_new[0]
    for tr in (survivor, reborn):
        _check_finished(tag, tr, platform, devices_per_group, steps)
    say(f"{tag}: chips — survivor g0 [{survivor.chips}], victim "
        f"[{trainers[victim_pid].chips}], respawn g1 [{reborn.chips}]")
    if reborn.chips != trainers[victim_pid].chips:
        raise PhaseFailed(f"{tag}: the respawn did not get its predecessor's chips")
    if reborn.t_heal is None or reborn.t_healed is None:
        raise PhaseFailed(f"{tag}: the respawned g1/{reborn.pid} log shows no heal\n{_tail(text)}")
    if survivor.checksum() is None or survivor.checksum() != reborn.checksum():
        raise PhaseFailed(
            f"{tag}: param_checksum differs: g0 {survivor.checksum()} vs g1 {reborn.checksum()}"
        )
    aborted = survivor.aborted()
    say(f"{tag}: survivor aborted (committed=False) steps: {aborted} (bound {max_aborted})")
    if aborted > max_aborted:
        raise PhaseFailed(f"{tag}: survivor aborted {aborted} steps > {max_aborted}")

    from torchft_tpu.telemetry import read_trail  # jax-free

    events = read_trail(trail)
    want = ["peer_death", "eviction", "heal_begin", "heal_end"]
    seq_seen, i = [], 0
    for e in sorted(events, key=lambda e: e["ts"]):
        if i < len(want) and e["event"] == want[i]:
            seq_seen.append(e)
            i += 1
    if i < len(want):
        raise PhaseFailed(
            f"{tag}: event trail lacks {' → '.join(want)}; kinds seen: "
            f"{sorted({e['event'] for e in events})}"
        )
    heal_end = seq_seen[-1]
    if not heal_end.get("bytes"):
        raise PhaseFailed(f"{tag}: heal_end carries no bytes: {heal_end}")

    planes = sorted(set(re.findall(r"plane=(\S+)", text)))
    t0 = reborn.t0()
    first_commit = reborn.committed()[0][0]
    cache = _cache_counts(reborn)
    obs = {
        "device": device,
        "plane_info": planes,
        "heal_bytes": heal_end["bytes"],
        "heal_s": heal_end.get("duration_s"),
        "heal_gb_per_s": round(heal_end["bytes"] / 1e9 / max(heal_end.get("duration_s") or 0, 1e-9), 3),
        "heal_stages": (heal_end.get("heal_stats") or {}).get("stages"),
        "kill_to_respawn_s": round(t0 - t_kill, 2),
        "respawn_exec_import_s": round((reborn.t_first or t0) - t0, 2),
        "respawn_backend_s": round((reborn.t_devices or t0) - (reborn.t_first or t0), 2),
        "respawn_init_state_s": round((reborn.t_model or t0) - (reborn.t_devices or t0), 2),
        "respawn_quorum_join_s": round(reborn.t_heal - (reborn.t_model or t0), 2),
        "respawn_heal_s": round(reborn.t_healed - reborn.t_heal, 2),
        "respawn_to_first_commit_s": round(first_commit - t0, 2),
        "kill_to_first_commit_s": round(first_commit - t_kill, 2),
        "respawn_cache_hits_misses": cache,
        "survivor_aborted_steps": aborted,
        "checksum": survivor.checksum(),
    }
    say(f"{tag}: observations (for ROADMAP S4, not results): {json.dumps(obs)}")
    say(f"{tag}: pass — killed, respawned on its own chips, healed, identical "
        f"param_checksum {survivor.checksum()}; trail {' → '.join(want)} "
        f"({heal_end['bytes']} bytes)")
    return obs


# ---------------------------------------------------------------------------


def main() -> int:
    t_start = time.monotonic()
    try:
        info = probe("tpu")
        device = f"{info['count']} x {info['kind']}"
        build_native()
        from torchft_tpu.utils.compile_cache import place_compile_cache

        cache_dir = place_compile_cache()  # children inherit it
        say(f"compile cache: {cache_dir} ({_cache_entries(cache_dir)} entries)")
        # phase 2 uses ONE chip whatever the host has (FSDP=1 TP=1)
        first = steady("2_steady", "tpu", info["count"], device)
        kernels("tpu")
        cache_phase(first, cache_dir, device, platform="tpu", n_devices=info["count"])
        if info["count"] >= 4:
            one_group_all_chips("tpu", info["count"], device)
            kill_and_heal("tpu", info["count"] // 2, device)
        else:
            say(
                f"5a/5b not run because {info['count']} chip(s) are visible "
                "on this machine (they need 4)"
            )
    except PhaseFailed as e:
        print(f"[smoke] FAILED after {time.monotonic() - t_start:.0f}s: {e}", flush=True)
        return 1
    say(f"all phases passed in {time.monotonic() - t_start:.0f}s")
    print(json.dumps({
        "ok": True,
        "device": {"platform": info["platform"], "kind": info["kind"], "count": info["count"]},
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
