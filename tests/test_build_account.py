"""Program builds seen from inside (``torchft_tpu/telemetry/builds.py``):
the ``jax.monitoring`` listeners that put every outermost trace, lowering
and compile-or-load on the Tracer as ``build.*`` spans, the account behind
``tft.build.counters``, and ``TrainStep``'s one annotation a call.
"""

import glob
import os
import threading

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax._src import monitoring as jax_monitoring

from torchft_tpu.models.transformer import TransformerConfig
from torchft_tpu.parallel.mesh import MeshConfig, make_mesh
from torchft_tpu.parallel.train_step import TrainStep
from torchft_tpu.telemetry import builds, tracing

STAGES = ("build.trace", "build.lower", "build.compile")
TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
CFG = TransformerConfig(
    vocab_size=128, d_model=32, n_layers=1, n_heads=2, head_dim=16, d_ff=64,
    dtype=jnp.float32,
)


@pytest.fixture(autouse=True)
def installed():
    assert builds.install()
    tracing.TRACER.clear()
    yield


def fresh_program(name="tft_probe"):
    """A new function (so a new build) that calls a nested ``jit`` and a
    ``custom_vjp``, as a train step does."""

    @jax.custom_vjp
    def wave(x):
        return jnp.sin(x)

    wave.defvjp(lambda x: (jnp.sin(x), x), lambda x, g: (g * jnp.cos(x),))

    @jax.jit
    def inner(x):
        return jnp.where(x > 0, x, 0.0).sum()

    def program(x):
        return jax.grad(lambda y: inner(wave(y)))(x)

    program.__name__ = program.__qualname__ = name
    return jax.jit(program)


def build_spans(program=None):
    return [
        s for s in tracing.TRACER.recent()
        if s["name"] in STAGES and (program is None or s["attrs"]["program"] == program)
    ]


def test_install_twice_leaves_one_set_of_listeners():
    assert builds.install() and builds.install()
    assert jax_monitoring.get_scalar_listeners().count(builds._on_start) == 1
    assert jax_monitoring.get_event_duration_listeners().count(builds._on_end) == 1
    assert jax_monitoring.get_event_listeners().count(builds._on_event) == 1
    builds.uninstall()
    try:
        assert builds._on_start not in jax_monitoring.get_scalar_listeners()
        builds.uninstall()  # idempotent too
    finally:
        assert builds.install()
    assert jax_monitoring.get_scalar_listeners().count(builds._on_start) == 1


@pytest.mark.parametrize("caller", ["place_compile_cache", "TrainStep"])
def test_no_caller_has_to_install(caller, monkeypatch):
    """Whoever places the compile cache after importing jax, or makes a
    ``TrainStep``, has the listeners: ``examples/train_hsdp.py``, the
    benchmark's loops and a respawned group add no call."""
    builds.uninstall()
    assert builds._on_start not in jax_monitoring.get_scalar_listeners()
    try:
        if caller == "place_compile_cache":
            from torchft_tpu.utils import compile_cache

            for name in ("JAX_COMPILATION_CACHE_DIR", "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"):
                monkeypatch.setenv(name, os.environ[name])  # tests/conftest.py's: nothing moves
            assert compile_cache.place_compile_cache() == os.environ["JAX_COMPILATION_CACHE_DIR"]
        else:
            TrainStep(CFG, optax.adamw(1e-2), make_mesh(MeshConfig(), devices=jax.devices()[:1]))
        assert jax_monitoring.get_scalar_listeners().count(builds._on_start) == 1
    finally:
        assert builds.install()


def test_one_span_a_stage_and_outermost_traces_only():
    x = jnp.ones(4)  # its own builds happen here, not under the probe
    traces = []

    def listen(event, duration, **kw):
        if event == TRACE_EVENT:
            traces.append((kw.get("fun_name"), duration))

    tracing.TRACER.clear()
    before = builds.counters()
    jax_monitoring.register_event_duration_secs_listener(listen)
    try:
        fresh_program("tft_nested")(x)
    finally:
        jax_monitoring.unregister_event_duration_listener(listen)
    after = builds.counters()
    spans = build_spans()
    assert [s["name"] for s in spans] == list(STAGES)
    assert all(s["attrs"]["program"] == "tft_nested" for s in spans)
    assert spans[2]["attrs"]["cache"] in ("off", "miss", "hit")
    # JAX reported the nested traces (inner, _where, sin, ...), none is a span
    assert len(traces) > 3
    outermost = dict(traces)["tft_nested"]
    assert sum(d for _, d in traces) > outermost
    assert after["trace_s"] - before["trace_s"] == pytest.approx(outermost, abs=1e-9)
    assert after["builds"] - before["builds"] == 1
    assert after["lower_s"] > before["lower_s"]
    # a tft_* program: all three stages are the group's own
    stage_s = sum(after[k] - before[k] for k in ("trace_s", "lower_s", "load_s", "compile_s"))
    assert after["step_program_s"] - before["step_program_s"] == pytest.approx(stage_s, abs=1e-9)
    assert after["listener_errors"] == before["listener_errors"]
    # the same dict until the next build ends: nothing is rebuilt on the hot path
    assert builds.counters() is after

    # a program by another name is nobody's step program
    fresh_program("harness_check")(x)
    assert builds.counters()["step_program_s"] == after["step_program_s"]
    assert builds.counters()["builds"] == after["builds"] + 1


@pytest.fixture
def temporary_cache(tmp_path):
    from jax.experimental.compilation_cache import compilation_cache

    keys = (
        "jax_compilation_cache_dir", "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes",
    )
    old = {k: getattr(jax.config, k) for k in keys}
    compilation_cache.reset_cache()
    jax.config.update(keys[0], str(tmp_path / "cache"))
    jax.config.update(keys[1], 0.0)
    jax.config.update(keys[2], 0)
    yield
    compilation_cache.reset_cache()
    for k, v in old.items():
        jax.config.update(k, v)


def test_a_second_build_is_a_load_from_the_cache(temporary_cache):
    x = jnp.ones(4)
    program = fresh_program("tft_cached")
    start = builds.counters()
    program(x)
    cold = builds.counters()
    (compiled,) = [s for s in build_spans("tft_cached") if s["name"] == "build.compile"]
    assert compiled["attrs"]["cache"] == "miss"
    assert cold["cache_misses"] == start["cache_misses"] + 1
    assert cold["compile_s"] > start["compile_s"] and cold["load_s"] == start["load_s"]

    jax.clear_caches()  # what is in memory goes; the directory stays
    tracing.TRACER.clear()
    program(x)
    warm = builds.counters()
    spans = build_spans("tft_cached")
    # Python tracing and the lowering are paid again: no cache keeps them
    assert [s["name"] for s in spans] == list(STAGES)
    assert spans[2]["attrs"]["cache"] == "hit"
    assert spans[2]["attrs"]["retrieval_s"] > 0
    assert warm["load_s"] > cold["load_s"] and warm["compile_s"] == cold["compile_s"]
    assert warm["cache_hits"] == cold["cache_hits"] + 1
    assert warm["cache_misses"] == cold["cache_misses"]
    assert warm["retrieval_s"] - cold["retrieval_s"] == pytest.approx(spans[2]["attrs"]["retrieval_s"])
    assert warm["trace_s"] > cold["trace_s"] and warm["lower_s"] > cold["lower_s"]
    assert warm["builds"] == start["builds"] + 2


def test_a_build_on_a_second_thread_while_the_main_thread_builds():
    x = jnp.ones(4)
    other = fresh_program("tft_other_thread")
    seen = {}

    def while_tracing(y):
        # the main thread is inside its build.trace here
        t = threading.Thread(target=lambda: seen.update(out=other(x), tid=threading.get_ident()))
        t.start()
        t.join(timeout=120)
        assert not t.is_alive()
        return y * 2.0

    while_tracing.__name__ = while_tracing.__qualname__ = "tft_main_thread"
    before = builds.counters()
    jax.jit(while_tracing)(x)
    after = builds.counters()
    mine, theirs = build_spans("tft_main_thread"), build_spans("tft_other_thread")
    assert [s["name"] for s in mine] == [s["name"] for s in theirs] == list(STAGES)
    assert {s["tid"] for s in theirs} == {seen["tid"] & 0x7FFFFFFF}
    assert {s["tid"] for s in mine} == {threading.get_ident() & 0x7FFFFFFF}
    # the other thread's stack is its own: its stages are nobody's children
    assert not any(s.get("parent_id") for s in theirs)
    assert after["builds"] == before["builds"] + 2
    assert after["listener_errors"] == before["listener_errors"]
    # both in the same totals; the main thread's trace held the other's build
    spent = after["step_program_s"] - before["step_program_s"]
    assert spent >= sum(s["dur_s"] for s in theirs) * 0.5


def test_a_build_under_a_step_carries_the_steps_trace_id():
    x = jnp.ones(4)
    tracing.TRACER.set_context(replica_id="builds_0", quorum_epoch=3)
    try:
        with tracing.TRACER.span("step", step_num=7) as step:
            with tracing.TRACER.span("grads") as grads:
                fresh_program("tft_recompiled")(x)
    finally:
        tracing.TRACER.set_context(replica_id="", quorum_epoch=-1)
    spans = build_spans("tft_recompiled")
    assert [s["name"] for s in spans] == list(STAGES)
    assert step.trace_id == "builds_0:7:3"
    for s in spans:
        assert s["trace_id"] == step.trace_id
        assert s["parent_id"] == grads.span_id
        assert s["replica_id"] == "builds_0"
        # CLOCK_MONOTONIC, inside the piece that built
        assert grads.t0_mono_ns <= s["t0_mono_ns"] <= grads.t0_mono_ns + grads.dur_s * 1e9


@pytest.mark.parametrize("fault", ["end_without_start", "unknown_event", "raising_tracer", "out_of_step"])
def test_a_faulty_listener_leaves_the_build_unharmed(fault, monkeypatch):
    x = jnp.ones(4)
    before = builds.counters()
    if fault == "end_without_start":
        jax.monitoring.record_event_duration_secs(TRACE_EVENT, 0.5, fun_name="never_started")
        jax.monitoring.record_event_duration_secs("/jax/core/compile/backend_compile_duration", 0.5, fun_name="x")
        assert builds.counters()["listener_errors"] == before["listener_errors"] + 2
        assert builds.counters()["trace_s"] == before["trace_s"]
    elif fault == "unknown_event":
        jax.monitoring.record_scalar("/jax/some/new_event", 1.0, fun_name="f")
        jax.monitoring.record_event_duration_secs("/jax/some/new_event", 1.0, other="f")
        jax.monitoring.record_event("/jax/some/new_event", why="x")
        jax.monitoring.record_event("/jax/compilation_cache/cache_hits")  # no build open
        jax.monitoring.record_event_duration_secs("/jax/compilation_cache/cache_retrieval_time_sec", 1.0)
        assert builds.counters() is before
    elif fault == "raising_tracer":
        def boom(*a, **kw):
            raise RuntimeError("tracer down")

        monkeypatch.setattr(tracing.TRACER, "span", boom)
        out = fresh_program("tft_unharmed")(x)
        monkeypatch.undo()
        assert out.shape == (4,)
        assert builds.counters()["listener_errors"] > before["listener_errors"]
        assert not build_spans("tft_unharmed")
    else:
        # a start whose end never comes (JAX drops ends at interpreter
        # exit): the next matching end closes down to its own name
        jax.monitoring.record_scalar("/jax/core/compile/jaxpr_to_mlir_module_duration", 0.0, fun_name="jit(lost)")
        jax.monitoring.record_scalar("/jax/core/compile/backend_compile_duration", 0.0, fun_name="jit(dangling)")
        jax.monitoring.record_event_duration_secs("/jax/core/compile/jaxpr_to_mlir_module_duration", 0.25, fun_name="jit(lost)")
        assert [s["name"] for s in build_spans()] == ["build.compile", "build.lower"]
        assert builds.counters()["lower_s"] == before["lower_s"] + 0.25
    # and the next build is whole
    tracing.TRACER.clear()
    fresh_program("tft_after")(x)
    assert [s["name"] for s in build_spans("tft_after")] == list(STAGES)
    assert not builds._state().stack and builds._state().depth == 0


def test_the_account_loses_no_update_between_threads():
    """Stages end on whatever thread built; the hot path reads with no lock.
    More writers than cores and a short switch interval: every delta lands,
    and a reader never sees a dict that is being written."""
    import sys

    account = builds._Account()
    writers, rounds = 16, 500
    torn = []

    def write():
        for _ in range(rounds):
            account.add(builds=1, trace_s=0.5, load_s=0.25)
            seen = account.counters()
            if seen["trace_s"] != 0.5 * seen["builds"] or seen["load_s"] != 0.25 * seen["builds"]:
                torn.append(dict(seen))

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=write) for _ in range(writers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads) and not torn
    final = account.counters()
    assert final["builds"] == writers * rounds
    assert final["trace_s"] == 0.5 * writers * rounds and final["load_s"] == 0.25 * writers * rounds


@pytest.fixture(scope="module")
def train_step():
    mesh = make_mesh(MeshConfig(), devices=jax.devices()[:1])
    return TrainStep(CFG, optax.adamw(1e-2), mesh)


def test_warm_apply_on_a_daemon_thread_builds_tft_apply(train_step):
    """The heal's warm-up (``FTTrainer._heal_warmup``) compiles ``apply`` on
    a daemon thread: its stages are that thread's, under the process's
    ``trace_id``."""
    ts = train_step
    params = ts.init_params(jax.random.PRNGKey(0))
    opt = ts.init_opt(params)
    spec = lambda tree: jax.tree_util.tree_map(lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype), tree)  # noqa: E731
    tracing.TRACER.set_context(replica_id="healer_1", step=12, quorum_epoch=4)
    tracing.TRACER.clear()
    try:
        t = threading.Thread(target=ts.warm_apply, args=(spec(params), spec(opt)), daemon=True)
        t.start()
        t.join(timeout=120)
        assert not t.is_alive()
    finally:
        tracing.TRACER.set_context(replica_id="", step=-1, quorum_epoch=-1)
    spans = build_spans("tft_apply")
    assert [s["name"] for s in spans] == list(STAGES)
    assert {s["trace_id"] for s in spans} == {"healer_1:12:4"}
    assert {s["tid"] for s in spans} == {t.ident & 0x7FFFFFFF}


def test_warm_apply_lowers_the_program_the_first_apply_after_a_heal_runs(train_step, monkeypatch):
    """``grads`` of this stack comes in pieces, so the update that runs takes
    pieces: the heal's warm-up lowers THAT program from the transferred shapes
    (``jax.ShapeDtypeStruct``s without shardings, as ``spec_tree_from_header``
    gives them), letter for letter what the first ``apply`` lowers from the
    arrays the exchange put back — one ``jit_tft_apply``, not a second variant
    for the persistent cache to miss."""
    ts = train_step
    assert ts._chain is not None
    params = ts.init_params(jax.random.PRNGKey(0))
    opt = ts.init_opt(params)
    spec = lambda tree: jax.tree_util.tree_map(lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype), tree)  # noqa: E731
    lowered = []
    monkeypatch.setattr(jax.stages.Lowered, "compile", lambda self, *a, **kw: lowered.append(self))
    ts.warm_apply(spec(params), spec(opt))
    monkeypatch.undo()
    (warmed,) = lowered
    _, grads = ts.grads(params, ts.shard_batch(jnp.zeros((2, 16), jnp.int32)))
    # as the host exchange hands them back: placed where `grads` had them
    averaged = jax.tree_util.tree_map(lambda g: jax.device_put(np.asarray(g), g.sharding), grads)
    with jax.set_mesh(ts.mesh):
        ran = ts._apply_pieces.lower(params, opt, averaged)
    assert ran.as_text() == warmed.as_text() and "module @jit_tft_apply" in ran.as_text()
    before = len(build_spans("tft_apply"))
    ts.apply(params, opt, averaged)
    assert [s["name"] for s in build_spans("tft_apply")[before:]].count("build.compile") <= 1


def test_first_call_s_is_each_programs_first_call(train_step):
    ts = TrainStep(CFG, optax.adamw(1e-2), train_step.mesh)
    params = ts.init_params(jax.random.PRNGKey(1))
    opt = ts.init_opt(params)
    tokens = ts.shard_batch(jnp.zeros((2, 16), jnp.int32))
    start = builds.counters()["first_call_s"]
    _, grads = ts.grads(params, tokens)
    after_grads = builds.counters()["first_call_s"]
    assert after_grads > start
    ts.grads(params, tokens)
    assert builds.counters()["first_call_s"] == after_grads
    params, opt = ts.apply(params, opt, grads)
    after_apply = builds.counters()["first_call_s"]
    assert after_apply > after_grads
    _, params, opt = ts.step(params, opt, tokens)
    after_fused = builds.counters()["first_call_s"]
    assert after_fused > after_apply
    ts.step(params, opt, tokens)
    assert builds.counters()["first_call_s"] == after_fused
    # the first calls hold their builds: no less than JAX's stages of them
    built = sum(s["dur_s"] for s in build_spans() if s["attrs"]["program"].startswith("tft_"))
    assert after_fused - start >= built * 0.9


def test_the_profilers_trace_holds_the_stages_and_the_counters(train_step, tmp_path):
    """Under a ``jax.profiler`` session a rebuild shows as
    ``tft.build.trace|lower|compile`` on the thread that built, with
    ``program`` and ``cache``, and ``tft.build.counters`` rides every
    ``TrainStep.step`` / ``grads`` call with the account as its stats."""
    ts = TrainStep(CFG, optax.adamw(1e-2), train_step.mesh)
    params = ts.init_params(jax.random.PRNGKey(2))
    opt = ts.init_opt(params)
    tokens = ts.shard_batch(jnp.zeros((2, 16), jnp.int32))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        before = builds.counters()
        ts.grads(params, tokens)  # a fresh TrainStep: builds tft_grads
        _, params, opt = ts.step(params, opt, tokens)  # ... and tft_fused
        _, params, opt = ts.step(params, opt, tokens)
        jax.block_until_ready(params)
        final = builds.counters()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    lines = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                events = [
                    (ev.start_ns, ev.name, dict(ev.stats), ev.duration_ns)
                    for ev in line.events if ev.name.startswith("tft.build.")
                ]
                if events:
                    lines.append(sorted(events, key=lambda e: e[0]))
    (main,) = lines  # everything was built, and stepped, on this thread
    stages = [(name, stats) for _, name, stats, _ in main if name != "tft.build.counters"]
    # this stack is cut a layer at a time: `grads` is a chain of three programs
    # under one name (head, layer, tail), each built at its first call
    assert ts._chain is not None
    for program, built in (("tft_grads", 3), ("tft_fused", 1)):
        mine = [(n, s) for n, s in stages if s["program"] == program]
        # a second call whose arguments are placed otherwise (the fused
        # step's own outputs, a layer's cotangent from the layer above)
        # looks its trace up again: microseconds, a trace
        full = [i for i, (n, _) in enumerate(mine) if n == "tft.build.compile"]
        assert len(full) == built
        for i in full:
            assert [n for n, _ in mine[i - 2 : i + 1]] == ["tft." + s for s in STAGES]
            assert mine[i][1]["cache"] in ("off", "miss", "hit") and "retrieval_s" in mine[i][1]
        assert {n for i, (n, _) in enumerate(mine) if not any(f - 2 <= i <= f for f in full)} <= {"tft.build.trace"}
    counters = [(stats, dur) for _, name, stats, dur in main if name == "tft.build.counters"]
    assert len(counters) == 3  # one a call of grads / step
    assert all(set(stats) == set(final) for stats, _ in counters)
    assert all(dur < 1e6 for _, dur in counters)  # zero-length carriers
    # totals since start: they only grow, and the last call's are the account's
    first, second, last = (stats for stats, _ in counters)
    assert before["builds"] < first["builds"] < second["builds"] == last["builds"] == final["builds"]
    assert first["first_call_s"] < second["first_call_s"] == last["first_call_s"]
    assert last["trace_s"] == pytest.approx(final["trace_s"])
    assert last["step_program_s"] == pytest.approx(final["step_program_s"])


def test_the_counters_ride_step_and_grads_and_not_apply(train_step, monkeypatch):
    ts = TrainStep(CFG, optax.adamw(1e-2), train_step.mesh)
    params = ts.init_params(jax.random.PRNGKey(3))
    opt = ts.init_opt(params)
    tokens = ts.shard_batch(jnp.zeros((2, 16), jnp.int32))
    seen = []
    monkeypatch.setattr(tracing, "session_open", lambda: True)  # as under a profiler session
    monkeypatch.setattr(tracing, "annotate", lambda name, **stats: seen.append((name, stats)) or tracing._NO_SPAN)
    _, grads = ts.grads(params, tokens)
    assert [n for n, _ in seen] == ["build.counters"]
    params, opt = ts.apply(params, opt, grads)
    assert len(seen) == 1
    ts.step(params, opt, tokens)
    assert [n for n, _ in seen] == ["build.counters"] * 2
    # the account itself, not a copy made a call
    assert seen[1][1] == builds.counters() and seen[0][1]["builds"] < seen[1][1]["builds"]


def test_without_a_session_a_call_costs_no_annotation(monkeypatch):
    made = []
    monkeypatch.setattr(tracing, "_profiler_annotation", lambda *a, **kw: made.append(a))
    builds.annotate_counters()
    with tracing.annotate("exchange.pack", step=1):
        pass
    assert not made


def test_a_spawned_trainer_builds_under_this_processs_cache_and_optimisation_settings(monkeypatch, tmp_path):
    """What ``tests/conftest.py`` sets is what JAX reads in this process and
    what a trainer of the soak tiers is started with: the child loads what the
    run has compiled, and optimises as little."""
    import subprocess

    from tests.test_chaos import _spawn

    started = []
    monkeypatch.setattr(subprocess, "Popen", lambda cmd, env, **kw: started.append(env))
    _spawn(0, "localhost:0", str(tmp_path))
    (env,) = started
    settings = ("JAX_COMPILATION_CACHE_DIR", "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "JAX_DISABLE_MOST_OPTIMIZATIONS")
    assert {name: env[name] for name in settings} == {name: os.environ[name] for name in settings}
    assert jax.config.jax_compilation_cache_dir == env[settings[0]]
    assert jax.config.jax_persistent_cache_min_compile_time_secs == float(env[settings[1]])
    assert jax.config.values["jax_disable_most_optimizations"] == (env[settings[2]] == "1")
