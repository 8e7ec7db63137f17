"""Program builds seen from inside: every trace, lowering and
compile-or-load of a jitted program as a span of the one Tracer.

JAX reports each stage of each build through ``jax.monitoring`` (public
API): a scalar at the START of ``/jax/core/compile/jaxpr_trace_duration``,
``…/jaxpr_to_mlir_module_duration`` and ``…/backend_compile_duration`` with
the function's name, a duration at its END, and between a backend stage's
start and end, on the same thread, ``/jax/compilation_cache/cache_hits`` /
``cache_misses`` / ``cache_retrieval_time_sec``. :func:`install` registers
three listeners for them — no wrapper around ``jax.jit``, no change to how
any program is built:

* a stage is a ``TRACER.span`` ``build.trace`` / ``build.lower`` /
  ``build.compile`` with ``program`` (``tft_fused``, ``tft_grads``,
  ``tft_apply``, ...) on the thread that builds, so it carries the
  ``trace_id`` of the step it ran under, lands in the ring, the JSONL and
  the piggyback to ``/trace``, and shows as ``tft.build.*`` in any profiler
  session; ``build.compile`` says ``cache`` = ``hit`` (loaded from the
  persistent cache, with ``retrieval_s``), ``miss`` (compiled and written)
  or ``off`` (neither reported);
* outermost stages only: JAX reports a trace for every jitted function it
  meets INSIDE a trace (thousands a program). A trace that starts while this
  thread is inside a build stage moves a per-thread depth and nothing else;
* the account (:func:`counters`): process totals since start, which
  ``TrainStep`` puts on a zero-length ``tft.build.counters`` a call.

A listener never raises into JAX: an error is swallowed and counted
(``listener_errors``). Imports nothing of JAX until ``jax`` is imported.
"""

from __future__ import annotations

import sys
import threading
from typing import Any, Dict, List, Optional

from torchft_tpu.telemetry import tracing

__all__ = ["install", "uninstall", "counters", "first_call", "annotate_counters"]

_COMPILE = "/jax/core/compile/"
_STAGES = {
    _COMPILE + "jaxpr_trace_duration": "trace",
    _COMPILE + "jaxpr_to_mlir_module_duration": "lower",
    _COMPILE + "backend_compile_duration": "compile",
}
_CACHE = "/jax/compilation_cache/"
_CACHE_EVENTS = {_CACHE + "cache_hits": "hit", _CACHE + "cache_misses": "miss"}
_RETRIEVAL = _CACHE + "cache_retrieval_time_sec"
# a respawned group's own programs (train_step.py names them), apart from a
# harness's reference check and eager ops
STEP_PROGRAMS = "tft_"


class _Account:
    """Process totals since start. Written when a stage ends, under one
    lock; read on the hot path as one dict that is rebuilt only then."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._totals: Dict[str, Any] = {
            "builds": 0,  # backend stages ended
            "trace_s": 0.0,  # outermost traces only
            "lower_s": 0.0,
            "load_s": 0.0,  # backend stages with cache="hit"
            "compile_s": 0.0,  # backend stages with cache "miss" or "off"
            "cache_hits": 0,
            "cache_misses": 0,
            "retrieval_s": 0.0,
            "step_program_s": 0.0,  # all stages of the tft_* programs
            "first_call_s": 0.0,  # TrainStep: first call of each program
            "listener_errors": 0,
        }
        self._counters = dict(self._totals)

    def add(self, **deltas: Any) -> None:
        with self._lock:
            for key, delta in deltas.items():
                self._totals[key] += delta
            self._counters = dict(self._totals)

    def counters(self) -> Dict[str, Any]:
        return self._counters


_ACCOUNT = _Account()
_TLS = threading.local()
_INSTALL_LOCK = threading.Lock()
_installed = False


class _Open:
    """A stage open on this thread."""

    __slots__ = ("ctx", "stage", "program", "cache", "retrieval_s", "inner_s")

    def __init__(self, ctx: Any, stage: str, program: str) -> None:
        self.ctx = ctx
        self.stage = stage
        self.program = program
        self.cache = "off"
        self.retrieval_s = 0.0
        self.inner_s = 0.0  # stages that ran inside this one, counted there


def _state() -> Any:
    if not hasattr(_TLS, "stack"):
        _TLS.stack = []  # List[_Open]
        _TLS.depth = 0  # traces open inside a stage
    return _TLS


def _error() -> None:
    _ACCOUNT.add(listener_errors=1)


def _program(fun_name: Any) -> str:
    name = str(fun_name)
    return name[4:-1] if name.startswith("jit(") and name.endswith(")") else name


def _on_start(event: str, value: Any = None, **kwargs: Any) -> None:
    stage = _STAGES.get(event)
    if stage is None:
        return
    try:
        st = _state()
        if stage == "trace" and (st.depth or st.stack):
            st.depth += 1
            return
        program = _program(kwargs.get("fun_name", ""))
        ctx = tracing.TRACER.span("build." + stage, program=program)
        ctx.__enter__()
        st.stack.append(_Open(ctx, stage, program))
    except Exception:  # noqa: BLE001
        _error()


def _on_end(event: str, duration: float = 0.0, **kwargs: Any) -> None:
    stage = _STAGES.get(event)
    try:
        if stage is None:
            if event == _RETRIEVAL:
                top = _open_compile()
                if top is not None:
                    top.retrieval_s += float(duration)
            return
        st = _state()
        if stage == "trace" and st.depth:
            st.depth -= 1
            return
        at = next(
            (i for i in range(len(st.stack) - 1, -1, -1) if st.stack[i].stage == stage),
            None,
        )
        if at is None:  # an end without a start
            _error()
            return
        while len(st.stack) > at + 1:  # out of step: their ends never came
            st.stack.pop().ctx.__exit__(None, None, None)
        _close(st.stack.pop(), float(duration), st.stack[-1] if st.stack else None)
    except Exception:  # noqa: BLE001
        _error()


def _on_event(event: str, **kwargs: Any) -> None:
    cache = _CACHE_EVENTS.get(event)
    if cache is None:
        return
    try:
        top = _open_compile()
        if top is not None:
            top.cache = cache
    except Exception:  # noqa: BLE001
        _error()


def _open_compile() -> Optional[_Open]:
    stack: List[_Open] = _state().stack
    return stack[-1] if stack and stack[-1].stage == "compile" else None


def _close(op: _Open, duration: float, parent: Optional[_Open]) -> None:
    """End the stage's span and put JAX's own seconds for it (the seconds
    of its ``Finished ...`` log line) into the account."""
    own = max(duration - op.inner_s, 0.0)
    if parent is not None:
        parent.inner_s += duration
    if op.stage == "compile":
        hit = op.cache == "hit"
        op.ctx.set(cache=op.cache, retrieval_s=op.retrieval_s)
        deltas: Dict[str, Any] = {
            "builds": 1,
            "load_s" if hit else "compile_s": own,
            "cache_hits": int(hit),
            "cache_misses": int(op.cache == "miss"),
            "retrieval_s": op.retrieval_s,
        }
    else:
        deltas = {op.stage + "_s": own}
    if op.program.startswith(STEP_PROGRAMS):
        deltas["step_program_s"] = own
    try:
        op.ctx.__exit__(None, None, None)
    finally:
        _ACCOUNT.add(**deltas)


def install() -> bool:
    """Register the listeners, once a process; a no-op before ``jax`` is
    imported. Never raises. ``TrainStep.__init__`` and
    ``place_compile_cache()`` call it, so no caller has to."""
    global _installed
    try:
        if "jax" not in sys.modules:
            return False
        with _INSTALL_LOCK:
            if not _installed:
                import jax.monitoring as monitoring

                monitoring.register_scalar_listener(_on_start)
                monitoring.register_event_duration_secs_listener(_on_end)
                monitoring.register_event_listener(_on_event)
                _installed = True
        return True
    except Exception:  # noqa: BLE001
        _error()
        return False


def uninstall() -> None:
    """Take the listeners away again (tests)."""
    global _installed
    with _INSTALL_LOCK:
        if _installed:
            import jax.monitoring as monitoring

            monitoring.unregister_scalar_listener(_on_start)
            monitoring.unregister_event_duration_listener(_on_end)
            monitoring.unregister_event_listener(_on_event)
            _installed = False


def counters() -> Dict[str, Any]:
    """The account as it stood when the last stage ended. Treat as
    read-only: every caller gets the same dict until the next one ends."""
    return _ACCOUNT.counters()


def first_call(seconds: float) -> None:
    """``TrainStep``: the wall seconds of the first call of one of its
    programs until it returned — the build as a caller feels it."""
    _ACCOUNT.add(first_call_s=float(seconds))


def annotate_counters() -> None:
    """One zero-length ``tft.build.counters`` with the account as its stats
    (an annotation takes its stats at entry) — trace grain: no ring entry,
    no lock, and with no session open nothing but the question."""
    if tracing.session_open():
        with tracing.annotate("build.counters", **_ACCOUNT.counters()):
            pass
