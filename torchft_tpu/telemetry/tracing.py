"""Distributed spans for the FT runtime — the cross-replica timeline.

PR 1's metrics/event-trail answer "how many, how long"; spans answer
"what overlapped what, across which replicas". Every quorum RPC, heal
send/recv, checkpoint transfer and commit barrier is a span carrying a
``trace_id`` of the form ``replica_id:step:quorum_epoch`` — because the
step counter and quorum epoch are *globally agreed* values, spans emitted
by different replicas for the same step/epoch correlate with no clock
sync beyond wall-clock timestamps. Context propagates between replicas
through RPC metadata (:meth:`Tracer.inject` / carrier dicts), so e.g. a
checkpoint GET served for a healing peer records the healer's span as
its parent.

Spans export two ways:

* JSONL (one span per line, ``TORCHFT_TRACE_PATH`` env or
  :meth:`Tracer.configure`) — grep/jq-friendly, merge-friendly;
* Chrome trace-event JSON (:meth:`Tracer.chrome_events` /
  :func:`chrome_trace`) — open in Perfetto (https://ui.perfetto.dev) or
  ``chrome://tracing``; the lighthouse's ``GET /trace`` serves the whole
  cluster merged on one timeline (replicas piggyback recent span batches
  on their quorum traffic — see ``docs/observability.md``).

Every span also lands in the profiler's trace: for its lifetime it holds a
``jax.profiler.TraceAnnotation`` named ``tft.<name>``, so a
``jax.profiler`` session (``telemetry/profiler.py::capture_jax_trace`` or
any other) shows the program's spans on the thread that ran them, on the
clock the device plane shares. The hot inner loop — per bucket, on the
collectives op thread — uses :func:`annotate`, the same annotation with no
ring entry. With no session open an annotation costs under a microsecond.

Program builds are spans too: ``telemetry/builds.py`` registers listeners
with ``jax.monitoring`` (the hook JAX itself offers) that open
``build.trace`` / ``build.lower`` / ``build.compile`` here, on the thread
that builds, around each outermost stage JAX reports.

Design constraints match the rest of the package: stdlib-only at import
(the annotation class is taken from an already-imported ``jax``, and is
skipped when there is none), exception-free on the hot path (a tracing bug
must never fail a step), and cheap when idle (span entry/exit is a couple
of dict ops).
"""

from __future__ import annotations

import contextlib
import json
import os
import sys
import threading
import time
import zlib
from collections import deque
from typing import Any, ContextManager, Deque, Dict, List, Optional

__all__ = [
    "Span",
    "Tracer",
    "TRACER",
    "annotate",
    "session_open",
    "chrome_trace",
    "ENV_TRACE_PATH",
    "TRACE_PREFIX",
]

ENV_TRACE_PATH = "TORCHFT_TRACE_PATH"
ENV_TRACE_RING = "TORCHFT_TRACE_RING"
# the program's spans in a profiler trace; keeps them apart from a
# harness's own wrapper spans
TRACE_PREFIX = "tft."

_NO_SPAN = contextlib.nullcontext()
# jax.profiler.TraceAnnotation.is_enabled, once jax is imported
_is_enabled = None


def _profiler_annotation(name: str, step_num: Optional[int], stats: Dict[str, Any]):
    """A ``jax.profiler`` annotation ``tft.<name>`` carrying the scalar
    ``stats`` (an annotation takes its stats at entry), or None when JAX was
    never imported. ``step_num`` makes it a ``StepTraceAnnotation``: the
    profiler's step tooling and the device plane's ``Steps`` line key on it."""
    profiler = getattr(sys.modules.get("jax"), "profiler", None)
    if profiler is None:
        return None
    scalars = {
        k: v for k, v in stats.items() if isinstance(v, (bool, int, float, str))
    }
    if step_num is not None:
        return profiler.StepTraceAnnotation(
            TRACE_PREFIX + name, step_num=int(step_num), **scalars
        )
    return profiler.TraceAnnotation(TRACE_PREFIX + name, **scalars)


def session_open() -> bool:
    """Whether a profiler session would record an annotation now; False
    when JAX was never imported. What :func:`annotate` asks first, so that a
    caller with many stats can ask before it gathers them."""
    global _is_enabled
    if _is_enabled is None:
        profiler = getattr(sys.modules.get("jax"), "profiler", None)
        if profiler is None:
            return False
        _is_enabled = profiler.TraceAnnotation.is_enabled
    return _is_enabled()


def annotate(name: str, **stats: Any) -> ContextManager[Any]:
    """The light span of the hot inner loop: ``tft.<name>`` in the
    profiler's trace with ``stats`` as the event's stats, and nothing else
    — no ring entry, no piggyback entry, no lock, no counter. A no-op when
    JAX was never imported, and while no profiler session is open."""
    if not session_open():
        return _NO_SPAN
    return _profiler_annotation(name, None, stats) or _NO_SPAN


def _ring_size() -> int:
    try:
        return max(16, int(os.environ.get(ENV_TRACE_RING, "4096")))
    except ValueError:
        return 4096


def _stable_pid(replica_id: str) -> int:
    """Deterministic Chrome-trace pid for a replica: the merged cluster
    trace groups each replica's spans into its own process lane even
    though the events were recorded on different hosts."""
    if not replica_id:
        return os.getpid()
    return zlib.crc32(replica_id.encode()) & 0x7FFFFFFF


class Span:
    """One recorded operation: name, trace identity, parent link, wall
    timestamps. Created via :meth:`Tracer.span`; attributes set inside the
    ``with`` block land in ``attrs``."""

    __slots__ = (
        "name",
        "trace_id",
        "span_id",
        "parent_id",
        "replica_id",
        "ts",
        "t0_mono_ns",
        "dur_s",
        "tid",
        "attrs",
    )

    def __init__(
        self,
        name: str,
        trace_id: str,
        span_id: str,
        parent_id: Optional[str],
        replica_id: str,
    ) -> None:
        self.name = name
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent_id = parent_id
        self.replica_id = replica_id
        # wall clock for the cross-replica /trace merge; CLOCK_MONOTONIC so
        # the ring/JSONL spans line up with other stamps of this host
        self.ts = time.time()
        self.t0_mono_ns = time.monotonic_ns()
        self.dur_s = 0.0
        self.tid = threading.get_ident() & 0x7FFFFFFF
        self.attrs: Dict[str, Any] = {}

    def set(self, **attrs: Any) -> "Span":
        self.attrs.update(attrs)
        return self

    def to_dict(self) -> Dict[str, Any]:
        d: Dict[str, Any] = {
            "name": self.name,
            "trace_id": self.trace_id,
            "span_id": self.span_id,
            "ts": self.ts,
            "t0_mono_ns": self.t0_mono_ns,
            "dur_s": round(self.dur_s, 6),
            "replica_id": self.replica_id,
            "tid": self.tid,
        }
        if self.parent_id:
            d["parent_id"] = self.parent_id
        if self.attrs:
            d["attrs"] = self.attrs
        return d

    def to_chrome(self) -> Dict[str, Any]:
        """Chrome trace-event ("X" complete event, microsecond clock)."""
        return _chrome_event(self.to_dict())


def _chrome_event(d: Dict[str, Any]) -> Dict[str, Any]:
    """One span dict -> one Chrome trace "X" event — the single place the
    event shape is defined (Span.to_chrome, chrome_events and the
    piggyback fragments all go through here)."""
    args = dict(d.get("attrs", {}))
    args["trace_id"] = d.get("trace_id", "")
    args["span_id"] = d.get("span_id", "")
    if d.get("parent_id"):
        args["parent_id"] = d["parent_id"]
    return {
        "name": d.get("name", "?"),
        "cat": "tft",
        "ph": "X",
        "ts": float(d.get("ts", 0.0)) * 1e6,
        "dur": max(float(d.get("dur_s", 0.0)), 0.0) * 1e6,
        "pid": _stable_pid(d.get("replica_id", "")),
        "tid": int(d.get("tid", 0)),
        "args": args,
    }


def _chrome_process_name(replica_id: str) -> Dict[str, Any]:
    """Metadata event naming a replica's process lane."""
    return {
        "name": "process_name",
        "ph": "M",
        "pid": _stable_pid(replica_id),
        "tid": 0,
        "args": {"name": replica_id},
    }


class _SpanCtx:
    """Context manager produced by :meth:`Tracer.span`."""

    def __init__(
        self, tracer: "Tracer", span: Span, step_num: Optional[int] = None
    ) -> None:
        self._tracer = tracer
        self.span = span
        self._step_num = step_num
        self._ann = None
        self._t0 = time.perf_counter()

    def __enter__(self) -> Span:
        self._tracer._push(self.span)
        try:
            self._ann = _profiler_annotation(
                self.span.name, self._step_num, self.span.attrs
            )
            if self._ann is not None:
                self._ann.__enter__()
        except Exception:  # noqa: BLE001 — tracing must never fail a step
            self._ann = None
        return self.span

    def set(self, **attrs: Any) -> None:
        """Attributes learned while the span is open (a build's ``cache``):
        on the span, and on its ``tft.<name>`` event in a profiler trace,
        which otherwise holds only what was known at entry."""
        self.span.attrs.update(attrs)
        if self._ann is not None:
            try:
                self._ann.set_metadata(**attrs)
            except Exception:  # noqa: BLE001 — tracing must never fail a step
                pass

    def __exit__(self, exc_type, exc, tb) -> None:
        if self._ann is not None:
            try:
                self._ann.__exit__(exc_type, exc, tb)
            except Exception:  # noqa: BLE001 — as above
                pass
        self.span.dur_s = time.perf_counter() - self._t0
        if exc is not None:
            self.span.attrs.setdefault("error", repr(exc))
        self._tracer._pop(self.span)
        self._tracer._record(self.span)
        return None  # never swallow exceptions


class Tracer:
    """Process-wide span recorder with carrier-based context propagation.

    The process context (``replica_id``, ``step``, ``quorum_epoch``) is set
    by the Manager at each step boundary; spans created without an explicit
    ``trace_id`` inherit it. Thread-local span stacks give implicit
    parent/child nesting; cross-process links use :meth:`inject` (producer)
    and the ``parent=`` carrier argument (consumer)."""

    def __init__(self, maxlen: Optional[int] = None) -> None:
        self._lock = threading.Lock()
        n = maxlen or _ring_size()
        self._ring: Deque[Dict[str, Any]] = deque(maxlen=n)
        # spans not yet shipped to the lighthouse (piggyback batches)
        self._pending: Deque[Span] = deque(maxlen=n)
        self._last_batch: List[Span] = []
        self._tls = threading.local()
        self._seq = 0
        self._file = None
        self._path: Optional[str] = None
        self._env_checked = False
        self._ctx: Dict[str, Any] = {
            "replica_id": "",
            "step": -1,
            "quorum_epoch": -1,
        }

    # -- context ---------------------------------------------------------

    def set_context(
        self,
        replica_id: Optional[str] = None,
        step: Optional[int] = None,
        quorum_epoch: Optional[int] = None,
    ) -> None:
        """Update the process trace context (Manager calls this at quorum
        start and whenever the epoch changes)."""
        with self._lock:
            if replica_id is not None:
                self._ctx["replica_id"] = replica_id
            if step is not None:
                self._ctx["step"] = int(step)
            if quorum_epoch is not None:
                self._ctx["quorum_epoch"] = int(quorum_epoch)

    def context(self) -> Dict[str, Any]:
        with self._lock:
            return dict(self._ctx)

    def current_trace_id(self) -> str:
        with self._lock:
            c = self._ctx
            return f"{c['replica_id']}:{c['step']}:{c['quorum_epoch']}"

    def _next_span_id(self) -> str:
        with self._lock:
            self._seq += 1
            return f"{os.getpid():x}-{self._seq:x}"

    # -- producing spans -------------------------------------------------

    def span(
        self,
        name: str,
        parent: Optional[Dict[str, Any]] = None,
        trace_id: Optional[str] = None,
        replica_id: Optional[str] = None,
        step_num: Optional[int] = None,
        **attrs: Any,
    ) -> _SpanCtx:
        """Open a span. ``parent`` is a carrier dict (from :meth:`inject`,
        possibly received over an RPC) that both links the parent span and
        adopts its trace_id; otherwise the innermost open span on this
        thread is the parent and the process context names the trace.
        ``step_num`` marks the span as the root of a training step: it is
        the step coordinate of the trace_id, and the profiler sees a
        ``StepTraceAnnotation``. Scalar ``attrs`` given here are the stats
        of the span's ``tft.<name>`` event in a profiler trace."""
        parent_id: Optional[str] = None
        if trace_id is None and step_num is not None:
            # a step's root opens before start_quorum moves the process
            # context on to that step
            c = self.context()
            trace_id = f"{c['replica_id']}:{int(step_num)}:{c['quorum_epoch']}"
        if parent:
            parent_id = parent.get("span_id") or None
            if trace_id is None:
                trace_id = parent.get("trace_id") or None
        if parent_id is None:
            cur = self._current()
            if cur is not None:
                parent_id = cur.span_id
                if trace_id is None:
                    trace_id = cur.trace_id
        if trace_id is None:
            trace_id = self.current_trace_id()
        if replica_id is None:
            replica_id = trace_id.split(":", 1)[0] or self.context()["replica_id"]
        s = Span(name, trace_id, self._next_span_id(), parent_id, replica_id)
        if attrs:
            s.attrs.update(attrs)
        return _SpanCtx(self, s, step_num)

    def inject(self) -> Dict[str, str]:
        """Carrier for RPC metadata: the current span (or bare context) as
        ``{"trace_id", "span_id"}`` — attach it to an outgoing request and
        pass it as ``parent=`` on the serving side."""
        cur = self._current()
        if cur is not None:
            return {"trace_id": cur.trace_id, "span_id": cur.span_id}
        return {"trace_id": self.current_trace_id(), "span_id": ""}

    @staticmethod
    def parse_carrier(raw: str) -> Optional[Dict[str, str]]:
        """Parse the ``trace_id|span_id`` header form used by the HTTP
        transports back into a carrier dict."""
        if not raw:
            return None
        trace_id, _, span_id = raw.partition("|")
        return {"trace_id": trace_id, "span_id": span_id}

    @staticmethod
    def format_carrier(carrier: Dict[str, str]) -> str:
        return f"{carrier.get('trace_id', '')}|{carrier.get('span_id', '')}"

    # -- thread-local stack ----------------------------------------------

    def _stack(self) -> List[Span]:
        st = getattr(self._tls, "stack", None)
        if st is None:
            st = []
            self._tls.stack = st
        return st

    def _current(self) -> Optional[Span]:
        st = self._stack()
        return st[-1] if st else None

    def _push(self, span: Span) -> None:
        self._stack().append(span)

    def _pop(self, span: Span) -> None:
        st = self._stack()
        if st and st[-1] is span:
            st.pop()
        elif span in st:  # tolerate mismatched exits
            st.remove(span)

    # -- recording -------------------------------------------------------

    def configure(self, path: Optional[str]) -> None:
        """Point the JSONL sink at ``path`` (append), or detach with None."""
        with self._lock:
            if self._file is not None:
                try:
                    self._file.close()
                except OSError:
                    pass
                self._file = None
            self._path = path
            self._env_checked = True
            if path:
                d = os.path.dirname(path)
                if d:
                    os.makedirs(d, exist_ok=True)
                self._file = open(path, "a", encoding="utf-8")

    def _maybe_open_from_env(self) -> None:
        # called under self._lock
        if self._env_checked:
            return
        self._env_checked = True
        path = os.environ.get(ENV_TRACE_PATH)
        if not path:
            return
        try:
            d = os.path.dirname(path)
            if d:
                os.makedirs(d, exist_ok=True)
            self._file = open(path, "a", encoding="utf-8")
            self._path = path
        except OSError:
            self._file = None
            self._path = None

    def _record(self, span: Span) -> None:
        try:
            d = span.to_dict()
            with self._lock:
                self._maybe_open_from_env()
                self._ring.append(d)
                self._pending.append(span)
                if self._file is not None:
                    try:
                        self._file.write(json.dumps(d, default=str) + "\n")
                        self._file.flush()
                    except (OSError, ValueError):
                        pass
            from torchft_tpu import telemetry

            telemetry.TRACE_SPANS.labels(span=span.name).inc()
        except Exception:  # noqa: BLE001 — tracing must never fail a step
            pass

    # -- consuming -------------------------------------------------------

    def recent(
        self, name: Optional[str] = None, limit: Optional[int] = None
    ) -> List[Dict[str, Any]]:
        """Most recent span dicts, oldest first, optionally by name."""
        with self._lock:
            spans = list(self._ring)
        if name is not None:
            spans = [s for s in spans if s.get("name") == name]
        if limit is not None:
            spans = spans[-limit:]
        return spans

    def drain_chrome_fragment(
        self, max_events: int = 64, max_bytes: int = 32768
    ) -> str:
        """Pop up-to-``max_events`` not-yet-shipped spans as a comma-joined
        Chrome trace-event fragment (no enclosing brackets) — the compact
        batch replicas piggyback on their quorum traffic. Includes a
        ``process_name`` metadata event per distinct replica so the merged
        timeline labels its lanes; duplicates across batches are harmless."""
        spans: List[Span] = []
        with self._lock:
            while self._pending and len(spans) < max_events:
                spans.append(self._pending.popleft())
        if not spans:
            return ""
        parts: List[str] = []
        named: set = set()
        total = 0
        consumed = 0
        for s in spans:
            try:
                frag = json.dumps(s.to_chrome(), separators=(",", ":"), default=str)
            except (TypeError, ValueError):
                consumed += 1
                continue  # unserializable span: drop it, keep draining
            if total + len(frag) > max_bytes and parts:
                break  # over budget: later spans stay pending (below)
            if s.replica_id and s.replica_id not in named:
                named.add(s.replica_id)
                parts.append(
                    json.dumps(
                        _chrome_process_name(s.replica_id),
                        separators=(",", ":"),
                    )
                )
            total += len(frag)
            parts.append(frag)
            consumed += 1
        if consumed < len(spans):
            # push the unshipped tail back (in order) for the next batch —
            # busy incident windows must not lose their spans to the cap
            with self._lock:
                for s in reversed(spans[consumed:]):
                    self._pending.appendleft(s)
        self._last_batch = spans[:consumed]
        return ",".join(parts)

    def requeue_last_batch(self) -> None:
        """Re-queue the spans returned by the most recent
        :meth:`drain_chrome_fragment` (callers that failed to ship a
        piggyback batch use this so an outage window keeps its spans; a
        rare double-requeue only duplicates events, which the merged
        trace tolerates)."""
        with self._lock:
            batch = getattr(self, "_last_batch", None)
            self._last_batch = []
            if batch:
                for s in reversed(batch):
                    self._pending.appendleft(s)

    def chrome_events(
        self, spans: Optional[List[Dict[str, Any]]] = None
    ) -> List[Dict[str, Any]]:
        """Chrome trace events for ``spans`` (default: the recent ring),
        with a ``process_name`` metadata event per replica."""
        if spans is None:
            spans = self.recent()
        out: List[Dict[str, Any]] = []
        named: set = set()
        for d in spans:
            rid = d.get("replica_id", "")
            if rid and rid not in named:
                named.add(rid)
                out.append(_chrome_process_name(rid))
            out.append(_chrome_event(d))
        return out

    def clear(self) -> None:
        """Empty the ring and pending batches (tests)."""
        with self._lock:
            self._ring.clear()
            self._pending.clear()

    def close(self) -> None:
        with self._lock:
            if self._file is not None:
                try:
                    self._file.close()
                except OSError:
                    pass
                self._file = None


TRACER = Tracer()


def chrome_trace(path: str, spans: Optional[List[Dict[str, Any]]] = None) -> str:
    """Write the recent spans (or ``spans``) as a Chrome trace-event JSON
    file loadable in Perfetto; returns the path."""
    events = TRACER.chrome_events(spans)
    with open(path, "w", encoding="utf-8") as f:
        json.dump({"displayTimeUnit": "ms", "traceEvents": events}, f)
    return path


def read_spans(path: str) -> List[Dict[str, Any]]:
    """Parse a JSONL span file back into dicts (skips torn tails)."""
    out: List[Dict[str, Any]] = []
    try:
        with open(path, encoding="utf-8") as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    out.append(json.loads(line))
                except ValueError:
                    continue
    except FileNotFoundError:
        pass
    return out
