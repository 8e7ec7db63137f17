"""Project-specific concurrency lint over the FT runtime modules.

The Manager runs a quorum long-poll thread, a step watchdog and
death-watch/evict threads — thread discipline there is load-bearing for
the paper's per-step recovery claim, and the remaining ROADMAP corruption
item is exactly the bug class that races produce. torchft's Rust core gets this from the compiler; this AST
lint is the Python analogue: the threading contract becomes checkable
rules instead of prose.

Rules (ids are the suppression-key prefix):

``lock-order-cycle``
    A cycle in the lock-order graph extracted from nested ``with <lock>``
    scopes (including one level of same-file call propagation) — a
    lock-order inversion that can deadlock under the right interleaving.

``blocking-under-lock``
    A blocking call (socket IO, RPC ``.call``, ``time.sleep``,
    ``Future.wait``/``result``, thread ``join`` …) while holding a
    ``Lock``/``Condition``. ``cond.wait()`` on the *held* condition is
    exempt (it releases the lock). Documented-intentional cases (e.g. a
    dedicated per-socket send lock) are suppressed in the baseline with a
    reason.

``callback-under-lock``
    ``Future.set_result``/``set_exception`` invoked while holding a lock:
    continuations (``then`` chains, flight-recorder completions, user
    callbacks) run inline on the resolving thread, so they execute UNDER
    the held lock — a continuation that re-enters the owning object
    deadlocks. Resolve futures after releasing the lock.

``unguarded-shared-write`` / ``guard-not-held``
    A ``self.<attr>`` mutated from more than one thread entry point must
    carry a ``# guarded-by: <lock>`` annotation on its ``__init__``
    assignment (or ``# unguarded-ok: <reason>`` when a happens-before
    hand-off — not a lock — is the synchronizer; say which). With a
    ``guarded-by``, every mutation site must sit lexically under
    ``with self.<lock>``.

``cond-wait-no-loop``
    A ``Condition.wait()`` not wrapped in an enclosing ``while`` predicate
    loop (``wait_for`` is fine) — wakeups are allowed to be spurious.

``thread-unnamed`` / ``thread-not-daemon-or-joined``
    Every ``threading.Thread`` must be named (hang forensics — ``py-spy``
    dumps and flight-recorder triage key on thread names) and must be a
    daemon or explicitly joined.

Annotation grammar (trailing comment on the attribute's first assignment,
normally in ``__init__``; the continuation line below also counts)::

    self._step = 0          # guarded-by: _commit_mu
    self._healing = False   # unguarded-ok: quorum-thread handoff via
                            #   the wait_quorum() barrier

The annotation names the lock attribute without ``self.`` and applies
file-wide to that attribute name within its class.
"""

from __future__ import annotations

import ast
import os
import re
from typing import Dict, List, Optional, Set, Tuple

from torchft_tpu.analysis.base import Finding, repo_root

__all__ = ["RUNTIME_MODULES", "analyze_source", "analyze_paths", "run"]

# The modules whose threading contract this lint enforces: the ISSUE 5
# list plus every thread-spawning module landed since (ISSUE 15 — the
# diagnosis/profiler/SLO/time-series monitors and the black box all run
# worker threads against Manager-visible state).
RUNTIME_MODULES = (
    "torchft_tpu/manager.py",
    "torchft_tpu/futures.py",
    "torchft_tpu/collectives.py",
    "torchft_tpu/collectives_device.py",
    "torchft_tpu/proxy.py",
    "torchft_tpu/telemetry/flight.py",
    "torchft_tpu/checkpointing/_rwlock.py",
    "torchft_tpu/faultinject/core.py",
    "torchft_tpu/telemetry/diagnosis.py",
    "torchft_tpu/telemetry/profiler.py",
    "torchft_tpu/telemetry/slo.py",
    "torchft_tpu/telemetry/timeseries.py",
    "torchft_tpu/telemetry/blackbox.py",
)

_LOCK_FACTORIES = {"Lock", "RLock", "Condition", "Semaphore", "BoundedSemaphore"}
_CONDITION_FACTORIES = {"Condition"}

# Attribute-call names considered blocking. Deliberately conservative:
# queue/dict get/put are ambiguous at the AST level and excluded; helper
# functions containing a direct blocking call are propagated one level so
# ``with lock: self._helper()`` is still caught.
_BLOCKING_ATTRS = {
    "sleep",              # time.sleep
    "sendall", "recv", "recv_into", "accept", "connect",
    "create_connection",  # socket IO
    "result",             # concurrent.futures / chained futures
    "wait",               # Future.wait / Event.wait / foreign cond.wait
    "join",               # thread join (str/path join excluded below)
    "call",               # NativeClient RPC
    "select",
}

# Future-resolution calls that run arbitrary continuations inline.
_CALLBACK_ATTRS = {"set_result", "set_exception"}

_GUARDED_BY_RE = re.compile(r"#\s*guarded-by:\s*([A-Za-z_][A-Za-z0-9_]*)")
_UNGUARDED_OK_RE = re.compile(r"#\s*unguarded-ok:")

# Mutating method calls on a self attribute that count as writes.
_MUTATORS = {
    "add", "append", "appendleft", "clear", "discard", "extend",
    "insert", "pop", "popleft", "remove", "update", "setdefault",
}


def _expr_id(node: ast.AST) -> str:
    """Stable textual identity for a lock expression: ``self._x`` stays
    qualified; ``p.cond`` becomes ``*.cond`` (instance-agnostic); a bare
    name stays itself."""
    if isinstance(node, ast.Attribute):
        if isinstance(node.value, ast.Name) and node.value.id == "self":
            return f"self.{node.attr}"
        return f"*.{node.attr}"
    if isinstance(node, ast.Name):
        return node.id
    return ast.dump(node)


class _FuncInfo:
    __slots__ = ("qualname", "node", "acquires", "blocks", "resolves", "calls")

    def __init__(self, qualname: str, node: ast.AST) -> None:
        self.qualname = qualname
        self.node = node
        self.acquires: List[Tuple[str, int]] = []  # (lock id, line)
        self.blocks = False     # body makes a direct blocking call
        self.resolves = False   # body resolves a future directly
        self.calls: List[str] = []


class _FileAnalysis:
    def __init__(self, path: str, source: str) -> None:
        self.path = path
        self.source = source
        self.lines = source.splitlines()
        self.tree = ast.parse(source)
        self.findings: List[Finding] = []
        self.lock_attrs: Dict[str, str] = {}    # attr name -> kind
        self.module_locks: Dict[str, str] = {}  # module global -> kind
        self.funcs: Dict[str, _FuncInfo] = {}
        # class -> attr -> {(func qualname, line, lock held?)}
        self.writes: Dict[str, Dict[str, Set[Tuple[str, int, bool]]]] = {}
        # class -> attr -> (decl line, guarded-by lock, unguarded-ok?)
        self.attr_decl: Dict[str, Dict[str, Tuple[int, Optional[str], bool]]] = {}
        self.worker_entries: Dict[str, Set[str]] = {}  # class -> short names
        self.classes: List[str] = []
        # method short name -> qualnames defining it (for *.m() resolution)
        self.method_index: Dict[str, List[str]] = {}
        self._inside_while: Dict[int, bool] = {}

    # ------------------------------------------------------------------
    # pass 0: locks + parent/while map
    # ------------------------------------------------------------------

    def _lock_kind(self, call: ast.Call) -> Optional[str]:
        fn = call.func
        name = fn.attr if isinstance(fn, ast.Attribute) else (
            fn.id if isinstance(fn, ast.Name) else None
        )
        if name in _CONDITION_FACTORIES:
            return "condition"
        if name in _LOCK_FACTORIES:
            return "lock"
        return None

    def prescan(self) -> None:
        def mark(node: ast.AST, inside: bool) -> None:
            self._inside_while[id(node)] = inside
            for child in ast.iter_child_nodes(node):
                mark(child, inside or isinstance(node, ast.While))

        mark(self.tree, False)
        for node in ast.walk(self.tree):
            if isinstance(node, ast.Assign) and isinstance(node.value, ast.Call):
                kind = self._lock_kind(node.value)
                if not kind:
                    continue
                for t in node.targets:
                    if isinstance(t, ast.Name):
                        self.module_locks[t.id] = kind
                    elif (
                        isinstance(t, ast.Attribute)
                        and isinstance(t.value, ast.Name)
                        and t.value.id == "self"
                    ):
                        self.lock_attrs[t.attr] = kind

    # ------------------------------------------------------------------
    # pass 1: per-function walk
    # ------------------------------------------------------------------

    def collect(self) -> None:
        # register every method/function FIRST so calls to later-defined
        # methods resolve (collection order must not matter), then walk
        self._register(self.tree.body, cls=None)
        for node in self.tree.body:
            if isinstance(node, ast.ClassDef):
                self._collect_class(node)
            elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                self._walk(node, self.funcs[node.name], None, [])

    def _register(self, body, cls: Optional[str]) -> None:
        for node in body:
            if isinstance(node, ast.ClassDef):
                self.classes.append(node.name)
                self.writes.setdefault(node.name, {})
                self.attr_decl.setdefault(node.name, {})
                self.worker_entries.setdefault(node.name, set())
                self._register(node.body, cls=node.name)
            elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                q = f"{cls}.{node.name}" if cls else node.name
                self.funcs[q] = _FuncInfo(q, node)
                if cls:
                    self.method_index.setdefault(node.name, []).append(q)

    def _collect_class(self, cls: ast.ClassDef) -> None:
        for item in cls.body:
            if isinstance(item, (ast.FunctionDef, ast.AsyncFunctionDef)):
                q = f"{cls.name}.{item.name}"
                self._walk(item, self.funcs[q], cls.name, [])
            elif isinstance(item, ast.ClassDef):
                self._collect_class(item)

    def _collect_func(self, qualname: str, fn: ast.AST, cls: Optional[str]) -> None:
        info = _FuncInfo(qualname, fn)
        self.funcs[qualname] = info
        self._walk(fn, info, cls, lock_stack=[])

    def _resolve_lock(self, expr: ast.AST) -> Optional[str]:
        lid = _expr_id(expr)
        if "." in lid:
            attr = lid.split(".", 1)[1]
            return lid if attr in self.lock_attrs else None
        return lid if lid in self.module_locks else None

    def _lock_obj_kind(self, lid: str) -> Optional[str]:
        if "." in lid:
            return self.lock_attrs.get(lid.split(".", 1)[1])
        return self.module_locks.get(lid)

    def _annotation_for_line(self, lineno: int) -> Tuple[Optional[str], bool]:
        """Annotation for the declaration at ``lineno``: the line's own
        trailing comment, or the contiguous block of comment lines
        directly ABOVE it (multi-line reasons read best as a leading
        comment). A leading block annotates only the statement
        immediately below it."""
        candidates = []
        if lineno - 1 < len(self.lines):
            candidates.append(self.lines[lineno - 1])
        i = lineno - 2
        while i >= 0 and self.lines[i].strip().startswith("#"):
            candidates.append(self.lines[i])
            i -= 1
        for text in candidates:
            m = _GUARDED_BY_RE.search(text)
            if m:
                return m.group(1), False
            if _UNGUARDED_OK_RE.search(text):
                return None, True
        return None, False

    def _record_write(
        self, cls: Optional[str], attr: str, func: _FuncInfo, lineno: int,
        lock_stack: List[str],
    ) -> None:
        if cls is None:
            return
        self.writes.setdefault(cls, {}).setdefault(attr, set()).add(
            (func.qualname, lineno, bool(lock_stack))
        )
        decl = self.attr_decl.setdefault(cls, {})
        in_init = func.qualname.endswith(".__init__")
        prev = decl.get(attr)
        if prev is None or (in_init and prev[1] is None and not prev[2]):
            guard, ok = self._annotation_for_line(lineno)
            if prev is None or guard is not None or ok:
                decl[attr] = (lineno, guard, ok)

    def _assign_targets(self, node) -> List[ast.AST]:
        targets = (
            node.targets if isinstance(node, ast.Assign) else [node.target]
        )
        flat: List[ast.AST] = []
        for t in targets:
            if isinstance(t, (ast.Tuple, ast.List)):
                flat.extend(t.elts)
            else:
                flat.append(t)
        return flat

    def _walk(
        self, node: ast.AST, func: _FuncInfo, cls: Optional[str],
        lock_stack: List[str],
    ) -> None:
        for child in ast.iter_child_nodes(node):
            self._visit(child, func, cls, lock_stack)

    def _visit(
        self, child: ast.AST, func: _FuncInfo, cls: Optional[str],
        lock_stack: List[str],
    ) -> None:
        # every statement/expr node flows through here exactly once —
        # including a With that is itself a With-body statement (walking
        # only children would skip directly-nested `with a: with b:`,
        # losing exactly the edges the lock-order rule exists for)
        if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
            # nested def: executes later, in its own context — locks
            # held at the definition site do not surround its body
            self._collect_func(f"{func.qualname}.{child.name}", child, cls)
            return
        if isinstance(child, ast.Lambda):
            inner = _FuncInfo(f"{func.qualname}.<lambda>", child)
            self.funcs.setdefault(inner.qualname, inner)
            self._walk(child.body, inner, cls, [])
            return
        if isinstance(child, ast.With):
            held = [
                lid for item in child.items
                if (lid := self._resolve_lock(item.context_expr)) is not None
            ]
            for lid in held:
                func.acquires.append((lid, child.lineno))
            new_stack = lock_stack + held
            for body_item in child.body:
                self._visit(body_item, func, cls, new_stack)
            return
        if isinstance(child, ast.Call):
            self._handle_call(child, func, cls, lock_stack)
        elif isinstance(child, (ast.Assign, ast.AugAssign, ast.AnnAssign)):
            if isinstance(child, ast.AnnAssign) and child.value is None:
                self._walk(child, func, cls, lock_stack)
                return
            for t in self._assign_targets(child):
                if (
                    isinstance(t, ast.Attribute)
                    and isinstance(t.value, ast.Name)
                    and t.value.id == "self"
                ):
                    self._record_write(cls, t.attr, func, child.lineno, lock_stack)
                elif (
                    isinstance(t, ast.Subscript)
                    and isinstance(t.value, ast.Attribute)
                    and isinstance(t.value.value, ast.Name)
                    and t.value.value.id == "self"
                ):
                    self._record_write(
                        cls, t.value.attr, func, child.lineno, lock_stack
                    )
        self._walk(child, func, cls, lock_stack)

    def _callee_name(self, fn: ast.AST, cls: Optional[str]) -> Optional[str]:
        """Resolve a call target to a same-file function qualname (best
        effort): bare names, ``self.m``, and ``x.m`` when exactly one
        class in this file defines ``m``."""
        if isinstance(fn, ast.Name):
            return fn.id if fn.id in self.funcs else None
        if isinstance(fn, ast.Attribute):
            if isinstance(fn.value, ast.Name) and fn.value.id == "self" and cls:
                q = f"{cls}.{fn.attr}"
                return q if q in self.funcs else None
            # x.m(): resolvable when exactly one class in this file defines
            # m — except for generic verb names (wait/join/...) where the
            # direct blocking check is authoritative and a unique-method
            # match would be coincidence (p.cond.wait is not Work.wait)
            if fn.attr not in _BLOCKING_ATTRS:
                owners = self.method_index.get(fn.attr, [])
                if len(owners) == 1:
                    return owners[0]
        return None

    def _handle_call(
        self, call: ast.Call, func: _FuncInfo, cls: Optional[str],
        lock_stack: List[str],
    ) -> None:
        fn = call.func
        self._thread_rule(call, func)
        self._worker_entry_targets(call, cls)
        # mutating method on a self attribute counts as a write
        if (
            isinstance(fn, ast.Attribute)
            and fn.attr in _MUTATORS
            and isinstance(fn.value, ast.Attribute)
            and isinstance(fn.value.value, ast.Name)
            and fn.value.value.id == "self"
        ):
            self._record_write(cls, fn.value.attr, func, call.lineno, lock_stack)
        callee = self._callee_name(fn, cls)
        if callee is not None:
            func.calls.append(callee)
        label = self._blocking_label(call, lock_stack)
        if label is not None:
            func.blocks = True
            if lock_stack:
                self.findings.append(Finding(
                    "blocking-under-lock", self.path, call.lineno,
                    f"{func.qualname}:{label}",
                    f"blocking call {label} while holding "
                    f"{'+'.join(lock_stack)} — every thread contending "
                    "that lock now waits out the slow path too",
                ))
        if isinstance(fn, ast.Attribute) and fn.attr in _CALLBACK_ATTRS:
            func.resolves = True
            if lock_stack:
                self.findings.append(Finding(
                    "callback-under-lock", self.path, call.lineno,
                    f"{func.qualname}:{_expr_id(fn.value)}.{fn.attr}",
                    f"future resolved ({fn.attr}) while holding "
                    f"{'+'.join(lock_stack)} — continuations run inline "
                    "under the lock; a callback that re-enters the owner "
                    "deadlocks",
                ))
        # cond-wait predicate-loop rule
        if isinstance(fn, ast.Attribute) and fn.attr == "wait":
            lid = _expr_id(fn.value)
            if self._lock_obj_kind(lid) == "condition":
                if not self._inside_while.get(id(call), False):
                    self.findings.append(Finding(
                        "cond-wait-no-loop", self.path, call.lineno,
                        f"{func.qualname}:{lid}",
                        "Condition.wait() outside a while predicate loop — "
                        "wakeups may be spurious; re-check the predicate "
                        "in a loop (or use wait_for)",
                    ))

    def _blocking_label(
        self, call: ast.Call, lock_stack: List[str]
    ) -> Optional[str]:
        fn = call.func
        if isinstance(fn, ast.Name):
            return fn.id if fn.id == "sleep" else None
        if not isinstance(fn, ast.Attribute) or fn.attr not in _BLOCKING_ATTRS:
            return None
        name = fn.attr
        if name == "join":
            v = fn.value
            if isinstance(v, (ast.Constant, ast.JoinedStr)):
                return None  # "sep".join(...)
            if isinstance(v, ast.Attribute) and v.attr == "path":
                return None  # os.path.join
            if isinstance(v, ast.Name) and v.id in ("os", "posixpath", "ntpath"):
                return None
        if name == "wait":
            # cond.wait() on the HELD condition releases it — canonical
            if _expr_id(fn.value) in lock_stack:
                return None
        return f"{_expr_id(fn.value)}.{name}"

    def _thread_rule(self, call: ast.Call, func: _FuncInfo) -> None:
        fn = call.func
        is_thread = (isinstance(fn, ast.Attribute) and fn.attr == "Thread") or (
            isinstance(fn, ast.Name) and fn.id == "Thread"
        )
        if not is_thread:
            return
        kw = {k.arg: k.value for k in call.keywords if k.arg}
        if "target" not in kw and not call.args:
            return  # bare Thread() subclass/typing use
        if "name" not in kw:
            self.findings.append(Finding(
                "thread-unnamed", self.path, call.lineno, func.qualname,
                "threading.Thread without name= — unnamed threads make "
                "py-spy / flight-recorder triage of a wedged step guesswork",
            ))
        daemon = kw.get("daemon")
        is_daemon = isinstance(daemon, ast.Constant) and daemon.value is True
        if not is_daemon and ".join(" not in self.source:
            self.findings.append(Finding(
                "thread-not-daemon-or-joined", self.path, call.lineno,
                func.qualname,
                "thread is neither daemon=True nor joined anywhere in this "
                "file — it can outlive shutdown and touch freed state",
            ))

    def _worker_entry_targets(self, call: ast.Call, cls: Optional[str]) -> None:
        """A bound ``self.<method>`` (or local def) handed away as a call
        argument — Thread target, executor.submit fn, ``then`` callback,
        death-watch registration — marks that function as a worker-context
        entry point for the class. Non-function attributes picked up by
        this heuristic are inert (they never appear in the call graph)."""
        if cls is None:
            return
        cands: List[ast.AST] = list(call.args) + [
            k.value for k in call.keywords if k.arg
        ]
        for c in cands:
            if (
                isinstance(c, ast.Attribute)
                and isinstance(c.value, ast.Name)
                and c.value.id == "self"
            ):
                self.worker_entries.setdefault(cls, set()).add(c.attr)
            elif isinstance(c, ast.Name) and any(
                q == c.id  # module-level function
                # nested def (Class.method.inner); a bare Name can never
                # reference a bound method, so 2-segment names (which a
                # local variable shadowing the method name would match)
                # are excluded
                or (q.count(".") >= 2 and q.endswith(f".{c.id}"))
                for q in self.funcs
            ):
                self.worker_entries.setdefault(cls, set()).add(c.id)

    # ------------------------------------------------------------------
    # pass 2: propagation + graph rules
    # ------------------------------------------------------------------

    def propagate_under_lock(self) -> None:
        """One level: calling a same-file function that blocks (or
        resolves futures) while holding a lock is itself a finding."""
        blocking = {q for q, i in self.funcs.items() if i.blocks}
        resolving = {q for q, i in self.funcs.items() if i.resolves}
        for q, info in self.funcs.items():
            self._prop_walk(info.node, info, [], blocking, resolving)

    def _prop_walk(self, node, func, lock_stack, blocking, resolving) -> None:
        for child in ast.iter_child_nodes(node):
            self._prop_visit(child, func, lock_stack, blocking, resolving)

    def _prop_visit(self, child, func, lock_stack, blocking, resolving) -> None:
        cls = func.qualname.split(".")[0] if "." in func.qualname else None
        if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
            return
        if isinstance(child, ast.With):
            held = [
                lid for item in child.items
                if (lid := self._resolve_lock(item.context_expr)) is not None
            ]
            new_stack = lock_stack + held
            for body_item in child.body:
                self._prop_visit(body_item, func, new_stack, blocking, resolving)
            return
        if isinstance(child, ast.Call) and lock_stack:
            callee = self._callee_name(child.func, cls)
            if callee in blocking:
                self.findings.append(Finding(
                    "blocking-under-lock", self.path, child.lineno,
                    f"{func.qualname}:{callee}()",
                    f"call to {callee}() (which blocks) while holding "
                    f"{'+'.join(lock_stack)}",
                ))
            if callee in resolving:
                self.findings.append(Finding(
                    "callback-under-lock", self.path, child.lineno,
                    f"{func.qualname}:{callee}()",
                    f"call to {callee}() (which resolves futures, "
                    "running continuations inline) while holding "
                    f"{'+'.join(lock_stack)}",
                ))
        self._prop_walk(child, func, lock_stack, blocking, resolving)

    def lock_order_rule(self) -> None:
        edges: Dict[str, Set[str]] = {}
        sites: Dict[Tuple[str, str], Tuple[str, int]] = {}

        def add_edge(a: str, b: str, where: str, line: int) -> None:
            if a != b:
                edges.setdefault(a, set()).add(b)
                sites.setdefault((a, b), (where, line))

        acq_by_func = {q: i.acquires for q, i in self.funcs.items()}
        for q, info in self.funcs.items():
            self._edge_walk(info.node, q, [], acq_by_func, add_edge)

        color: Dict[str, int] = {}
        stack: List[str] = []

        def dfs(n: str) -> Optional[List[str]]:
            color[n] = 1
            stack.append(n)
            for m in sorted(edges.get(n, ())):
                if color.get(m, 0) == 1:
                    return stack[stack.index(m):] + [m]
                if color.get(m, 0) == 0:
                    cyc = dfs(m)
                    if cyc:
                        return cyc
            stack.pop()
            color[n] = 2
            return None

        for n in sorted(edges):
            if color.get(n, 0) == 0:
                cyc = dfs(n)
                if cyc:
                    pairs = [p for p in zip(cyc, cyc[1:]) if p in sites]
                    where = "; ".join(
                        f"{a}->{b} at {sites[(a, b)][0]}:{sites[(a, b)][1]}"
                        for a, b in pairs
                    )
                    line = sites[pairs[0]][1] if pairs else 0
                    self.findings.append(Finding(
                        "lock-order-cycle", self.path, line, "->".join(cyc),
                        f"lock-order inversion: {' -> '.join(cyc)} ({where})"
                        " — two threads taking these locks in opposing "
                        "order deadlock",
                    ))
                    return  # one cycle report per file is plenty

    def _edge_walk(self, node, q, lock_stack, acq_by_func, add_edge) -> None:
        for child in ast.iter_child_nodes(node):
            self._edge_visit(child, q, lock_stack, acq_by_func, add_edge)

    def _edge_visit(self, child, q, lock_stack, acq_by_func, add_edge) -> None:
        cls = q.split(".")[0] if "." in q else None
        if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
            return
        if isinstance(child, ast.With):
            held = [
                lid for item in child.items
                if (lid := self._resolve_lock(item.context_expr)) is not None
            ]
            for lid in held:
                for h in lock_stack:
                    add_edge(h, lid, q, child.lineno)
            new_stack = lock_stack + held
            for body_item in child.body:
                self._edge_visit(body_item, q, new_stack, acq_by_func, add_edge)
            return
        if isinstance(child, ast.Call) and lock_stack:
            callee = self._callee_name(child.func, cls)
            if callee in acq_by_func:
                for lid, _line in acq_by_func[callee]:
                    for h in lock_stack:
                        add_edge(h, lid, q, child.lineno)
        self._edge_walk(child, q, lock_stack, acq_by_func, add_edge)

    def shared_state_rule(self) -> None:
        for cls in self.classes:
            entries = self.worker_entries.get(cls, set())
            if not entries:
                continue
            graph: Dict[str, Set[str]] = {}
            for q, info in self.funcs.items():
                if not q.startswith(f"{cls}."):
                    continue
                short = q[len(cls) + 1:]
                graph[short] = {
                    c[len(cls) + 1:].split(".")[0]
                    for c in info.calls
                    if c.startswith(f"{cls}.")
                }

            def reach(start: str) -> Set[str]:
                seen = {start}
                frontier = [start]
                while frontier:
                    cur = frontier.pop()
                    for nxt in graph.get(cur, ()):
                        if nxt not in seen:
                            seen.add(nxt)
                            frontier.append(nxt)
                return seen

            worker_reach = {
                e: reach(e) for e in entries
                if e in graph and not e.startswith("__")
            }

            for attr, sites in self.writes.get(cls, {}).items():
                contexts: Set[str] = set()
                unheld: List[Tuple[str, int]] = []
                for qual, line, held in sites:
                    short = (
                        qual[len(cls) + 1:]
                        if qual.startswith(f"{cls}.") else qual
                    )
                    if short.endswith("__init__"):
                        continue  # construction happens-before thread start
                    base = short.split(".")[0]
                    leaf = short.split(".")[-1]
                    ctx = "main"
                    for entry, reached in worker_reach.items():
                        if base == entry or base in reached:
                            ctx = f"worker:{entry}"
                            break
                    if ctx == "main" and leaf != base and leaf in entries:
                        # nested def handed away as a callback/thread target
                        ctx = f"worker:{short}"
                    contexts.add(ctx)
                    if not held:
                        unheld.append((short, line))
                if len(contexts) < 2:
                    continue
                decl = self.attr_decl.get(cls, {}).get(attr)
                line0, guard, unguarded_ok = decl if decl else (0, None, False)
                if unguarded_ok:
                    continue
                if guard is None:
                    self.findings.append(Finding(
                        "unguarded-shared-write", self.path, line0,
                        f"{cls}.{attr}",
                        f"mutated from {len(contexts)} thread contexts "
                        f"({', '.join(sorted(contexts))}) with no "
                        "'# guarded-by: <lock>' (or '# unguarded-ok: "
                        "<reason>') annotation on its declaration",
                    ))
                    continue
                for short, line in unheld:
                    self.findings.append(Finding(
                        "guard-not-held", self.path, line,
                        f"{cls}.{attr}@{short}",
                        f"declared '# guarded-by: {guard}' but this write "
                        f"is not under 'with self.{guard}'",
                    ))


def analyze_source(path: str, source: str) -> List[Finding]:
    fa = _FileAnalysis(path, source)
    fa.prescan()
    fa.collect()
    fa.propagate_under_lock()
    fa.lock_order_rule()
    fa.shared_state_rule()
    # dedupe (propagation can re-derive a direct finding) + stable order
    seen: Set[Tuple] = set()
    out: List[Finding] = []
    for f in sorted(
        fa.findings, key=lambda f: (f.path, f.line, f.rule, f.symbol)
    ):
        k = (f.rule, f.path, f.line, f.symbol)
        if k not in seen:
            seen.add(k)
            out.append(f)
    return out


def analyze_paths(paths: List[str], root: Optional[str] = None) -> List[Finding]:
    root = root or repo_root()
    out: List[Finding] = []
    for rel in paths:
        with open(os.path.join(root, rel), encoding="utf-8") as f:
            out.extend(analyze_source(rel, f.read()))
    return out


def run(root: Optional[str] = None) -> List[Finding]:
    """Analyze the runtime module set (the repo gate)."""
    return analyze_paths(list(RUNTIME_MODULES), root=root)
