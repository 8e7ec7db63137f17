"""Cross-language wire/protocol drift checker.

The C++ coordination core and the Python client speak a hand-rolled TLV
codec plus a small RPC vocabulary; nothing but convention keeps the two
sides in sync, and a silent mismatch is exactly how torn-frame bugs are
born (a tag decoded as a length, an opcode answered by nobody, an env
knob parsed on one side only). This module scrapes both sides with
regexes — no clang, no compile — and errors on any constant that exists
on one side only.

Checks (rule ids):

``wire-tag-drift``
    ``native/wire.h`` ``Value::Type`` enum vs ``utils/wire.py`` ``_I64``…
    constants: same names, same values, both directions.

``status-code-drift``
    ``native/wire.h`` ``Status`` enum vs ``_native/__init__.py`` status
    constants vs the ``.pyi`` stub.

``rpc-method-drift``
    Every ``"mgr.*" / "lh.*" / "store.*"`` method the Python side calls
    must have a native dispatch arm; every native dispatch arm must have a
    caller (Python or native-internal). A dead handler is drift waiting
    to diverge.

``fi-env-drift``
    The ``TORCHFT_FI_*`` family: knobs parsed by the native plane vs
    knobs documented in ``docs/fault_injection.md`` (exact match) and
    knobs referenced from Python (must be a subset of the parsed set —
    a scenario driving an unparsed knob silently no-ops).

``wire-env-drift``
    The ``TORCHFT_WIRE_*`` family (the wire-plane knob registry): knobs
    referenced anywhere in the Python tree vs the knob table in
    ``docs/wire_plane.md``, both directions — an undocumented knob is
    invisible to operators, a documented-but-unparsed knob silently
    no-ops in deploy configs.

``obs-env-drift``
    Same contract for the step-anatomy/SLO/straggler/forensics/
    divergence/time-series knob families (``TORCHFT_SLO_*`` /
    ``TORCHFT_STRAGGLER_*`` / ``TORCHFT_BLACKBOX_*`` /
    ``TORCHFT_DIVERGENCE_*`` / ``TORCHFT_TSDB_*``) against the knob
    registry in ``docs/observability.md``.

``heal-env-drift``
    Same contract for the heal-plane knob family (``TORCHFT_HEAL_*``)
    against the knob registry in ``docs/heal_plane.md``, both
    directions.

``fault-site-drift``
    Native evidence-record site labels (``fi::write_evidence`` /
    ``fi::kill_self`` call sites) vs ``faultinject.core.NATIVE_SITES``:
    conftest's injection-evidence check and the scenario runner consume
    these labels, so an unlisted label breaks death attribution.

``stub-drift``
    Public names in ``_native/__init__.py`` vs ``_native/__init__.pyi``:
    the typed surface must cover the real one, both directions.

``makefile-hdrs-drift``
    Every ``native/*.h`` must appear in ``native/Makefile``'s ``HDRS``
    prerequisite list (and every HDRS entry must exist): a header
    missing from HDRS means its edits do not rebuild the ``.so`` — the
    stale-library class that shipped twice (tsdb.h, profiler.h).
"""

from __future__ import annotations

import os
import re
from typing import Dict, List, Optional, Set

from torchft_tpu.analysis.base import Finding, repo_root

__all__ = ["run", "scrape_cpp_enum", "scrape_py_constants"]

_NATIVE_SOURCES = ("wire.h", "rpc.h", "coord.h", "dataplane.h",
                   "faultinject.h", "stripe.h", "blob.h", "rpc.cc",
                   "coord.cc", "dataplane.cc", "blob.cc", "capi.cc",
                   "lighthouse_main.cc")

_PY_RPC_SOURCES = (
    "torchft_tpu/coordination.py",
    "torchft_tpu/store.py",
)


def _read(root: str, rel: str) -> str:
    with open(os.path.join(root, rel), encoding="utf-8") as f:
        return f.read()


def scrape_cpp_enum(text: str, enum_name: str) -> Dict[str, int]:
    """``enum [class] <name> [: type] { A = 1, B = 2, ... }`` -> dict.
    Members without explicit ``= value`` continue the running count, like
    the compiler."""
    m = re.search(
        r"enum\s+(?:class\s+)?" + re.escape(enum_name)
        + r"\s*(?::\s*[\w:]+\s*)?\{([^}]*)\}",
        text, re.S,
    )
    if not m:
        return {}
    out: Dict[str, int] = {}
    nxt = 0
    for part in m.group(1).split(","):
        part = re.sub(r"//.*", "", part).strip()
        if not part:
            continue
        mm = re.match(r"(\w+)\s*(?:=\s*(\d+))?", part)
        if not mm:
            continue
        val = int(mm.group(2)) if mm.group(2) is not None else nxt
        out[mm.group(1)] = val
        nxt = val + 1
    return out


def scrape_py_constants(text: str, pattern: str) -> Dict[str, int]:
    """Module-level ``NAME = <int>`` constants matching ``pattern``."""
    out: Dict[str, int] = {}
    for m in re.finditer(
        r"^(" + pattern + r")\s*(?::\s*\w+)?\s*=\s*(\d+)\s*$", text, re.M
    ):
        out[m.group(1)] = int(m.group(2))
    return out


def _diff_maps(
    rule: str, path: str, a_name: str, a: Dict[str, int],
    b_name: str, b: Dict[str, int], normalize=lambda s: s,
) -> List[Finding]:
    finds: List[Finding] = []
    na = {normalize(k): v for k, v in a.items()}
    nb = {normalize(k): v for k, v in b.items()}
    for k in sorted(set(na) | set(nb)):
        if k not in na:
            finds.append(Finding(
                rule, path, 0, k,
                f"defined in {b_name} (={nb[k]}) but missing from {a_name}",
            ))
        elif k not in nb:
            finds.append(Finding(
                rule, path, 0, k,
                f"defined in {a_name} (={na[k]}) but missing from {b_name}",
            ))
        elif na[k] != nb[k]:
            finds.append(Finding(
                rule, path, 0, k,
                f"value mismatch: {a_name}={na[k]} vs {b_name}={nb[k]} — "
                "the two codecs would disagree byte-for-byte",
            ))
    return finds


# ---------------------------------------------------------------------------
# individual checks (each takes file texts so fixtures can drive them)
# ---------------------------------------------------------------------------


def check_wire_tags(wire_h: str, wire_py: str) -> List[Finding]:
    cpp = scrape_cpp_enum(wire_h, "Type")
    py = scrape_py_constants(wire_py, r"_[A-Z][A-Z0-9]*")
    return _diff_maps(
        "wire-tag-drift", "torchft_tpu/utils/wire.py",
        "native/wire.h Value::Type", cpp,
        "utils/wire.py", py,
        normalize=lambda s: s.lstrip("_").upper(),
    )


def check_status_codes(wire_h: str, native_init: str, pyi: str) -> List[Finding]:
    cpp = scrape_cpp_enum(wire_h, "Status")
    py = scrape_py_constants(native_init, r"[A-Z][A-Z_]*")
    finds = _diff_maps(
        "status-code-drift", "torchft_tpu/_native/__init__.py",
        "native/wire.h Status", cpp, "_native/__init__.py", py,
    )
    stub_names = set(re.findall(r"^([A-Z][A-Z_]*)\s*:\s*int\s*$", pyi, re.M))
    for k in sorted(set(cpp) - stub_names):
        finds.append(Finding(
            "status-code-drift", "torchft_tpu/_native/__init__.pyi", 0, k,
            "status code missing from the .pyi stub",
        ))
    for k in sorted(stub_names - set(cpp)):
        finds.append(Finding(
            "status-code-drift", "torchft_tpu/_native/__init__.pyi", 0, k,
            "stub declares a status code the native enum does not define",
        ))
    return finds


_METHOD_RE = re.compile(r'"((?:mgr|lh|store)\.[a-z_]+)"')


def check_rpc_methods(
    native_texts: Dict[str, str], py_texts: Dict[str, str]
) -> List[Finding]:
    handled: Set[str] = set()
    native_calls: Set[str] = set()
    for _name, text in native_texts.items():
        for m in re.finditer(r'method\s*==\s*"((?:mgr|lh|store)\.[a-z_]+)"', text):
            handled.add(m.group(1))
        for m in re.finditer(r'call\("((?:mgr|lh|store)\.[a-z_]+)"', text):
            native_calls.add(m.group(1))
    py_calls: Set[str] = set()
    for _name, text in py_texts.items():
        py_calls.update(_METHOD_RE.findall(text))
    finds: List[Finding] = []
    for m in sorted(py_calls - handled):
        finds.append(Finding(
            "rpc-method-drift", "native/coord.cc", 0, m,
            "Python calls this RPC method but no native dispatch arm "
            "handles it — the call can only ever return INVALID_ARGUMENT",
        ))
    for m in sorted(handled - py_calls - native_calls):
        finds.append(Finding(
            "rpc-method-drift", "native/coord.cc", 0, m,
            "native dispatch arm with no caller on either side — dead "
            "protocol surface drifts silently; remove it or justify in "
            "the baseline",
        ))
    return finds


_FI_RE = re.compile(r"TORCHFT_FI_[A-Z_0-9]+")


def check_fi_env(
    native_texts: Dict[str, str], doc_text: str, py_texts: Dict[str, str]
) -> List[Finding]:
    native: Set[str] = set()
    for text in native_texts.values():
        native.update(_FI_RE.findall(text))
    doc = set(_FI_RE.findall(doc_text))
    py: Set[str] = set()
    for text in py_texts.values():
        py.update(m for m in _FI_RE.findall(text) if m != "TORCHFT_FI_")
    finds: List[Finding] = []
    for k in sorted(native - doc):
        finds.append(Finding(
            "fi-env-drift", "docs/fault_injection.md", 0, k,
            "native fault-injection knob not documented in the knob table",
        ))
    for k in sorted(doc - native):
        finds.append(Finding(
            "fi-env-drift", "docs/fault_injection.md", 0, k,
            "documented knob that no native code parses — schedules "
            "driving it silently no-op",
        ))
    for k in sorted(py - native):
        finds.append(Finding(
            "fi-env-drift", "torchft_tpu/faultinject/runner.py", 0, k,
            "Python references a TORCHFT_FI_ knob the native plane does "
            "not parse — the scenario silently no-ops",
        ))
    return finds


_WIRE_RE = re.compile(r"TORCHFT_WIRE_[A-Z0-9_]+")


def check_wire_env(
    py_texts: Dict[str, str], wire_doc_text: str
) -> List[Finding]:
    py: Set[str] = set()
    for text in py_texts.values():
        py.update(_WIRE_RE.findall(text))
    doc = set(_WIRE_RE.findall(wire_doc_text))
    finds: List[Finding] = []
    for k in sorted(py - doc):
        finds.append(Finding(
            "wire-env-drift", "docs/wire_plane.md", 0, k,
            "wire-plane knob referenced in code but missing from the "
            "docs/wire_plane.md knob registry — invisible to operators",
        ))
    for k in sorted(doc - py):
        finds.append(Finding(
            "wire-env-drift", "docs/wire_plane.md", 0, k,
            "documented wire-plane knob that no code reads — a deploy "
            "config setting it silently no-ops",
        ))
    return finds


_OBS_RE = re.compile(
    r"TORCHFT_(?:SLO|STRAGGLER|BLACKBOX|DIVERGENCE|TSDB|PROF"
    r"|DIAG|TELEMETRY)_[A-Z0-9_]+"
)


def check_obs_env(
    py_texts: Dict[str, str], obs_doc_text: str
) -> List[Finding]:
    """The TORCHFT_SLO_* / TORCHFT_STRAGGLER_* / TORCHFT_BLACKBOX_* /
    TORCHFT_DIVERGENCE_* / TORCHFT_TSDB_* / TORCHFT_PROF_* /
    TORCHFT_DIAG_* knob families vs the docs/observability.md knob
    registry, both directions (the
    wire-env-drift contract for the step-anatomy, forensics, divergence,
    history and diagnosis planes). The TSDB and PROF knobs are ALSO
    parsed natively (tsdb.h / profiler.h getenv) — the Python references
    the rule checks are the builder/client's shared constants, so both
    sides stay on one registry."""
    py: Set[str] = set()
    for text in py_texts.values():
        py.update(_OBS_RE.findall(text))
    doc = set(_OBS_RE.findall(obs_doc_text))
    finds: List[Finding] = []
    for k in sorted(py - doc):
        finds.append(Finding(
            "obs-env-drift", "docs/observability.md", 0, k,
            "SLO/straggler knob referenced in code but missing from the "
            "docs/observability.md knob registry — invisible to operators",
        ))
    for k in sorted(doc - py):
        finds.append(Finding(
            "obs-env-drift", "docs/observability.md", 0, k,
            "documented SLO/straggler knob that no code reads — a deploy "
            "config setting it silently no-ops",
        ))
    return finds


_HEAL_RE = re.compile(r"TORCHFT_HEAL_[A-Z0-9_]+")


def check_heal_env(
    py_texts: Dict[str, str], heal_doc_text: str
) -> List[Finding]:
    """The TORCHFT_HEAL_* knob family vs the docs/heal_plane.md knob
    registry, both directions (the wire-env-drift contract for the
    striped/differential heal plane)."""
    py: Set[str] = set()
    for text in py_texts.values():
        py.update(_HEAL_RE.findall(text))
    doc = set(_HEAL_RE.findall(heal_doc_text))
    finds: List[Finding] = []
    for k in sorted(py - doc):
        finds.append(Finding(
            "heal-env-drift", "docs/heal_plane.md", 0, k,
            "heal-plane knob referenced in code but missing from the "
            "docs/heal_plane.md knob registry — invisible to operators",
        ))
    for k in sorted(doc - py):
        finds.append(Finding(
            "heal-env-drift", "docs/heal_plane.md", 0, k,
            "documented heal-plane knob that no code reads — a deploy "
            "config setting it silently no-ops",
        ))
    return finds


def check_fault_sites(
    native_texts: Dict[str, str], native_sites: tuple
) -> List[Finding]:
    used: Set[str] = set()
    for text in native_texts.values():
        for m in re.finditer(
            r'(?:write_evidence|kill_self)\("([a-z_.]+)"', text
        ):
            used.add(m.group(1))
    finds: List[Finding] = []
    for s in sorted(used - set(native_sites)):
        finds.append(Finding(
            "fault-site-drift", "torchft_tpu/faultinject/core.py", 0, s,
            "native evidence site label not listed in "
            "faultinject.core.NATIVE_SITES — death attribution "
            "(conftest/runner evidence checks) won't recognize it",
        ))
    for s in sorted(set(native_sites) - used):
        finds.append(Finding(
            "fault-site-drift", "torchft_tpu/faultinject/core.py", 0, s,
            "NATIVE_SITES lists a site no native code emits — stale "
            "catalog entry",
        ))
    return finds


_PY_PUBLIC_RE = re.compile(r"^(?:def|class)\s+([A-Za-z_][A-Za-z0-9_]*)", re.M)


def check_stub(native_init: str, pyi: str) -> List[Finding]:
    real = {
        n for n in _PY_PUBLIC_RE.findall(native_init) if not n.startswith("_")
    }
    stub = {
        n for n in _PY_PUBLIC_RE.findall(pyi) if not n.startswith("_")
    }
    finds: List[Finding] = []
    for n in sorted(real - stub):
        finds.append(Finding(
            "stub-drift", "torchft_tpu/_native/__init__.pyi", 0, n,
            "public binding missing from the .pyi stub — typed callers "
            "can't see it",
        ))
    for n in sorted(stub - real):
        finds.append(Finding(
            "stub-drift", "torchft_tpu/_native/__init__.pyi", 0, n,
            "stub declares a binding the loader does not define",
        ))
    return finds


def check_makefile_hdrs(
    makefile: str, header_names: List[str]
) -> List[Finding]:
    """``makefile-hdrs-drift``: every ``native/*.h`` must appear in the
    Makefile's ``HDRS`` variable — HDRS is the .so targets' prerequisite
    list, so a header missing from it means editing that header does NOT
    rebuild the libraries and a stale ``.so`` ships silently. This exact
    omission happened twice (tsdb.h in PR 11, profiler.h caught again in
    PR 12); this rule makes it un-shippable. The reverse direction —
    an HDRS entry whose file is gone — is dead weight that masks the
    next real omission, so it errors too."""
    # HDRS := a.h b.h \
    #         c.h         (continuation lines folded first)
    folded = re.sub(r"\\\s*\n", " ", makefile)
    m = re.search(r"^HDRS\s*[:+?]?=\s*(.*)$", folded, re.MULTILINE)
    listed: Set[str] = set(m.group(1).split()) if m else set()
    finds: List[Finding] = []
    if m is None:
        finds.append(Finding(
            "makefile-hdrs-drift", "native/Makefile", 0, "HDRS",
            "no HDRS variable found — the .so targets have no header "
            "prerequisites at all; every header edit ships a stale .so",
        ))
        return finds
    for name in sorted(header_names):
        if name not in listed:
            finds.append(Finding(
                "makefile-hdrs-drift", "native/Makefile", 0, name,
                f"native/{name} is not in the Makefile's HDRS — editing "
                "it will NOT rebuild libtftcore*.so and a stale library "
                "ships silently (the tsdb.h/profiler.h incident class)",
            ))
    for name in sorted(listed):
        if name not in header_names:
            finds.append(Finding(
                "makefile-hdrs-drift", "native/Makefile", 0, name,
                f"HDRS lists {name} but native/{name} does not exist — "
                "dead prerequisites mask the next real omission",
            ))
    return finds


# ---------------------------------------------------------------------------
# repo gate
# ---------------------------------------------------------------------------


def run(root: Optional[str] = None) -> List[Finding]:
    root = root or repo_root()
    native_texts = {
        name: _read(root, os.path.join("native", name))
        for name in _NATIVE_SOURCES
        if os.path.exists(os.path.join(root, "native", name))
    }
    wire_h = native_texts.get("wire.h", "")
    wire_py = _read(root, "torchft_tpu/utils/wire.py")
    native_init = _read(root, "torchft_tpu/_native/__init__.py")
    pyi = _read(root, "torchft_tpu/_native/__init__.pyi")
    doc = _read(root, "docs/fault_injection.md")
    wire_doc_path = os.path.join(root, "docs", "wire_plane.md")
    wire_doc = (
        _read(root, "docs/wire_plane.md")
        if os.path.exists(wire_doc_path)
        else ""
    )

    py_rpc = {rel: _read(root, rel) for rel in _PY_RPC_SOURCES}
    py_fi: Dict[str, str] = {}
    for base, _dirs, files in os.walk(os.path.join(root, "torchft_tpu")):
        if "__pycache__" in base:
            continue
        for fn in files:
            if fn.endswith(".py"):
                rel = os.path.relpath(os.path.join(base, fn), root)
                py_fi[rel] = _read(root, rel)

    from torchft_tpu.faultinject.core import NATIVE_SITES

    out: List[Finding] = []
    out += check_wire_tags(wire_h, wire_py)
    out += check_status_codes(wire_h, native_init, pyi)
    out += check_rpc_methods(native_texts, py_rpc)
    out += check_fi_env(native_texts, doc, py_fi)
    out += check_wire_env(py_fi, wire_doc)
    obs_doc_path = os.path.join(root, "docs", "observability.md")
    obs_doc = (
        _read(root, "docs/observability.md")
        if os.path.exists(obs_doc_path)
        else ""
    )
    out += check_obs_env(py_fi, obs_doc)
    heal_doc_path = os.path.join(root, "docs", "heal_plane.md")
    heal_doc = (
        _read(root, "docs/heal_plane.md")
        if os.path.exists(heal_doc_path)
        else ""
    )
    out += check_heal_env(py_fi, heal_doc)
    out += check_fault_sites(native_texts, NATIVE_SITES)
    out += check_stub(native_init, pyi)
    native_dir = os.path.join(root, "native")
    headers = sorted(
        fn for fn in os.listdir(native_dir) if fn.endswith(".h")
    ) if os.path.isdir(native_dir) else []
    out += check_makefile_hdrs(_read(root, "native/Makefile"), headers)
    return out
