"""torchft_tpu static-analysis suite — one gate for the invariants that
the test tier can't see.

Run it as ``python -m torchft_tpu.analysis`` (single exit code, human or
``--json`` output, checked-in baseline at ``analysis/baseline.json``).
Four analyzers:

* :mod:`~torchft_tpu.analysis.concurrency` — AST concurrency lint over
  the FT runtime modules (lock-order cycles, blocking/callback calls
  under locks, guarded-by annotations for cross-thread state,
  ``Condition.wait`` predicate loops, thread hygiene);
* :mod:`~torchft_tpu.analysis.wiredrift` — C++ ↔ Python protocol drift
  (wire tags, status codes, RPC opcodes, ``TORCHFT_FI_*`` knobs, fault
  site labels, ``.pyi`` stub coverage, Makefile HDRS coverage);
* :mod:`~torchft_tpu.analysis.docdrift` — the bidirectional doc/registry
  catalogs (metrics, events, fault sites, premerge gate ids);
* :mod:`~torchft_tpu.analysis.nativelint` — the clang-free lexical
  concurrency lint over ``native/*.{h,cc}`` (lock-order graph,
  blocking-syscall-under-lock, cv predicate loops, non-seq_cst atomic
  annotations).

The FT-protocol verification plane (executable spec + bounded model
checker + trace conformance) lives in
:mod:`~torchft_tpu.analysis.protocol` with its own CLI
(``python -m torchft_tpu.analysis.protocol``, premerge gate [4]).

See ``docs/static_analysis.md`` for the rule catalog and the baseline
workflow.
"""

from __future__ import annotations

from typing import Dict, List, Optional

from torchft_tpu.analysis.base import (
    Baseline,
    DEFAULT_BASELINE,
    Finding,
    repo_root,
)

__all__ = [
    "Baseline",
    "DEFAULT_BASELINE",
    "Finding",
    "repo_root",
    "run_all",
]


def run_all(
    root: Optional[str] = None, cache: Optional[object] = None
) -> Dict[str, List[Finding]]:
    """Run every analyzer; returns findings per analyzer (pre-baseline).

    ``cache`` — an :class:`~torchft_tpu.analysis.cache.AnalysisCache`:
    analyzers whose input fingerprint matches replay their stored
    findings instead of re-scanning (the CLI passes one unless
    ``--no-cache``; programmatic callers default to uncached)."""
    from torchft_tpu.analysis import (
        concurrency,
        docdrift,
        nativelint,
        wiredrift,
    )

    runners = {
        "concurrency": concurrency.run,
        "wiredrift": wiredrift.run,
        "docdrift": docdrift.run,
        "nativelint": nativelint.run,
    }
    out: Dict[str, List[Finding]] = {}
    for name, runner in runners.items():
        cached = cache.get(name) if cache is not None else None
        if cached is not None:
            out[name] = cached
            continue
        out[name] = runner(root)
        if cache is not None:
            cache.put(name, out[name])
    return out
