"""Deterministic fault-injection plane for the FT runtime.

``faultinject.core`` is the site registry + seeded schedule engine (the
Python layers' injection points consult it through
:func:`~torchft_tpu.faultinject.core.fault_point`);
``faultinject.runner`` drives the 2-group example trainer through a
scenario matrix (mid-op kills per data plane, torn CMA pulls, delayed
commit votes, checkpoint-serve death) and asserts the end-to-end safety
invariant — no committed step may carry corrupt averages;
``faultinject.recovery`` is the kill harness (SIGKILL one of N numpy
groups, respawn it, blackout and rejoin bookkeeping). See
``docs/fault_injection.md``.
"""

from torchft_tpu.faultinject.core import (
    ACTIONS,
    ENV_EVIDENCE_DIR,
    ENV_SCHEDULE,
    SITES,
    FaultPlane,
    Injection,
    active,
    configure,
    fault_point,
    read_evidence,
)

__all__ = [
    "ACTIONS",
    "ENV_EVIDENCE_DIR",
    "ENV_SCHEDULE",
    "SITES",
    "FaultPlane",
    "Injection",
    "active",
    "configure",
    "fault_point",
    "read_evidence",
]
