"""Executable specification of the FT protocol + its three consumers.

The per-step quorum → vote → commit/abort/heal lifecycle (Lighthouse
quorum + Manager arbitration — ``manager.py`` / ``coordination.py`` /
``native/coord.cc``) is the one protocol the paper's value rests on, and
until now its only proofs were dynamic: faultmatrix scenarios sample
interleavings, sanitizers sample executions. This package is the
machine-checked side:

* :mod:`~torchft_tpu.analysis.protocol.spec` — the protocol as an
  explicit state machine: replica states (JOINING / HEALTHY / HEALING /
  DEAD), lighthouse epoch rounds, vote folding and the divergence fence
  (PR 10), with the core invariants as checkable predicates;
* :mod:`~torchft_tpu.analysis.protocol.checker` — a deterministic DFS
  model checker that exhaustively explores bounded configurations with a
  crash injected at every transition point (the SIGKILL-anywhere
  semantics faultinject implements dynamically);
* :mod:`~torchft_tpu.analysis.protocol.conformance` — replays real FT
  event trails and black-box records against the spec's event-level
  transition rules, flagging any illegal transition (wired into
  ``postmortem --conformance`` and the faultmatrix runner).

CLI: ``python -m torchft_tpu.analysis.protocol`` (model-check the gate
configurations; ``--conformance DIR`` additionally replays every trail
under DIR). See ``docs/static_analysis.md`` "Protocol verification".
"""

from torchft_tpu.analysis.protocol.spec import (
    CANDIDATE,
    DEAD,
    FOLLOWER,
    HEALING,
    HEALTHY,
    JOINING,
    LEADER,
    Invariant,
    SpecConfig,
)
from torchft_tpu.analysis.protocol.checker import (
    GATE_CONFIGS,
    HA_STATE_BUDGETS,
    CheckResult,
    check,
)
from torchft_tpu.analysis.protocol.conformance import (
    check_records,
    check_trail_file,
    check_tree,
)

__all__ = [
    "JOINING",
    "HEALTHY",
    "HEALING",
    "DEAD",
    "FOLLOWER",
    "CANDIDATE",
    "LEADER",
    "Invariant",
    "SpecConfig",
    "CheckResult",
    "check",
    "GATE_CONFIGS",
    "HA_STATE_BUDGETS",
    "check_records",
    "check_trail_file",
    "check_tree",
]
