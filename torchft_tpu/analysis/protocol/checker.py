"""Bounded model checker for the FT-protocol spec, with reductions.

Plain explicit-state depth-first search with a visited set: every
interleaving of every enabled transition — including the crash action,
which :func:`~torchft_tpu.analysis.protocol.spec.enabled_actions` offers
at every transition point (SIGKILL-anywhere) — is explored. Safety
invariants are evaluated at every visited state; the liveness check at
every terminal state. A violation comes back with the full action trace
from the initial state, so a red check reads like a reproduction recipe,
not a boolean — and :mod:`~torchft_tpu.analysis.protocol.compile` lowers
that trace into a runnable faultinject schedule.

The HA lighthouse tier (ISSUE 20) multiplies the state space far past
what plain DFS can exhaust in a premerge budget, so the checker carries
three *sound* reductions and one loud approximation:

* **Partial-order reduction** (``por=True``): when a *pure-local* action
  is enabled whose effects commute with every other enabled action and
  are invisible to every invariant, the checker expands only that action
  and defers the rest (they stay enabled in the successor). Two action
  families qualify, each under the precondition that makes it safe:
  ``join`` (only with the join barrier on — ``form`` is then disabled
  until every live replica joined, and a join erased by a later crash
  collapses to the crash alone) and ``work`` (only once the crash AND
  corrupt budgets are spent — a pending ``crash(i)``/``work_corrupt(i)``
  does *not* commute with ``work(i)``: dying before vs. after the
  contribution changes the survivors' ``lost`` verdict).
* **State canonicalization**: visited-set keys are rendered through a
  normal form that (a) sorts each round's cast-vote tuple (every reader
  is order-insensitive), (b) collapses *closed* rounds — every member
  resolved or permanently detached — to their identity (no enabled
  action or invariant reads a closed round's bookkeeping), and
  (c) scrubs dead replicas' membership view (a respawn rebuilds it from
  the snapshot). The checker still explores REAL states — only the
  dedup key is canonical — so violation traces stay executable.
* **Symmetry reduction** (``symmetry=True``): interchangeable replica
  groups (and lighthouse replicas) are quotiented by taking the
  lexicographically-least rendering over index permutations. Sound
  because the transition relation is index-uniform: a permuted state's
  behaviour is the permutation of the original's.
* **Bitstate hashing** (``bitstate=True``): the visited set stores 64-bit
  digests instead of renderings. A hash collision silently *prunes* an
  unexplored subtree, so coverage becomes APPROXIMATE — the result is
  marked ``approximate`` and every front end prints it loudly. Off by
  default; for exploratory sweeps of configs beyond the gate budget.

Budgets: ``max_states`` / ``max_transitions`` cap the search; hitting a
cap sets ``truncated`` and the explicit counters ``truncated_states``
(frontier states never expanded) / ``truncated_transitions`` (enabled
actions never taken), so "the check passed" can never silently mean
"the check gave up".

The four single-lighthouse gate configurations verify with verdicts
identical to the exhaustive run at >5x fewer explored states under
POR+symmetry (asserted by tests/test_protocol.py); the HA gate configs
verify clean within the stated budgets in ``HA_STATE_BUDGETS``.
"""

from __future__ import annotations

import hashlib
import itertools
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

from torchft_tpu.analysis.protocol.spec import (
    DEAD,
    Invariant,
    Round,
    SpecConfig,
    State,
    check_state,
    check_terminal,
    enabled_actions,
    init_state,
)

__all__ = [
    "CheckResult", "Violation", "check", "GATE_CONFIGS",
    "HA_STATE_BUDGETS",
]


@dataclass
class Violation:
    invariant: str
    detail: str
    trace: List[str]  # action labels from the initial state

    def render(self) -> str:
        path = " -> ".join(self.trace) if self.trace else "<initial>"
        return f"[{self.invariant}] {self.detail}\n    trace: {path}"


@dataclass
class CheckResult:
    config: SpecConfig
    states: int = 0
    transitions: int = 0
    terminals: int = 0
    violations: List[Violation] = field(default_factory=list)
    truncated: bool = False          # a state/transition budget was hit
    truncated_states: int = 0        # frontier states never expanded
    truncated_transitions: int = 0   # enabled actions never taken
    pruned_actions: int = 0          # actions deferred by POR
    approximate: bool = False        # bitstate: coverage NOT exhaustive

    @property
    def ok(self) -> bool:
        return not self.violations and not self.truncated


# ---------------------------------------------------------------------------
# canonicalization: the visited-set normal form
# ---------------------------------------------------------------------------


def _round_closed(state: State, rnd: Round) -> bool:
    """A round no enabled action and no invariant will ever read again:
    every member either resolved its vote or is permanently detached
    (crashed out — old round ids are never re-attached)."""
    for j in rnd.members:
        if j in rnd.resolved:
            continue
        if state.replicas[j].round == rnd.rid:
            return False
    return True


def _render(
    state: State,
    rperm: Tuple[int, ...],
    lperm: Tuple[int, ...],
) -> tuple:
    """One fully-ordered rendering of ``state`` under a replica-index
    permutation ``rperm`` and a lighthouse-index permutation ``lperm``
    (old index -> new index). Frozensets become sorted tuples so
    renderings are totally ordered; the identity permutation's
    rendering is itself a faithful state key."""
    rmap = rperm.__getitem__

    reps: List[tuple] = [()] * len(state.replicas)
    for i, r in enumerate(state.replicas):
        if r.status == DEAD:
            # volatile-on-respawn fields: a respawn rebuilds the
            # membership view from the snapshot, so two dead states
            # differing only there are bisimilar
            mview, view = 0, ()
        else:
            mview, view = r.mview, tuple(sorted(rmap(x) for x in r.view))
        reps[rmap(i)] = (
            r.status, r.step, r.lineage, r.joined, r.round,
            r.voted, r.abstain, r.worked, r.diverged, r.healer,
            r.healed, r.epoch, mview, view,
        )

    rounds: List[tuple] = []
    for rnd in state.rounds:
        if _round_closed(state, rnd):
            rounds.append((rnd.rid, rnd.epoch, "closed"))
        else:
            rounds.append((
                rnd.rid, rnd.epoch, rnd.step,
                tuple(sorted(rmap(m) for m in rnd.members)),
                tuple(sorted((rmap(m), t) for m, t in rnd.votes)),
                tuple(sorted(rmap(m) for m in rnd.resolved)),
                tuple(sorted(rmap(m) for m in rnd.done)),
                rnd.mver,
            ))

    lmap = lperm.__getitem__
    lhs: List[tuple] = [()] * len(state.lighthouses)
    for i, lh in enumerate(state.lighthouses):
        lhs[lmap(i)] = (
            lh.status, lh.term,
            (lmap(lh.voted_for) if lh.voted_for >= 0 else -1),
            tuple(sorted(lmap(v) for v in lh.votes)),
            lh.log, lh.commit_len, lh.cell,
        )

    return (
        tuple(reps), tuple(rounds),
        tuple(sorted(rmap(i) for i in state.open_round)),
        state.epoch, state.rounds_formed,
        state.crash_budget, state.respawn_budget, state.corrupt_budget,
        state.commits, state.divergence_latched,
        tuple(lhs), state.ha_committed,
        state.lh_crash_budget, state.lh_respawn_budget,
        state.partition_budget,
        state.mversion,
        tuple((v, rmap(rep), a) for v, rep, a in state.mlog),
        tuple(
            (s.status, tuple(sorted(rmap(x) for x in s.owns)))
            for s in state.subaggs
        ),
        state.subagg_budget,
    )


def _perm_sets(
    cfg: SpecConfig, symmetry: bool
) -> Tuple[List[Tuple[int, ...]], List[Tuple[int, ...]]]:
    rid = tuple(range(cfg.n_replicas))
    lid = tuple(range(cfg.n_lighthouses if cfg.n_lighthouses >= 2 else 0))
    if not symmetry:
        return [rid], [lid]
    # factorials past 4 cost more than they merge; fall back to identity
    rperms = (
        [tuple(p) for p in itertools.permutations(rid)]
        if 2 <= cfg.n_replicas <= 4 else [rid]
    )
    lperms = (
        [tuple(p) for p in itertools.permutations(lid)]
        if 2 <= len(lid) <= 4 else [lid]
    )
    return rperms, lperms


# ---------------------------------------------------------------------------
# partial-order reduction: the ample-action selector
# ---------------------------------------------------------------------------


def _por_select(
    state: State, cfg: SpecConfig,
    actions: List[Tuple[str, State]],
) -> List[Tuple[str, State]]:
    """Return the subset of ``actions`` to expand. Picks a single safe
    pure-local action when one exists (see the module docstring for the
    commutation argument); otherwise everything."""
    # joins commute pairwise and with every non-form action; with the
    # barrier on, form is disabled until no join is enabled, and a
    # join erased by a later crash equals the crash alone
    if cfg.join_barrier:
        for a in actions:
            if a[0].startswith("join("):
                return [a]
    # work(i) commutes with everything EXCEPT crash(i) (dying before
    # vs. after contributing flips the survivors' `lost` verdict) and
    # work_corrupt(i) (the same replica's branching choice) — both
    # excluded by requiring the budgets already spent
    if state.crash_budget == 0 and state.corrupt_budget == 0:
        for a in actions:
            if a[0].startswith("work("):
                return [a]
    return actions


# ---------------------------------------------------------------------------
# the search
# ---------------------------------------------------------------------------


def check(
    cfg: SpecConfig,
    max_states: int = 2_000_000,
    max_violations: int = 16,
    *,
    por: bool = True,
    symmetry: bool = True,
    bitstate: bool = False,
    max_transitions: Optional[int] = None,
) -> CheckResult:
    """Explore ``cfg``; returns states visited + violations (each with
    its executable action trace). ``por=False, symmetry=False`` is the
    exhaustive reference mode the reductions are validated against.

    Collecting ``max_violations`` violations stops the search early
    (marked ``truncated`` — exploration was incomplete, but the verdict
    is already red); pass ``max_violations=1`` for a fast fail-on-first
    run over a known-broken config."""
    res = CheckResult(config=cfg, approximate=bitstate)
    root = init_state(cfg)
    rperms, lperms = _perm_sets(cfg, symmetry)

    def key_of(state: State):
        k = min(
            _render(state, rp, lp)
            for rp in rperms for lp in lperms
        )
        if bitstate:
            return hashlib.blake2b(
                repr(k).encode(), digest_size=8
            ).digest()
        return k

    # parent pointers for trace reconstruction (state -> (prev, action))
    parent: Dict[State, Optional[Tuple[State, str]]] = {root: None}
    stack: List[State] = [root]
    seen = {key_of(root)}

    def trace_of(state: State, extra: Optional[str] = None) -> List[str]:
        labels: List[str] = []
        cur: Optional[State] = state
        while cur is not None:
            link = parent[cur]
            if link is None:
                break
            prev, action = link
            labels.append(action)
            cur = prev
        labels.reverse()
        if extra:
            labels.append(extra)
        return labels

    def record(inv: Invariant, state: State,
               extra: Optional[str] = None) -> None:
        if len(res.violations) >= max_violations:
            return
        res.violations.append(
            Violation(inv.name, inv.detail, trace_of(state, extra))
        )

    for inv in check_state(root, cfg):
        record(inv, root)

    while stack:
        if len(res.violations) >= max_violations:
            # verdict is already red; stop burning budget on more paths
            res.truncated = True
            res.truncated_states = len(stack)
            break
        state = stack.pop()
        res.states += 1
        if res.states > max_states:
            res.truncated = True
            res.truncated_states = len(stack) + 1
            break
        actions = enabled_actions(state, cfg)
        if not actions:
            res.terminals += 1
            for inv in check_terminal(state, cfg):
                record(inv, state)
            continue
        if por:
            expand = _por_select(state, cfg, actions)
            res.pruned_actions += len(actions) - len(expand)
        else:
            expand = actions
        for label, nxt in expand:
            if (
                max_transitions is not None
                and res.transitions >= max_transitions
            ):
                res.truncated = True
                res.truncated_transitions += 1
                continue
            res.transitions += 1
            # action-labelled invariants (the heal-fence and stale-view
            # checks key on the transition itself) are evaluated on the
            # SUCCESSOR with the action attached, even when the
            # successor was already reached by a benign path
            for inv in check_state(nxt, cfg, action=label):
                # dedupe identical (invariant, detail) repeats — one
                # trace per distinct violation is plenty
                if not any(
                    v.invariant == inv.name and v.detail == inv.detail
                    for v in res.violations
                ):
                    record(inv, state, extra=label)
            k = key_of(nxt)
            if k not in seen:
                seen.add(k)
                parent[nxt] = (state, label)
                stack.append(nxt)
    return res


# The repo-gate configurations (premerge gate [4] + tier-1 wrapper):
# every one of these must come back clean. The broken variants live in
# tests/fixtures/analysis/ as seeded fixtures, not here.
GATE_CONFIGS: Dict[str, SpecConfig] = {
    # the shipped sync protocol, 2 groups, a crash anywhere + respawn
    "sync-2g": SpecConfig(
        n_replicas=2, min_replicas=1, max_rounds=3,
        crash_budget=1, respawn_budget=1,
    ),
    # divergence fence armed against a silently-corrupting compute
    "divergence-fenced-2g": SpecConfig(
        n_replicas=2, min_replicas=1, max_rounds=3,
        crash_budget=1, respawn_budget=1, corrupt_budget=1,
    ),
    # three groups, shipped protocol (wider interleavings, quick config)
    "sync-3g": SpecConfig(
        n_replicas=3, min_replicas=2, max_rounds=3,
        crash_budget=1, respawn_budget=1,
    ),
    # --- the HA tier (ISSUE 20). The replica-group protocol is carried
    # by the three configs above; these stress the lighthouse tier, so
    # the group side stays minimal to keep the product space honest.
    # leader SIGKILLed mid-epoch, durable-log respawn, one re-election
    "ha-leader-crash": SpecConfig(
        n_replicas=1, min_replicas=1, max_rounds=2,
        n_lighthouses=3, lh_crash_budget=1, lh_respawn_budget=1,
        max_terms=2,
    ),
    # the leader isolated by a network split; majority side re-elects;
    # the stale leader keeps serving joins but can never commit
    "ha-partition-reelect": SpecConfig(
        n_replicas=1, min_replicas=1, max_rounds=2,
        n_lighthouses=3, partition_budget=1, max_terms=2,
    ),
    # versioned membership deltas under crash+respawn churn: in-order
    # apply, loss -> gap-detect -> full-snapshot resync, stale-view
    # fence on the commit vote
    "ha-delta-resync": SpecConfig(
        n_replicas=2, min_replicas=1, max_rounds=3,
        crash_budget=1, respawn_budget=1, membership_deltas=True,
    ),
    # two-level quorum tree: a sub-aggregator crash drops its buffered
    # joins, the groups re-home and re-join — epochs never split
    "ha-subagg-crash": SpecConfig(
        n_replicas=3, min_replicas=2, max_rounds=2,
        n_subaggs=2, subagg_crash_budget=1,
    ),
}

# The stated exploration budget per HA gate config (acceptance: clean
# within these bounds — a config outgrowing its budget fails loudly via
# `truncated` instead of silently passing on partial coverage).
HA_STATE_BUDGETS: Dict[str, int] = {
    "ha-leader-crash": 600_000,
    "ha-partition-reelect": 600_000,
    "ha-delta-resync": 400_000,
    "ha-subagg-crash": 400_000,
}
