"""The FT protocol as an executable state machine.

The model is the per-step lifecycle exactly as the implementation ships
it (``manager.py`` / ``coord.cc`` semantics), abstracted to the decisions
that carry the correctness argument:

* **Replicas** (one per replica group — the Manager's unit of commit)
  hold a committed *lineage* — the ordered tuple of per-step commit
  tokens. A replica is JOINING (pre-first-quorum), HEALTHY, HEALING
  (behind the round's max step, pulling state from a source), or DEAD.
* **The lighthouse** forms rounds: replicas join, a round *forms* when
  the join barrier is satisfied (every live replica — the quorum), and
  each formed round bumps the epoch (quorum_id). Members compute, vote,
  and **resolve independently**: the commit vote is arbitrated per
  replica group (``mgr.should_commit``), not fleet-wide — the only
  fleet-global wait is the divergence fence's cohort digest compare
  (PR 10), which blocks resolution until every member's digest (or
  abstention) is in and vetoes every member's commit on a mismatch.
* **Crashes** are a first-class action: while the crash budget lasts,
  any live replica can die *between any two transitions* — the
  model-checker scheduler interleaves the crash action at every
  transition point, which is the SIGKILL-anywhere semantics the
  faultinject runner implements dynamically. Dead replicas respawn from
  their last committed state (the checkpoint), rejoin behind, and heal.

``SpecConfig`` flags deliberately allow *broken* variants — the fences
off, the join barrier off (split brain) — so the checker can demonstrate that each protection is load-bearing: turning
one off must produce an invariant violation (the seeded-fixture tests
assert exactly that), and the shipped configuration must produce none.

Invariants (``check_state`` / ``check_terminal``):

* ``I1 unique-commit``   — at most one committed lineage token per step,
  fleet-wide (a split brain or silently diverged commit violates this);
* ``I2 epoch-monotonic`` — a replica's observed quorum epoch never
  decreases;
* ``I4 lineage-length``  — a replica's lineage holds exactly one token
  per committed step;
* ``I5 diverged-commit`` — a *detected* divergence (two member states
  disagreeing) never commits while the divergence fence is armed
  (PR 10);
* ``L  liveness``        — in every terminal state with at least
  ``min_replicas`` live replicas, some step committed.

**The HA layer (ISSUE 20).** With ``n_lighthouses >= 2`` the model grows
a Raft-replicated lighthouse tier: each lighthouse replica is FOLLOWER /
CANDIDATE / LEADER / DEAD with a term, a single persistent vote per
term, and a durable log of quorum *decisions* (one ``(term, rid)`` entry
appended by the leader that forms round ``rid``). Leaders commit a log
prefix once a majority of lighthouses replicated it; managers fail over
via the peer list (``form`` goes through *any* live leader — including a
stale minority-partitioned one, which is exactly the hazard the
majority-commit fence neutralizes). ``membership_deltas`` adds the
sublinear-control-traffic membership protocol: the lighthouse keeps a
versioned membership log, replicas apply deltas in order (a gap forces a
full-snapshot resync), and rounds stamp the membership version their
quorum was computed against. ``n_subaggs`` adds the two-level quorum
tree: sub-aggregator nodes front the joins of the groups they own; a
sub-aggregator crash loses its buffered joins (the members re-join
through a re-homed aggregator) but never touches a formed round.

HA invariants:

* ``H1 one-leader-per-term``  — no two live leaders share a term
  (election safety; ``raft_single_vote=False`` plants the double-vote
  bug that breaks it);
* ``H2 committed-survives``   — every decision ever majority-committed
  is present in every live leader's log (leader-death durability;
  ``stale_leader_fence=False`` lets a minority leader commit locally
  and breaks it);
* ``H3 stale-view-commit``    — no commit vote rides a membership view
  older than the round's (``stale_view_fence=False`` breaks it);
* ``H4 delta-chain``          — a replica's incrementally-applied view
  always equals the full snapshot at its version
  (``ordered_deltas=False`` applies deltas out of order and breaks it);
* ``H5 epoch-unique``         — formed rounds carry globally unique
  epochs: a sub-aggregator crash/re-home never splits a group's epoch.

Election *liveness* is deliberately out of scope: Raft terminates
elections with randomized timeouts, which a bounded nondeterministic
model cannot honor — terminal states with no live leader are exempt from
``L`` (the checker proves election safety, not election progress).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import FrozenSet, List, NamedTuple, Optional, Tuple

__all__ = [
    "JOINING", "HEALTHY", "HEALING", "DEAD",
    "FOLLOWER", "CANDIDATE", "LEADER",
    "SpecConfig", "Replica", "Round", "State", "Invariant",
    "Lighthouse", "Subagg",
    "init_state", "enabled_actions", "check_state", "check_terminal",
    "is_terminal",
]

# replica status values (shared vocabulary with the conformance checker
# and docs/static_analysis.md's state catalog)
JOINING = "JOINING"
HEALTHY = "HEALTHY"
HEALING = "HEALING"
DEAD = "DEAD"

# lighthouse replica (Raft) status values — DEAD is shared
FOLLOWER = "FOLLOWER"
CANDIDATE = "CANDIDATE"
LEADER = "LEADER"


@dataclass(frozen=True)
class SpecConfig:
    """One bounded configuration of the model.

    The shipped protocol is ``fence_divergence=True`` (sentinel armed)
    and ``join_barrier=True``. Every flag exists so the checker can
    prove the protection matters by turning it off.
    """

    n_replicas: int = 2
    min_replicas: int = 1
    max_rounds: int = 3          # formed quorum rounds (bounded steps)
    crash_budget: int = 1        # SIGKILL-anywhere injections
    respawn_budget: int = 1
    corrupt_budget: int = 0      # silently-diverging computes
    join_barrier: bool = True    # False = split-brain-capable lighthouse
    fence_divergence: bool = True    # PR 10: mismatched digests veto

    # --- the HA layer (ISSUE 20) — all off/neutral by default, so the
    # single-lighthouse configurations above explore the exact PR 15
    # state space
    n_lighthouses: int = 1       # >= 2 arms the Raft lighthouse tier
    lh_crash_budget: int = 0     # lighthouse SIGKILLs (durable log kept)
    lh_respawn_budget: int = 0
    max_terms: int = 1           # term ids 1..max_terms bound elections
    partition_budget: int = 0    # isolate-the-leader network splits
    raft_single_vote: bool = True    # False = double-vote split brain
    stale_leader_fence: bool = True  # False = minority leader commits
    membership_deltas: bool = False  # versioned membership delta stream
    ordered_deltas: bool = True      # False = deltas applied out of order
    stale_view_fence: bool = True    # False = commit on a stale view
    n_subaggs: int = 0           # two-level quorum tree fan-in nodes
    subagg_crash_budget: int = 0


class Replica(NamedTuple):
    status: str
    step: int                 # committed step
    lineage: Tuple[str, ...]  # committed tokens; len == step
    joined: bool              # in the lighthouse's open (unformed) round
    round: int                # formed-round id this replica is in, or -1
    voted: bool               # voted in `round`
    abstain: bool             # vote was an abstention (failed heal)
    worked: bool              # computed this round's reduction
    diverged: bool            # this round's compute silently corrupted
    healer: bool              # assigned to heal in `round`
    healed: bool              # heal transfer landed
    epoch: int                # last quorum epoch observed
    mview: int = 0            # membership version this replica applied
    view: FrozenSet[int] = frozenset()  # its membership view at mview


class Round(NamedTuple):
    rid: int
    epoch: int
    step: int                            # the step this round attempts
    members: FrozenSet[int]
    # votes recorded at cast time: (member, token) — token "" = abstain
    votes: Tuple[Tuple[int, str], ...]
    resolved: FrozenSet[int]             # members whose vote resolved
    # members whose collective contribution completed (work done). This
    # is ROUND state, not replica state: it must survive the member's
    # later crash — a peer that died AFTER contributing does not fail
    # the survivors' allreduce, and their commits are per-group.
    done: FrozenSet[int]
    mver: int = 0   # membership version the quorum was computed against


class Lighthouse(NamedTuple):
    """One lighthouse replica of the Raft tier (``n_lighthouses >= 2``).

    ``term``/``voted_for``/``log`` are *durable* (they survive a crash —
    Raft's persistent state); ``votes`` (the ballots a candidate
    gathered) is volatile. ``log`` holds quorum-decision entries
    ``(term, rid)``; ``commit_len`` is the majority-replicated prefix
    this node, as leader, has committed. ``cell`` is the partition cell
    (0 = the majority side)."""

    status: str
    term: int
    voted_for: int                       # -1 = no vote cast this term
    votes: FrozenSet[int]
    log: Tuple[Tuple[int, int], ...]
    commit_len: int
    cell: int


class Subagg(NamedTuple):
    """A sub-aggregator of the two-level quorum tree: fronts the joins
    of the replica groups it ``owns``. Its only protocol state is the
    buffered joins — a crash loses those (the members re-join through a
    re-homed aggregator) and nothing else."""

    status: str                          # HEALTHY / DEAD
    owns: FrozenSet[int]


class State(NamedTuple):
    replicas: Tuple[Replica, ...]
    rounds: Tuple[Round, ...]       # formed rounds, in formation order
    open_round: FrozenSet[int]      # joined-but-unformed replica ids
    epoch: int
    rounds_formed: int
    crash_budget: int
    respawn_budget: int
    corrupt_budget: int
    # committed tokens per step, fleet-wide: ((step, (tokens...)), ...)
    commits: Tuple[Tuple[int, Tuple[str, ...]], ...]
    divergence_latched: bool
    # --- HA layer (constant () / 0 in single-lighthouse configs, so
    # the PR 15 state space is unchanged byte for byte)
    lighthouses: Tuple[Lighthouse, ...] = ()
    # every decision entry ever majority-committed, fleet-global ledger
    # (the H2 durability oracle — the model's ghost variable):
    # (commit_term, entry_term, rid) — commit_term scopes the Raft
    # Leader Completeness claim (a STALE lower-term leader legally
    # lacks entries committed after its term; it can't commit anything)
    ha_committed: Tuple[Tuple[int, int, int], ...] = ()
    lh_crash_budget: int = 0
    lh_respawn_budget: int = 0
    partition_budget: int = 0
    mversion: int = 0                       # membership log head version
    # membership deltas: (version, replica, alive) — version is 1-based
    mlog: Tuple[Tuple[int, int, bool], ...] = ()
    subaggs: Tuple[Subagg, ...] = ()
    subagg_budget: int = 0


class Invariant(NamedTuple):
    """One violated invariant, with human detail."""

    name: str
    detail: str


def init_state(cfg: SpecConfig) -> State:
    full_view = (
        frozenset(range(cfg.n_replicas))
        if cfg.membership_deltas else frozenset()
    )
    lighthouses: Tuple[Lighthouse, ...] = ()
    if cfg.n_lighthouses >= 2:
        # boot with lighthouse 0 already elected at term 1 (every peer
        # voted for it) — the interesting space is what happens AFTER
        # the steady state, not the bootstrap election
        lighthouses = tuple(
            Lighthouse(
                status=(LEADER if i == 0 else FOLLOWER), term=1,
                voted_for=0, votes=frozenset(), log=(), commit_len=0,
                cell=0,
            )
            for i in range(cfg.n_lighthouses)
        )
    subaggs: Tuple[Subagg, ...] = ()
    if cfg.n_subaggs > 0:
        subaggs = tuple(
            Subagg(status=HEALTHY, owns=frozenset(
                i for i in range(cfg.n_replicas)
                if i % cfg.n_subaggs == s
            ))
            for s in range(cfg.n_subaggs)
        )
    return State(
        replicas=tuple(
            Replica(
                status=JOINING, step=0, lineage=(),
                joined=False, round=-1, voted=False, abstain=False,
                worked=False, diverged=False, healer=False, healed=False,
                epoch=-1,
                mview=0, view=full_view,
            )
            for _ in range(cfg.n_replicas)
        ),
        rounds=(), open_round=frozenset(), epoch=0, rounds_formed=0,
        crash_budget=cfg.crash_budget,
        respawn_budget=cfg.respawn_budget,
        corrupt_budget=cfg.corrupt_budget,
        commits=(), divergence_latched=False,
        lighthouses=lighthouses,
        lh_crash_budget=cfg.lh_crash_budget,
        lh_respawn_budget=cfg.lh_respawn_budget,
        partition_budget=cfg.partition_budget,
        subaggs=subaggs,
        subagg_budget=cfg.subagg_crash_budget,
    )


def _token(step: int, diverged: bool, epoch: int) -> str:
    """A commit token: the identity of the state a replica commits at a
    step. Epoch-tagged, because one round produces ONE agreed state —
    two rounds each committing the same step (a split brain) are two
    lineages even when both computes were clean. Within a round the tag
    is constant, so the divergence compare keys on the clean/corrupt
    prefix alone."""
    return f"{'x' if diverged else 'c'}{step}@e{epoch}"


def _commit_record(
    commits: Tuple[Tuple[int, Tuple[str, ...]], ...], step: int, token: str
) -> Tuple[Tuple[int, Tuple[str, ...]], ...]:
    out: List[Tuple[int, Tuple[str, ...]]] = []
    seen = False
    for s, toks in commits:
        if s == step:
            seen = True
            if token not in toks:
                toks = tuple(sorted(toks + (token,)))
        out.append((s, toks))
    if not seen:
        out.append((step, (token,)))
    return tuple(sorted(out))


def _replace(state: State, idx: int, rep: Replica, **kw) -> State:
    reps = state.replicas[:idx] + (rep,) + state.replicas[idx + 1:]
    return state._replace(replicas=reps, **kw)


def _set_round(state: State, rnd: Round) -> State:
    return state._replace(rounds=tuple(
        rnd if rd.rid == rnd.rid else rd for rd in state.rounds
    ))


def _live(state: State) -> List[int]:
    return [i for i, r in enumerate(state.replicas) if r.status != DEAD]


def _attached(state: State, rnd: Round, j: int) -> bool:
    return state.replicas[j].round == rnd.rid


# --- HA helpers ------------------------------------------------------------


def _lh_majority(cfg: SpecConfig) -> int:
    return cfg.n_lighthouses // 2 + 1


def _lh_live(state: State) -> List[int]:
    return [
        i for i, lh in enumerate(state.lighthouses) if lh.status != DEAD
    ]


def _live_leaders(state: State) -> List[int]:
    return [
        i for i, lh in enumerate(state.lighthouses)
        if lh.status == LEADER
    ]


def _set_lh(state: State, idx: int, lh: Lighthouse, **kw) -> State:
    lhs = state.lighthouses[:idx] + (lh,) + state.lighthouses[idx + 1:]
    return state._replace(lighthouses=lhs, **kw)


def _log_up_to_date(
    a: Tuple[Tuple[int, int], ...], b: Tuple[Tuple[int, int], ...]
) -> bool:
    """Raft §5.4.1: is log ``a`` at least as up-to-date as ``b``?
    (compare last entry's term, then length)"""
    la = a[-1][0] if a else 0
    lb = b[-1][0] if b else 0
    return la > lb or (la == lb and len(a) >= len(b))


def _mem_snapshot(
    mlog: Tuple[Tuple[int, int, bool], ...], version: int, n: int
) -> FrozenSet[int]:
    """The full membership snapshot at ``version``: the initial full
    set with every delta up to and including ``version`` applied in
    order — the reference the delta chain must be equivalent to."""
    view = set(range(n))
    for ver, rep, alive in mlog:
        if ver > version:
            break
        if alive:
            view.add(rep)
        else:
            view.discard(rep)
    return frozenset(view)


def _mem_bump(state: State, cfg: SpecConfig, rep: int,
              alive: bool) -> dict:
    """State-field updates for one membership change (crash/respawn of
    replica ``rep``): bump the version, append the delta."""
    if not cfg.membership_deltas:
        return {}
    v = state.mversion + 1
    return {"mversion": v, "mlog": state.mlog + ((v, rep, alive),)}


def _home(state: State, i: int) -> Optional[int]:
    """The sub-aggregator owning replica ``i`` (None = no tree)."""
    for s, sub in enumerate(state.subaggs):
        if i in sub.owns:
            return s
    return None


def enabled_actions(
    state: State, cfg: SpecConfig
) -> List[Tuple[str, State]]:
    """Every transition enabled in ``state``: the scheduler's menu. The
    crash action appears here like any other, so the DFS interleaves a
    crash at every transition point — exhaustive SIGKILL-anywhere."""
    out: List[Tuple[str, State]] = []
    live = _live(state)

    # -- crash: any live replica, at any point, while the budget lasts
    if state.crash_budget > 0:
        for i in live:
            r = state.replicas[i]
            # SIGKILL loses everything in memory: round membership and
            # the step in flight. The committed lineage survives (the
            # checkpoint).
            dead = r._replace(
                status=DEAD, joined=False, round=-1, voted=False,
                abstain=False, worked=False, diverged=False,
                healer=False, healed=False,
            )
            ns = _replace(
                state, i, dead,
                open_round=state.open_round - {i},
                crash_budget=state.crash_budget - 1,
                # a death is a membership change: the lighthouse bumps
                # the membership version and appends the delta
                **_mem_bump(state, cfg, i, alive=False),
            )
            out.append((f"crash({i})", ns))

    # -- respawn: a dead replica returns, state = its last commit
    if state.respawn_budget > 0:
        for i, r in enumerate(state.replicas):
            if r.status != DEAD:
                continue
            bump = _mem_bump(state, cfg, i, alive=True)
            rep = r._replace(status=JOINING)
            if cfg.membership_deltas:
                # a (re)join hands the replica the FULL membership
                # snapshot (the sublinear protocol's bootstrap path) —
                # deltas only flow to already-synced members
                v = bump["mversion"]
                rep = rep._replace(
                    mview=v,
                    view=_mem_snapshot(
                        bump["mlog"], v, cfg.n_replicas
                    ),
                )
            ns = _replace(
                state, i, rep,
                respawn_budget=state.respawn_budget - 1,
                **bump,
            )
            out.append((f"respawn({i})", ns))

    # -- join: a free live replica enters the lighthouse's open round
    if state.rounds_formed < cfg.max_rounds:
        for i in live:
            r = state.replicas[i]
            if r.joined or r.round >= 0:
                continue
            if state.subaggs:
                # two-level tree: the join goes through the replica's
                # sub-aggregator; a dead home blocks it until re-home
                home = _home(state, i)
                if home is None or state.subaggs[home].status == DEAD:
                    continue
            ns = _replace(
                state, i, r._replace(joined=True),
                open_round=state.open_round | {i},
            )
            out.append((f"join({i})", ns))

    # -- form: the open round becomes a quorum
    if state.open_round and state.rounds_formed < cfg.max_rounds:
        joined = state.open_round
        barrier_ok = (
            joined == frozenset(live)
            if cfg.join_barrier
            else len(joined) >= cfg.min_replicas
        )
        if barrier_ok:
            rid = state.rounds_formed
            epoch = state.epoch + 1
            # the round attempts the max committed step of its members;
            # members behind it heal first
            max_step = max(state.replicas[i].step for i in joined)
            reps = list(state.replicas)
            for i in joined:
                r = reps[i]
                behind = r.step < max_step
                reps[i] = r._replace(
                    joined=False, round=rid, voted=False, abstain=False,
                    worked=False, healer=behind, healed=False,
                    epoch=epoch,
                    status=(HEALING if behind else HEALTHY),
                )
            ns = state._replace(
                replicas=tuple(reps),
                rounds=state.rounds + (
                    Round(rid=rid, epoch=epoch, step=max_step,
                          members=joined, votes=(),
                          resolved=frozenset(), done=frozenset(),
                          mver=state.mversion),
                ),
                open_round=frozenset(),
                epoch=epoch,
                rounds_formed=rid + 1,
            )
            if not state.lighthouses:
                out.append((f"form(r{rid},step={max_step})", ns))
            else:
                # HA tier: the round is a quorum DECISION — it must go
                # through a leader, which appends the (term, rid) entry
                # to its durable log. Managers fail over via the peer
                # list, so ANY live leader serves — including a stale
                # minority-partitioned one (its appended entry can never
                # majority-commit while the fence holds; with the fence
                # off that is exactly the H2 counterexample).
                for li in _live_leaders(state):
                    lh = ns.lighthouses[li]
                    ns2 = _set_lh(
                        ns, li,
                        lh._replace(log=lh.log + ((lh.term, rid),)),
                    )
                    out.append(
                        (f"form(r{rid},step={max_step},lh={li})", ns2)
                    )

    # per-round member actions
    for rnd in state.rounds:
        for i in sorted(rnd.members):
            if i in rnd.resolved:
                continue
            r = state.replicas[i]
            if r.status == DEAD:
                continue

            # -- heal: copy state from an up-to-date round member that
            # has not voted yet (the serve happens at quorum time,
            # before the source's compute/vote — a voted source's
            # staged window is closed). The source serves its CURRENT
            # committed state (manager.py: "the received state dict is
            # authoritative ... never rewind below the state the bytes
            # actually encode").
            if r.round == rnd.rid and r.healer and not r.healed:
                sourced = False
                for j in sorted(rnd.members):
                    src = state.replicas[j]
                    if (
                        j == i or src.status == DEAD or src.healer
                        or src.round != rnd.rid or src.voted
                    ):
                        continue
                    sourced = True
                    healed = r._replace(
                        step=src.step, lineage=src.lineage,
                        healed=True, status=HEALING,
                    )
                    out.append(
                        (f"heal({i}<-{j})", _replace(state, i, healed))
                    )
                # -- heal_fail: transfers can fail (torn stream, source
                # shutdown): the healer latches the error and its barrier vote
                # abstains — its own step aborts, nobody else's does
                if not sourced and not r.voted:
                    ns = _replace(
                        state, i,
                        r._replace(voted=True, abstain=True),
                    )
                    ns = _set_round(
                        ns, rnd._replace(votes=rnd.votes + ((i, ""),))
                    )
                    out.append((f"heal_fail({i})", ns))

            # -- work: compute this round's reduction
            ready = (not r.healer) or r.healed
            if (
                r.round == rnd.rid and ready and not r.worked
                and not r.voted
            ):
                with_done = _set_round(
                    state, rnd._replace(done=rnd.done | {i})
                )
                ns = _replace(with_done, i, r._replace(worked=True))
                out.append((f"work({i})", ns))
                if state.corrupt_budget > 0 and not r.healer:
                    ns2 = _replace(
                        with_done, i,
                        r._replace(worked=True, diverged=True),
                        corrupt_budget=state.corrupt_budget - 1,
                    )
                    out.append((f"work_corrupt({i})", ns2))

            # -- vote: cast this round's commit vote (with the state
            # digest riding it — the token). The token's step is the
            # REPLICA's committed step at vote time (the vote RPC's
            # step), not the round label.
            # a commit vote must ride a membership view at least as new
            # as the one the round's quorum was computed against: with
            # the fence on, a lagging replica applies its pending deltas
            # (or snapshot-resyncs) before voting — the action is
            # disabled, not taken; with the fence off the vote goes out
            # stale and H3 flags it (the !stale label)
            stale_view = (
                cfg.membership_deltas and r.mview < rnd.mver
            )
            if (
                r.round == rnd.rid and r.worked and not r.voted
                and not (stale_view and cfg.stale_view_fence)
            ):
                token = _token(
                    r.step, r.diverged and not r.healer, rnd.epoch
                )
                tag = "!stale" if stale_view else ""
                ns = _replace(state, i, r._replace(voted=True))
                ns = _set_round(
                    ns, rnd._replace(votes=rnd.votes + ((i, token),))
                )
                out.append((f"vote({i}){tag}", ns))

            # -- resolve: this replica's vote decision lands. Commit is
            # arbitrated PER replica group; the divergence fence is the
            # only fleet-global wait (the cohort digest compare).
            if r.round == rnd.rid and r.voted:
                unresolved = [
                    j for j in rnd.members if j not in rnd.resolved
                ]
                accounted = all(
                    (not _attached(state, rnd, j))
                    or state.replicas[j].status == DEAD
                    or any(v[0] == j for v in rnd.votes)
                    for j in unresolved
                )
                if cfg.fence_divergence and not accounted:
                    continue  # fence: wait for the full cohort's digests
                out.append(_resolve(state, cfg, rnd, i))

    _ha_actions(state, cfg, out)
    return out


def _ha_actions(
    state: State, cfg: SpecConfig, out: List[Tuple[str, State]]
) -> None:
    """The HA-layer transitions: Raft lighthouse tier, membership
    deltas, sub-aggregator tree. All empty in a default config."""

    # ---- Raft lighthouse tier -------------------------------------------
    for li, lh in enumerate(state.lighthouses):
        if lh.status == DEAD:
            # -- lh_respawn: durable state (term/voted_for/log) intact,
            # volatile ballots gone; returns as a follower
            if state.lh_respawn_budget > 0:
                ns = _set_lh(
                    state, li,
                    lh._replace(status=FOLLOWER, votes=frozenset()),
                    lh_respawn_budget=state.lh_respawn_budget - 1,
                )
                out.append((f"lh_respawn({li})", ns))
            continue

        # -- lh_crash: SIGKILL a lighthouse; the log is durable
        if state.lh_crash_budget > 0:
            ns = _set_lh(
                state, li,
                lh._replace(status=DEAD, votes=frozenset()),
                lh_crash_budget=state.lh_crash_budget - 1,
            )
            out.append((f"lh_crash({li})", ns))

        # -- lh_campaign: a non-leader that sees no live leader in its
        # cell at its term or above starts an election one term up
        # (bounded by max_terms — election *liveness* is randomized-
        # timeout territory, out of the model's scope)
        if lh.status != LEADER and lh.term + 1 <= cfg.max_terms:
            leader_visible = any(
                o.status == LEADER and o.cell == lh.cell
                and o.term >= lh.term
                for oi, o in enumerate(state.lighthouses)
                if oi != li and o.status != DEAD
            )
            if not leader_visible:
                ns = _set_lh(state, li, lh._replace(
                    status=CANDIDATE, term=lh.term + 1, voted_for=li,
                    votes=frozenset({li}),
                ))
                out.append((f"lh_campaign({li},t{lh.term + 1})", ns))

        # -- lh_vote: grant a ballot to a live same-cell candidate.
        # Raft's two checks: one vote per term (persistent voted_for),
        # and the candidate's log must be at least as up-to-date.
        # ``raft_single_vote=False`` plants the double-vote bug.
        if lh.status == CANDIDATE:
            for vi, v in enumerate(state.lighthouses):
                if (
                    vi == li or v.status == DEAD or v.cell != lh.cell
                    or lh.term < v.term
                ):
                    continue
                already = v.voted_for >= 0 and v.term == lh.term
                if already and v.voted_for != li and cfg.raft_single_vote:
                    continue
                if v.voted_for == li and v.term == lh.term:
                    continue  # ballot already counted
                if not _log_up_to_date(lh.log, v.log):
                    continue
                granter = v._replace(
                    status=(FOLLOWER if v.status != DEAD else v.status),
                    term=lh.term, voted_for=li, votes=frozenset(),
                )
                ns = _set_lh(state, vi, granter)
                ns = _set_lh(
                    ns, li,
                    ns.lighthouses[li]._replace(
                        votes=lh.votes | {vi}
                    ),
                )
                out.append((f"lh_vote({vi}->{li},t{lh.term})", ns))

        # -- lh_elect: a candidate with a majority of ballots wins
        if (
            lh.status == CANDIDATE
            and len(lh.votes) >= _lh_majority(cfg)
        ):
            ns = _set_lh(state, li, lh._replace(status=LEADER))
            out.append((f"lh_elect({li},t{lh.term})", ns))

        # -- lh_append: a leader replicates its log to a live same-cell
        # peer at or below its term (full-prefix adoption — the
        # AppendEntries catch-up collapsed to one step; a stale leader
        # adopting a newer leader's log is Raft's log repair and steps
        # it down)
        if lh.status == LEADER:
            for fi, f in enumerate(state.lighthouses):
                if (
                    fi == li or f.status == DEAD or f.cell != lh.cell
                    or f.term > lh.term or f.log == lh.log
                ):
                    continue
                ns = _set_lh(state, fi, f._replace(
                    status=FOLLOWER, term=lh.term, log=lh.log,
                    votes=frozenset(),
                ))
                out.append((f"lh_append({fi}<-{li})", ns))

        # -- lh_commit: the leader advances its commit index over the
        # longest prefix a majority of lighthouses hold (logs are
        # durable, so a dead node's replicated prefix still counts).
        # ``stale_leader_fence=False`` plants the bug: the leader
        # commits its whole log with no majority check — a minority-
        # partitioned stale leader then "commits" decisions the next
        # leader never saw (H2).
        if lh.status == LEADER and lh.commit_len < len(lh.log):
            if cfg.stale_leader_fence:
                new_len = lh.commit_len
                for k in range(lh.commit_len + 1, len(lh.log) + 1):
                    holders = sum(
                        1 for o in state.lighthouses
                        if o.log[:k] == lh.log[:k]
                    )
                    if holders >= _lh_majority(cfg):
                        new_len = k
                    else:
                        break
            else:
                new_len = len(lh.log)
            if new_len > lh.commit_len:
                known = {
                    (et, rid) for _ct, et, rid in state.ha_committed
                }
                committed = state.ha_committed + tuple(
                    (lh.term, e[0], e[1])
                    for e in lh.log[lh.commit_len:new_len]
                    if e not in known
                )
                ns = _set_lh(
                    state, li, lh._replace(commit_len=new_len),
                    ha_committed=committed,
                )
                out.append((f"lh_commit({li},{new_len})", ns))

    # -- lh_partition / lh_unpartition: a network split that isolates
    # the current leader (the classic stale-leader scenario); healing
    # restores one cell
    if state.partition_budget > 0 and len(state.lighthouses) >= 3:
        for li in _live_leaders(state):
            if state.lighthouses[li].cell != 0:
                continue
            ns = _set_lh(
                state, li,
                state.lighthouses[li]._replace(cell=1),
                partition_budget=state.partition_budget - 1,
            )
            out.append((f"lh_partition({li})", ns))
    if any(lh.cell != 0 for lh in state.lighthouses):
        ns = state._replace(lighthouses=tuple(
            lh._replace(cell=0) for lh in state.lighthouses
        ))
        out.append(("lh_unpartition", ns))

    # ---- membership deltas ----------------------------------------------
    if cfg.membership_deltas and state.mversion > 0:
        for i, r in enumerate(state.replicas):
            if r.status == DEAD or r.mview >= state.mversion:
                continue
            if cfg.ordered_deltas:
                versions = (r.mview + 1,)
            else:
                # the planted bug: the transport reorders/drops, and the
                # replica applies whatever delta arrives next
                versions = tuple(
                    range(r.mview + 1, state.mversion + 1)
                )
            for v in versions:
                ver, rep, alive = state.mlog[v - 1]
                view = (r.view | {rep}) if alive else (r.view - {rep})
                ns = _replace(
                    state, i, r._replace(mview=v, view=view)
                )
                out.append((f"delta({i},v{v})", ns))
            if state.mversion - r.mview >= 2:
                # gap detected (a delta was lost): the sublinear
                # protocol falls back to the full snapshot
                ns = _replace(state, i, r._replace(
                    mview=state.mversion,
                    view=_mem_snapshot(
                        state.mlog, state.mversion, cfg.n_replicas
                    ),
                ))
                out.append((f"delta_snap({i})", ns))

    # ---- sub-aggregator tree --------------------------------------------
    if state.subaggs:
        live_subs = [
            s for s, sub in enumerate(state.subaggs)
            if sub.status != DEAD
        ]
        # -- sub_crash: the aggregator dies; its buffered (un-formed)
        # joins die with it — the owned members fall out of the open
        # round and must re-join once re-homed. Formed rounds are
        # untouched: the tree only fronts joins (H5's contract).
        if state.subagg_budget > 0 and len(live_subs) > 1:
            for s in live_subs:
                sub = state.subaggs[s]
                reps = tuple(
                    r._replace(joined=False) if i in sub.owns else r
                    for i, r in enumerate(state.replicas)
                )
                ns = state._replace(
                    replicas=reps,
                    open_round=state.open_round - sub.owns,
                    subaggs=tuple(
                        x._replace(status=DEAD) if j == s else x
                        for j, x in enumerate(state.subaggs)
                    ),
                    subagg_budget=state.subagg_budget - 1,
                )
                out.append((f"sub_crash({s})", ns))
        # -- sub_rehome: a dead aggregator's groups re-home onto the
        # first live one (deterministic — the lighthouse assigns)
        for s, sub in enumerate(state.subaggs):
            if sub.status != DEAD or not sub.owns or not live_subs:
                continue
            t = live_subs[0]
            subs = list(state.subaggs)
            subs[t] = subs[t]._replace(owns=subs[t].owns | sub.owns)
            subs[s] = sub._replace(owns=frozenset())
            ns = state._replace(subaggs=tuple(subs))
            out.append((f"sub_rehome({s}->{t})", ns))


def _resolve(
    state: State, cfg: SpecConfig, rnd: Round, i: int
) -> Tuple[str, State]:
    r = state.replicas[i]

    # a member that disappeared BEFORE its collective contribution
    # landed broke the survivors' allreduce: their ops errored, the
    # error latched, their steps abort. A member that died after
    # contributing (work done), cast its vote (incl. a failed-heal
    # abstention — its ranks still rode the plane with zeros), or
    # already resolved fails nobody — commits are per-group; the dead
    # group simply respawns behind and heals.
    lost = any(
        j not in rnd.done
        and j not in rnd.resolved
        and not any(v[0] == j for v in rnd.votes)
        and (state.replicas[j].status == DEAD
             or not _attached(state, rnd, j))
        for j in rnd.members
    )
    # the divergence fence: compare the cast digests within MY (epoch,
    # step) cohort — the lighthouse keys its compare on (epoch, step),
    # so votes for a different step never enter it; abstains ("")
    # complete the cohort but never enter the comparison
    my_step = state.replicas[i].step
    tokens = {
        t for _j, t in rnd.votes
        if t and t[1:].split("@", 1)[0] == str(my_step)
    }
    diverged = len(tokens) > 1
    latched = state.divergence_latched
    my_token = next((t for j, t in rnd.votes if j == i), "")
    commit = bool(my_token) and not r.abstain and not lost
    if diverged and cfg.fence_divergence:
        commit = False
        latched = True

    if commit:
        new_step = r.step + 1
        lineage = r.lineage + (my_token,)
        rep = r._replace(
            status=HEALTHY, step=new_step, lineage=lineage,
            round=-1, voted=False, abstain=False,
            worked=False, diverged=False, healer=False, healed=False,
        )
        commits = _commit_record(state.commits, r.step, my_token)
    else:
        rep = r._replace(
            status=HEALTHY, round=-1, voted=False, abstain=False,
            worked=False, diverged=False, healer=False,
            # an aborted heal is discarded with the step: the healer
            # stays behind until a committing round
            healed=False,
        )
        commits = state.commits

    ns = _replace(state, i, rep, commits=commits,
                  divergence_latched=latched)
    ns = _set_round(ns, rnd._replace(resolved=rnd.resolved | {i}))
    verdict = "commit" if commit else "abort"
    return (f"resolve({i},r{rnd.rid},{verdict})", ns)


# ---------------------------------------------------------------------------
# invariants
# ---------------------------------------------------------------------------


def check_state(
    state: State, cfg: SpecConfig, action: str = ""
) -> List[Invariant]:
    """Safety invariants, checked at every visited state."""
    out: List[Invariant] = []

    # I1: at most one committed lineage per step, fleet-wide
    for step, tokens in state.commits:
        if len(tokens) > 1:
            out.append(Invariant(
                "I1-unique-commit",
                f"step {step} committed {len(tokens)} distinct lineages "
                f"{list(tokens)} — split brain or silently diverged "
                "commit",
            ))

    # I4: one lineage token per committed step
    for i, r in enumerate(state.replicas):
        if r.status == DEAD:
            continue
        if len(r.lineage) != r.step:
            out.append(Invariant(
                "I4-lineage-length",
                f"replica {i}: lineage length {len(r.lineage)} != "
                f"committed step {r.step}",
            ))

    # I5: a DETECTED divergence never commits while the fence is armed.
    # (A single-member cohort committing a corrupt state is invisible to
    # any digest compare — the sentinel's contract, like the real one's,
    # covers disagreement, which needs two states to disagree.)
    if cfg.fence_divergence:
        for step, tokens in state.commits:
            if len(tokens) > 1 and any(t.startswith("x") for t in tokens):
                out.append(Invariant(
                    "I5-diverged-commit",
                    f"step {step} committed disagreeing tokens "
                    f"{list(tokens)} with the divergence fence armed — "
                    "the cohort compare must have vetoed this",
                ))

    # I2: epochs only increment (structural in the model; the
    # conformance checker enforces it on real trails)
    for i, r in enumerate(state.replicas):
        if r.epoch > state.epoch:
            out.append(Invariant(
                "I2-epoch-monotonic",
                f"replica {i} observed epoch {r.epoch} beyond the "
                f"lighthouse's {state.epoch}",
            ))

    # ---- HA invariants (ISSUE 20) --------------------------------------

    # H1: at most one live leader per term (Raft election safety)
    if state.lighthouses:
        by_term: dict = {}
        for li, lh in enumerate(state.lighthouses):
            if lh.status == LEADER:
                by_term.setdefault(lh.term, []).append(li)
        for term, leaders in sorted(by_term.items()):
            if len(leaders) > 1:
                out.append(Invariant(
                    "H1-one-leader-per-term",
                    f"term {term} has {len(leaders)} live leaders "
                    f"{leaders} — split-brain election (a voter granted "
                    "two ballots in one term)",
                ))

        # H2: Raft Leader Completeness over quorum decisions — a
        # decision committed in term T must be present in every live
        # leader of term >= T (a STALE lower-term leader legally lacks
        # newer entries; the majority-commit fence keeps it impotent)
        for li in _live_leaders(state):
            lh = state.lighthouses[li]
            for ct, et, rid in state.ha_committed:
                if lh.term >= ct and (et, rid) not in lh.log:
                    out.append(Invariant(
                        "H2-committed-survives",
                        f"leader {li} (term {lh.term}) is missing "
                        f"decision ({et}, r{rid}) committed in term "
                        f"{ct} — a committed quorum decision was lost "
                        "across a leader change (stale-leader commit)",
                    ))

    # H3: a commit vote rode a membership view older than the round's
    # (action-labelled — the !stale tag marks the transition)
    if action.startswith("vote(") and action.endswith("!stale"):
        out.append(Invariant(
            "H3-stale-view-commit",
            f"{action}: the commit vote rode a membership view older "
            "than the version the round's quorum was computed against "
            "— the stale-view fence must hold the vote until the "
            "replica's deltas catch up",
        ))

    # H4: delta-chain equivalence — the incrementally-applied view must
    # equal the full snapshot at the replica's version
    if cfg.membership_deltas:
        for i, r in enumerate(state.replicas):
            if r.status == DEAD:
                continue
            want = _mem_snapshot(state.mlog, r.mview, cfg.n_replicas)
            if r.view != want:
                out.append(Invariant(
                    "H4-delta-chain",
                    f"replica {i} at membership v{r.mview} holds view "
                    f"{sorted(r.view)} but the snapshot at v{r.mview} "
                    f"is {sorted(want)} — the delta stream was applied "
                    "out of order (delta-chain equivalence broken)",
                ))

    # H5: formed rounds carry globally unique epochs — a sub-aggregator
    # crash/re-home must never split a group's epoch plane
    seen_epochs: dict = {}
    for rnd in state.rounds:
        if rnd.epoch in seen_epochs:
            out.append(Invariant(
                "H5-epoch-unique",
                f"rounds r{seen_epochs[rnd.epoch]} and r{rnd.rid} both "
                f"carry epoch {rnd.epoch} — the epoch plane split",
            ))
        else:
            seen_epochs[rnd.epoch] = rnd.rid

    return out


def is_terminal(state: State, cfg: SpecConfig) -> bool:
    return not enabled_actions(state, cfg)


def check_terminal(state: State, cfg: SpecConfig) -> List[Invariant]:
    """Liveness-ish: a terminal state with a quorum's worth of live
    replicas must have committed something."""
    live = _live(state)
    if state.lighthouses and not _live_leaders(state):
        # no live leader in a terminal state: the election deadlocked
        # inside the term bound (two candidates splitting the vote
        # forever). Raft breaks these with randomized timeouts — a
        # probabilistic liveness argument a bounded nondeterministic
        # model cannot make, so these terminals are exempt from L (the
        # checker proves election SAFETY, not election progress).
        return []
    if len(live) >= cfg.min_replicas and cfg.max_rounds > 0:
        if not state.commits:
            return [Invariant(
                "L-liveness",
                f"terminal state with {len(live)} live replicas "
                f"(min_replicas={cfg.min_replicas}) committed nothing "
                f"in {cfg.max_rounds} rounds",
            )]
    return []
