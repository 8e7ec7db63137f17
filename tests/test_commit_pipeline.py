"""Pipelined-commit unit tests (ISSUE 3 tentpole).

Manager-level: the async vote lifecycle (issue → overlap → resolve),
veto/rollback bookkeeping, the speculation gates (healing replica never
speculates, errored latch, death-watch re-quorum mid-speculation), and
the misuse guards.

Trainer-level: bit-identical committed ``(params, opt_state)`` parity
between pipelined and sync mode over a schedule that includes a
group-wide veto (rollback + batch replay) and a mid-run data-plane
``PeerGoneError`` (the failed-op face of a peer dying) — the
fault-injection acceptance check.
"""

import hashlib
import threading
from datetime import timedelta
from unittest.mock import MagicMock, patch

import numpy as np
import pytest

from torchft_tpu import telemetry
from torchft_tpu.collectives import CollectivesDummy, PeerGoneError
from torchft_tpu.coordination import QuorumResult
from torchft_tpu.manager import (
    MANAGER_ADDR_KEY,
    REPLICA_ID_KEY,
    Manager,
)
from torchft_tpu.store import StoreClient, StoreServer


def quorum_result(
    quorum_id=123,
    replica_rank=1,
    replica_world_size=2,
    heal=False,
    max_step=20,
    max_rank=None,
    max_world_size=2,
    recover_src_rank=None,
    recover_dst_ranks=(),
    participant_ids=(),
):
    q = QuorumResult()
    q.quorum_id = quorum_id
    q.replica_rank = replica_rank
    q.replica_world_size = replica_world_size
    q.recover_src_manager_address = "manager address"
    q.recover_src_rank = recover_src_rank
    q.recover_dst_ranks = list(recover_dst_ranks)
    q.store_address = "store_addr/prefix"
    q.max_step = max_step
    q.max_rank = max_rank
    q.max_world_size = max_world_size
    q.heal = heal
    q.participant_ids = list(participant_ids)
    return q


@pytest.fixture
def store_server():
    s = StoreServer()
    yield s
    s.shutdown()


class ManagerHarness:
    def __init__(self, store_server, collectives=None, **kwargs):
        self.store = StoreClient(store_server.address())
        self.store.set(MANAGER_ADDR_KEY, "dummy")
        self.store.set(REPLICA_ID_KEY, "dummy_id")
        self.collectives = collectives or CollectivesDummy(rank=0, world_size=1)
        self.load_state_dict = MagicMock()
        self.transport = MagicMock()
        self.transport.metadata.return_value = "transport_meta"
        # the striped heal path prefers recv_checkpoint_multi when the
        # transport has one (a MagicMock always does) — delegate to the
        # recv_checkpoint.return_value contract the tests configure
        self.transport.recv_checkpoint_multi.side_effect = (
            lambda *a, **k: self.transport.recv_checkpoint.return_value
        )
        kwargs.setdefault("min_replica_size", 2)
        kwargs.setdefault("timeout", timedelta(seconds=10))
        kwargs.setdefault("commit_pipeline", True)
        # patch stays active for the harness lifetime: the pipelined vote
        # path constructs a dedicated commit ManagerClient (and the
        # healing path one for the recovery source) — autospec returns the
        # same mock instance for every construction, so scripted votes on
        # self.client drive the async path too
        self._patcher = patch("torchft_tpu.manager.ManagerClient", autospec=True)
        self._patcher.start()
        self.manager = Manager(
            collectives=self.collectives,
            load_state_dict=self.load_state_dict,
            state_dict=lambda: {"user_key": 1},
            rank=1,
            world_size=2,
            store_addr=store_server.address(),
            checkpoint_transport=self.transport,
            **kwargs,
        )
        self.client = self.manager._client

    def shutdown(self):
        self.manager.shutdown(wait=False)
        self._patcher.stop()


@pytest.fixture
def harness(store_server):
    hs = []

    def make(**kwargs):
        h = ManagerHarness(store_server, **kwargs)
        hs.append(h)
        return h

    yield make
    for h in hs:
        h.shutdown()


def test_pipelined_happy_path(harness):
    h = harness()
    m = h.manager
    h.client._quorum.return_value = quorum_result(max_rank=1)
    h.client.should_commit.return_value = True
    rollbacks0 = telemetry.COMMIT_PIPELINE_ROLLBACKS.value

    m.start_quorum()
    t = np.array([2.0, 4.0], dtype=np.float32)
    m.allreduce(t).wait()
    assert m.speculation_allowed()

    resolved = []
    fut = m.should_commit_async(on_resolved=resolved.append)
    assert m.pending_commit() is fut
    # issue-time disallow: the serving window closes before the overlap
    h.transport.disallow_checkpoint.assert_called_once()
    assert not m.speculation_allowed()  # at most one outstanding

    assert m.resolve_pending_commit() is True
    assert resolved == [True]
    assert m.pending_commit() is None
    assert m.current_step() == 1
    assert m.batches_committed() == 2
    assert telemetry.COMMIT_PIPELINE_ROLLBACKS.value == rollbacks0
    # vote went through the dedicated commit client (same mock object)
    h.client.should_commit.assert_called_once()


def test_veto_rolls_back(harness):
    h = harness()
    m = h.manager
    h.client._quorum.return_value = quorum_result(max_rank=1)
    h.client.should_commit.return_value = False  # a peer rank vetoed
    rollbacks0 = telemetry.COMMIT_PIPELINE_ROLLBACKS.value

    m.start_quorum()
    m.allreduce(np.ones(2, dtype=np.float32)).wait()
    resolved = []
    m.should_commit_async(on_resolved=resolved.append)
    assert m.resolve_pending_commit() is False
    assert resolved == [False]  # restore callback ran
    assert m.current_step() == 0  # nothing committed
    assert telemetry.COMMIT_PIPELINE_ROLLBACKS.value == rollbacks0 + 1
    events = telemetry.EVENTS.recent("commit_rollback")
    assert events and events[-1]["step"] == 0


def test_vote_rpc_failure_restores_and_raises(harness):
    h = harness()
    m = h.manager
    h.client._quorum.return_value = quorum_result(max_rank=1)
    h.client.should_commit.side_effect = TimeoutError("vote lost")

    m.start_quorum()
    m.allreduce(np.ones(2, dtype=np.float32)).wait()
    resolved = []
    m.should_commit_async(on_resolved=resolved.append)
    with pytest.raises(TimeoutError, match="vote lost"):
        m.resolve_pending_commit()
    # the step counts as not applied (sync parity): snapshot restored,
    # pending cleared so the manager is not wedged
    assert resolved == [False]
    assert m.pending_commit() is None
    assert m.current_step() == 0


def test_healing_replica_never_speculates(harness):
    h = harness(use_async_quorum=True)
    m = h.manager
    h.client._quorum.return_value = quorum_result(
        heal=True, max_step=20, max_rank=None, recover_src_rank=0
    )
    h.transport.recv_checkpoint.return_value = {
        "user": {"recovered": True},
        "torchft": {"step": 20, "batches_committed": 40},
    }

    m.start_quorum()
    m.wait_quorum()
    assert m._healing
    assert not m.speculation_allowed()
    with pytest.raises(AssertionError, match="healing"):
        m.should_commit_async()
    # the sync path still works and lands the staged heal
    h.client.should_commit.side_effect = None
    h.client.should_commit.return_value = True
    assert m.should_commit()
    h.load_state_dict.assert_called_once_with({"recovered": True})
    assert m.current_step() == 21


def test_errored_latch_blocks_speculation_and_aborts_cleanly(harness):
    h = harness()
    m = h.manager
    h.client._quorum.return_value = quorum_result(max_rank=1)
    # group decision echoes the local vote
    h.client.should_commit.side_effect = (
        lambda rank, step, vote, timeout=None, **kw: vote
    )

    # clean step k: speculate
    m.start_quorum()
    m.allreduce(np.ones(2, dtype=np.float32)).wait()
    m.should_commit_async()

    # step k+1: an error latches DURING the speculative window
    m.start_quorum()
    m.report_error(RuntimeError("plane torn"))
    # the pending vote (snapshotted clean at issue time) still commits
    assert m.resolve_pending_commit() is True
    assert m.current_step() == 1
    # the CURRENT step is doomed: no speculation, sync vote aborts
    assert not m.speculation_allowed()
    assert not m.should_commit()
    assert m.current_step() == 1


def test_allreduce_guard_while_pending(harness):
    h = harness()
    m = h.manager
    h.client._quorum.return_value = quorum_result(max_rank=1)
    h.client.should_commit.return_value = True

    m.start_quorum()
    m.allreduce(np.ones(2, dtype=np.float32)).wait()
    m.should_commit_async()
    m.start_quorum()
    with pytest.raises(RuntimeError, match="resolve_pending_commit"):
        m.allreduce(np.ones(2, dtype=np.float32))
    m.resolve_pending_commit()


def test_should_commit_resolves_stray_pending(harness):
    # LocalSGD-style callers vote synchronously; a stray pending vote from
    # a mixed-paradigm caller is resolved first instead of wedging
    h = harness()
    m = h.manager
    h.client._quorum.return_value = quorum_result(max_rank=1)
    h.client.should_commit.return_value = True

    m.start_quorum()
    m.allreduce(np.ones(2, dtype=np.float32)).wait()
    m.should_commit_async()
    m.start_quorum()
    assert m.should_commit()  # resolves the pending vote, then votes
    assert m.pending_commit() is None
    assert m.current_step() == 2
    assert h.client.should_commit.call_count == 2


def test_deathwatch_requorum_mid_speculation_vetoes_step(harness):
    """A death-watch re-quorum lands while a vote is in flight: the
    pending vote (issue-time snapshot) commits untouched; the step whose
    ops then span two plane epochs is vetoed by the mixed-epoch guard."""
    h = harness(min_replica_size=1)
    m = h.manager
    h.client.should_commit.side_effect = (
        lambda rank, step, vote, timeout=None, **kw: vote
    )
    ids = ["replica_a", "replica_b"]
    h.client._quorum.side_effect = [
        quorum_result(quorum_id=123, max_rank=1, participant_ids=ids),
        # step 1's own quorum: same epoch (steady state) ...
        quorum_result(quorum_id=123, max_rank=1, participant_ids=ids),
        # ... then the death-watch early re-quorum delivers the shrink
        quorum_result(quorum_id=124, max_rank=1, participant_ids=["replica_a"]),
    ]

    # step 0: clean, speculate (vote rides a barrier we control so the
    # re-quorum demonstrably lands DURING the speculative window)
    gate = threading.Event()
    real_vote = h.client.should_commit.side_effect

    def gated_vote(rank, step, vote, timeout=None, **kw):
        gate.wait(5)
        return real_vote(rank, step, vote, timeout=timeout)

    h.client.should_commit.side_effect = gated_vote
    m.start_quorum()
    m.allreduce(np.ones(2, dtype=np.float32)).wait()
    m.should_commit_async()

    # step 1 begins; first op rides epoch 123
    m.start_quorum()
    m.wait_quorum()
    assert m._quorum_id == 123
    # ... vote still in flight; resolve before this step's collectives
    gate.set()
    assert m.resolve_pending_commit() is True
    assert m.current_step() == 1
    m.allreduce(np.ones(2, dtype=np.float32)).wait()

    # death watch: peer's socket died mid-step -> early re-quorum
    m._on_peer_death(1)
    m.wait_quorum()
    assert m._quorum_id == 124  # plane rebuilt under the doomed step
    # a later op of the SAME step rides the new epoch -> mixed
    m.allreduce(np.ones(2, dtype=np.float32)).wait()
    assert not m.speculation_allowed()
    assert not m.should_commit()
    assert m.current_step() == 1
    aborts = telemetry.EVENTS.recent("abort")
    assert aborts and aborts[-1]["mixed_epochs"] is True


def test_managed_optimizer_pipelined_rollback_replay(harness):
    import jax.numpy as jnp
    import optax

    from torchft_tpu.optim import ManagedOptimizer

    h = harness()
    m = h.manager
    h.client._quorum.return_value = quorum_result(max_rank=1)
    votes = {"n": 0}

    def vote_fn(rank, step, vote, timeout=None, **kw):
        votes["n"] += 1
        return vote and votes["n"] != 2  # veto the 2nd vote

    h.client.should_commit.side_effect = vote_fn

    opt = ManagedOptimizer(m, optax.sgd(0.1))
    opt.init({"w": jnp.ones(4, jnp.float32)})

    def grad_fn(params):
        return {"w": jnp.ones(4, jnp.float32)}

    for _ in range(4):
        opt.begin_step()
        grads = grad_fn(opt.params)
        opt.step(grads, grad_fn=grad_fn)
    opt.finish()

    assert opt.rollbacks == 1
    assert m.current_step() == 3  # 4 votes, one vetoed
    # sgd(0.1) on grads averaged over n=2 participants: 3 * 0.1 * 0.5
    np.testing.assert_allclose(
        np.asarray(opt.params["w"]), np.full(4, 0.85, np.float32), rtol=1e-6
    )


def test_heal_supersedes_pending_replay(harness):
    """A heal that lands after an out-of-band rollback must clear the
    sticky replay flag: the next step's gradients are computed on the
    healed (committed) state, so replaying/dropping them would lose a
    valid batch."""
    import jax.numpy as jnp
    import optax

    from torchft_tpu.optim import ManagedOptimizer

    h = harness()
    opt = ManagedOptimizer(h.manager, optax.sgd(0.1))
    opt.init({"w": jnp.ones(4, jnp.float32)})

    # an out-of-band resolution (e.g. LocalSGD.sync on a pipelined
    # manager) rolled a speculative step back...
    opt._replay_needed = True
    # ...then a heal installs committed state before the next step
    opt.load_state_dict(
        {"params": {"w": jnp.zeros(4, jnp.float32)}, "opt_state": opt._opt_state}
    )
    assert not opt._consume_replay()


def test_diloco_rejects_pipelined_manager(harness):
    import optax

    from torchft_tpu.local_sgd import DiLoCo

    h = harness(use_async_quorum=False)
    with pytest.raises(ValueError, match="commit_pipeline"):
        DiLoCo(h.manager, optax.sgd(0.1), sync_every=2)


# ---------------------------------------------------------------------------
# trainer parity: pipelined committed state is bit-identical to sync mode
# ---------------------------------------------------------------------------


class FaultyDummy(CollectivesDummy):
    """CollectivesDummy that raises PeerGoneError on scripted allreduce
    calls — the failed-op face of a peer dying mid-step."""

    def __init__(self, fault_calls, **kwargs):
        super().__init__(**kwargs)
        self.fault_calls = set(fault_calls)
        self.calls = 0

    def allreduce(self, arrays, op=None, divisor=1):
        self.calls += 1
        if self.calls in self.fault_calls:
            raise PeerGoneError(0, f"peer 0 died mid-op (call {self.calls})")
        return super().allreduce(arrays, divisor=divisor)


def _tree_checksum(tree) -> str:
    import jax

    h = hashlib.sha256()
    for leaf in jax.tree_util.tree_leaves(tree):
        h.update(np.asarray(leaf).tobytes())
    return h.hexdigest()


class TestTrainerParity:
    STEPS = 5
    VETO_VOTES = {2}  # 1-based vote index vetoed group-wide
    FAULT_CALLS = {4}  # 1-based backend-allreduce index that dies

    @pytest.fixture(scope="class")
    def train_step(self):
        import jax.numpy as jnp
        import optax

        from torchft_tpu.models.transformer import TransformerConfig
        from torchft_tpu.parallel.mesh import MeshConfig, make_mesh
        from torchft_tpu.parallel.train_step import TrainStep

        cfg = TransformerConfig(
            vocab_size=32,
            d_model=16,
            n_layers=1,
            n_heads=2,
            head_dim=8,
            d_ff=32,
            dtype=jnp.float32,
        )
        # one shared TrainStep: both variants reuse the same jit caches
        # (identical compiled programs — any state divergence is real)
        return TrainStep(cfg, optax.adam(1e-2), make_mesh(MeshConfig(dp=1)))

    def _run(self, store_server, train_step, pipelined: bool):
        import jax
        import jax.numpy as jnp

        from torchft_tpu.parallel.ft import FTTrainer

        h = ManagerHarness(
            store_server,
            collectives=FaultyDummy(
                self.FAULT_CALLS, rank=0, world_size=1
            ),
            commit_pipeline=pipelined,
        )
        try:
            m = h.manager
            h.client._quorum.return_value = quorum_result(max_rank=1)
            votes = {"n": 0}

            def vote_fn(rank, step, vote, timeout=None, **kw):
                votes["n"] += 1
                return vote and votes["n"] not in self.VETO_VOTES

            h.client.should_commit.side_effect = vote_fn

            trainer = FTTrainer(m, train_step)
            trainer.init(jax.random.PRNGKey(0))
            data_rng = np.random.default_rng(7)
            batches = [
                jnp.asarray(
                    data_rng.integers(0, 32, (2, 4)), jnp.int32
                )
                for _ in range(self.STEPS)
            ]
            for tokens in batches:
                trainer.step(tokens)
            if pipelined:
                trainer.finish()
            return (
                _tree_checksum(trainer.params),
                _tree_checksum(trainer.opt_state),
                m.current_step(),
                votes["n"],
                trainer.rollbacks,
            )
        finally:
            h.shutdown()

    def test_committed_state_bit_identical(self, store_server, train_step):
        """Veto (rollback + replay) and a mid-run PeerGoneError leave the
        pipelined run's committed (params, opt_state) checksums exactly
        equal to sync mode's — the fault-injection acceptance check."""
        p_params, p_opt, p_step, p_votes, p_rb = self._run(
            store_server, train_step, pipelined=True
        )
        s_params, s_opt, s_step, s_votes, s_rb = self._run(
            store_server, train_step, pipelined=False
        )
        assert p_votes == s_votes == self.STEPS  # one vote per step
        assert p_step == s_step == self.STEPS - len(
            self.VETO_VOTES | self.FAULT_CALLS
        )
        assert p_rb >= 1 and s_rb == 0  # the veto really exercised rollback
        assert p_params == s_params
        assert p_opt == s_opt

    def test_heal_supersedes_pending_replay(self, store_server, train_step):
        """FTTrainer.load_state_dict (the heal path) must clear both the
        snapshot AND the sticky replay flag — see the ManagedOptimizer
        twin above."""
        import jax

        from torchft_tpu.parallel.ft import FTTrainer

        h = ManagerHarness(store_server, commit_pipeline=True)
        try:
            trainer = FTTrainer(h.manager, train_step)
            trainer.init(jax.random.PRNGKey(0))
            trainer._replay_needed = True
            trainer._snapshot = (trainer.params, trainer.opt_state)
            trainer.load_state_dict(
                {"params": trainer.params, "opt_state": trainer.opt_state}
            )
            assert trainer._snapshot is None
            assert not trainer._consume_replay()
        finally:
            h.shutdown()
