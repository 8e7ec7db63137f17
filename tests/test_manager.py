"""Manager runtime unit tests.

Ports the reference's mock-driven Manager coverage
(torchft/manager_test.py): handcrafted QuorumResults driven through
start_quorum / allreduce / should_commit with a patched ManagerClient and a
dummy data plane.
"""

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
    WorldSizeMode,
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
    recover_src_addresses=(),
    heal_pending=False,
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
    q.recover_src_addresses = list(recover_src_addresses)
    q.heal_pending = heal_pending or heal or bool(recover_dst_ranks)
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
        # patch stays active for the harness lifetime: the healing path
        # constructs a second ManagerClient for the recovery source
        # (a second harness alive at once shares the patch and gets a
        # client double of its own below)
        import torchft_tpu.manager as manager_mod

        self._patcher = None
        if not isinstance(manager_mod.ManagerClient, MagicMock):
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
        if self._patcher is None:
            self.manager._client = MagicMock()
        self.client = self.manager._client

    def shutdown(self):
        self.manager.shutdown(wait=False)
        if self._patcher is not None:
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


def test_state_dict(harness):
    m = harness().manager
    assert m.state_dict() == {"step": 0, "batches_committed": 0}
    m.load_state_dict({"step": 1234, "batches_committed": 2345})
    assert m.current_step() == 1234
    assert m.batches_committed() == 2345


def test_user_state_dict(harness):
    h = harness()
    assert h.manager._manager_state_dict() == {
        "user": {"user_key": 1},
        "torchft": {"step": 0, "batches_committed": 0},
    }
    h.manager.set_state_dict_fns(h.load_state_dict, lambda: {"new_state": 1})
    assert h.manager._manager_state_dict()["user"] == {"new_state": 1}


def test_participation_queries_before_first_quorum(harness):
    # must not assert-crash pre-quorum (round-1 review weak #3): a trainer
    # may log participation before its first start_quorum
    m = harness().manager
    assert m.num_participants() == 0
    assert m.participating_rank() is None
    assert not m.is_participating()


def test_quorum_happy(harness):
    h = harness()
    m = h.manager
    h.client._quorum.return_value = quorum_result(max_rank=1)

    assert m._quorum_id == -1
    assert m.current_step() == 0

    m.start_quorum()
    t = np.array([1.0, 2.0], dtype=np.float32)
    m.allreduce(t).wait()
    np.testing.assert_allclose(t, [0.5, 1.0])  # divided by num_participants=2

    h.client.should_commit.return_value = True
    assert m.should_commit()
    assert m._quorum_id == 123
    assert m.current_step() == 1
    assert m.batches_committed() == 2
    assert h.collectives.configure_count == 1
    h.transport.disallow_checkpoint.assert_called_once()

    # same quorum id -> no reconfigure
    m.start_quorum()
    assert m.should_commit()
    assert h.collectives.configure_count == 1


def test_quorum_heal_sync(harness):
    h = harness(use_async_quorum=False)
    m = h.manager
    h.client._quorum.return_value = quorum_result(
        heal=True, max_step=20, recover_src_rank=0
    )
    h.transport.recv_checkpoint.return_value = {
        "user": {"recovered": True},
        "torchft": {"step": 20, "batches_committed": 0},
    }

    m.start_quorum()
    # sync quorum heals eagerly: state applied before returning
    assert not m._healing
    h.load_state_dict.assert_called_once_with({"recovered": True})
    assert m.current_step() == 20
    assert m.is_participating()

    h.client.should_commit.return_value = True
    assert m.should_commit()
    assert m.current_step() == 21


def test_quorum_heal_async_zeroes_contribution(harness):
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
    assert not m.is_participating()
    assert m.participating_rank() is None

    t = np.ones(4, dtype=np.float32)
    m.allreduce(t).wait()
    np.testing.assert_allclose(t, 0)  # healing replica contributes zeros

    h.client.should_commit.return_value = True
    assert m.should_commit()
    h.load_state_dict.assert_called_once_with({"recovered": True})
    assert m.current_step() == 21
    # batches_committed advances by participants (2) from the restored 40
    assert m.batches_committed() == 42


def test_quorum_send_checkpoint(harness):
    h = harness()
    m = h.manager
    h.client._quorum.return_value = quorum_result(
        max_rank=1, recover_dst_ranks=(0,), max_step=7
    )
    m.start_quorum()
    m.wait_quorum()
    h.transport.send_checkpoint.assert_called_once()
    kwargs = h.transport.send_checkpoint.call_args.kwargs
    assert kwargs["dst_ranks"] == [0]
    assert kwargs["step"] == 7
    assert kwargs["state_dict"]["user"] == {"user_key": 1}


def test_stripe_source_stages_without_assigned_healer(harness):
    # ISSUE 9: when ANYONE heals this round (heal_pending), every
    # up-to-date member stages — not just the round-robin-assigned
    # sources — so the healer can pull a stripe from each of them
    h = harness()
    m = h.manager
    h.client._quorum.return_value = quorum_result(
        max_rank=1, recover_dst_ranks=(), heal_pending=True, max_step=7,
        recover_src_addresses=("a0", "a1"),
    )
    m.start_quorum()
    m.wait_quorum()
    h.transport.send_checkpoint.assert_called_once()
    assert h.transport.send_checkpoint.call_args.kwargs["dst_ranks"] == []


def test_stripe_source_staging_respects_single_source_knob(harness, monkeypatch):
    monkeypatch.setenv("TORCHFT_HEAL_SOURCES", "1")
    h = harness()
    m = h.manager
    h.client._quorum.return_value = quorum_result(
        max_rank=1, recover_dst_ranks=(), heal_pending=True, max_step=7,
        recover_src_addresses=("a0", "a1"),
    )
    m.start_quorum()
    m.wait_quorum()
    h.transport.send_checkpoint.assert_not_called()


def test_heal_uses_multi_source_with_cohort(harness):
    # the healer resolves the whole max-step cohort (primary first) and
    # hands the transport the multi-source list + the header warmup hook
    h = harness()
    m = h.manager
    h.client._quorum.return_value = quorum_result(
        heal=True, max_step=20, recover_src_rank=0,
        recover_src_addresses=("manager address", "peer2 address"),
    )
    h.transport.recv_checkpoint.return_value = {
        "user": {"recovered": True},
        "torchft": {"step": 20, "batches_committed": 0},
    }
    h.transport.last_heal_stats = {
        "stages": {"meta_s": 0.25, "recv_s": 0.5}, "streams": 2,
    }
    m.start_quorum()
    m.wait_quorum()
    assert m._healing
    call = h.transport.recv_checkpoint_multi.call_args
    sources = call.args[0]
    assert len(sources) == 2  # both cohort members' metadata resolved
    assert call.kwargs["header_cb"] is not None
    # the heal's own account rides its heal_end, where the benchmark's
    # bootstrap_heal_s / heal_meta_s / heal_recv_s / heal_fetch_streams
    # read it (benchmark/heal_stats.py)
    (*_, ended) = telemetry.EVENTS.recent("heal_end")
    assert ended["step"] == 20 and isinstance(ended["duration_s"], float)
    assert ended["heal_stats"] == h.transport.last_heal_stats


def test_commit_trail_recorded_at_step_boundaries(harness, monkeypatch):
    # TORCHFT_HEAL_DIFF=1: the Manager digests the committed state at
    # every start_quorum and shares the trail with the transport (the
    # differential heal's server half)
    monkeypatch.setenv("TORCHFT_HEAL_DIFF", "1")
    h = harness()
    m = h.manager
    assert m._heal_trail is not None
    assert h.transport.commit_trail is m._heal_trail
    h.client._quorum.return_value = quorum_result(max_rank=1)
    m.start_quorum()
    assert m._heal_trail.steps() == [0]
    h.client.should_commit.return_value = True
    assert m.should_commit()
    m.start_quorum()
    assert m._heal_trail.steps() == [0, 1]


def test_heal_warmup_hook_fires_with_spec_tree(harness):
    import threading

    from torchft_tpu.checkpointing.serialization import flatten_state

    h = harness()
    m = h.manager
    seen = []
    done = threading.Event()

    def warmup(spec):
        seen.append(spec)
        done.set()

    m.set_heal_warmup(warmup)
    header, _ = flatten_state({"w": np.zeros((3, 2), np.float32)})
    m._heal_header_cb(header)
    assert done.wait(5.0)
    assert seen[0]["w"].shape == (3, 2)


def test_error_latching(harness):
    h = harness()
    m = h.manager
    h.client._quorum.return_value = quorum_result(max_rank=1)
    m.start_quorum()

    m.report_error(RuntimeError("boom"))
    t = np.ones(2, dtype=np.float32)
    m.allreduce(t).wait()
    np.testing.assert_allclose(t, 1.0)  # untouched no-op

    h.client.should_commit.return_value = False
    assert not m.should_commit()
    assert m.current_step() == 0

    # next quorum clears the error
    m.start_quorum()
    assert m.errored() is None


def test_allreduce_error_latches(harness):
    h = harness()
    m = h.manager
    h.client._quorum.return_value = quorum_result(max_rank=1)
    m.start_quorum()

    h.collectives.allreduce = MagicMock(side_effect=RuntimeError("net down"))
    t = np.ones(2, dtype=np.float32)
    m.allreduce(t).wait()  # completes despite the failure
    assert m.errored() is not None

    h.client.should_commit.return_value = False
    assert not m.should_commit()


def test_mixed_epoch_span_on_one_rank_vetoes_group_wide(harness):
    """Round-4 advisor low (manager.py:730): the epoch span is a LOCAL
    observation — a death-watch re-quorum can land between ops on one rank
    and entirely outside another's step. The lone observer votes False and
    client.should_commit's global conjunction aborts everyone."""
    h = harness()
    m = h.manager
    h.client._quorum.return_value = quorum_result(max_rank=1)
    m.start_quorum()
    t = np.ones(2, dtype=np.float32)
    m.allreduce(t).wait()
    # a death-watch re-quorum lands mid-step on THIS rank only
    m._quorum_id = 124
    m.allreduce(t).wait()
    assert len(m._step_epochs) == 2
    h.client.should_commit.return_value = False  # global AND result
    assert not m.should_commit()
    # this rank's local vote was the veto that fed the conjunction
    assert h.client.should_commit.call_args.args[2] is False

    # the OTHER side of the same step: a rank that saw a single epoch
    # votes True locally but is aborted by the conjunction anyway
    m.start_quorum()
    m.allreduce(t).wait()
    assert len(m._step_epochs) == 1
    h.client.should_commit.return_value = False
    assert not m.should_commit()
    assert h.client.should_commit.call_args.args[2] is True


def test_death_watch_requorum_mid_step_vetoes_the_step(harness):
    """The death watch's early re-quorum rebuilds the plane under a step in
    flight: an op of the same step then rides the new epoch, the step's ops
    span two, and the barrier votes it down with the reason on its abort."""
    h = harness(min_replica_size=1)
    m = h.manager
    h.client.should_commit.side_effect = (
        lambda rank, step, vote, timeout=None, **kw: vote
    )
    ids = ["replica_a", "replica_b"]
    h.client._quorum.side_effect = [
        quorum_result(quorum_id=123, max_rank=1, participant_ids=ids),
        # the death watch's re-quorum delivers the shrink
        quorum_result(quorum_id=124, max_rank=1, participant_ids=["replica_a"]),
        quorum_result(quorum_id=124, max_rank=1, participant_ids=["replica_a"]),
    ]
    m.start_quorum()
    m.allreduce(np.ones(2, dtype=np.float32)).wait()
    assert m._quorum_id == 123

    m._on_peer_death(1)  # the peer's socket closed mid-step
    m.wait_quorum()
    assert m._quorum_id == 124
    m.allreduce(np.ones(2, dtype=np.float32)).wait()
    assert not m.should_commit()
    assert m.current_step() == 0
    aborts = telemetry.EVENTS.recent("abort")
    assert aborts and aborts[-1]["mixed_epochs"] is True and aborts[-1]["step"] == 0

    # the step after it rides one epoch and commits
    m.start_quorum()
    m.allreduce(np.ones(2, dtype=np.float32)).wait()
    assert m.should_commit()
    assert m.current_step() == 1


def test_stale_death_watch_callback_dropped(harness):
    """Round-4 advisor low (manager.py:574): a POLLHUP delivered for an
    OLD plane generation must not map its ring rank through the CURRENT
    participant list (it could accuse a live replica)."""
    h = harness()
    m = h.manager
    m._death_watch_snapshot = (5, ["rep_a", "rep_b"])
    m._participant_ids = ["rep_x", "rep_y"]  # membership already replaced

    m._on_peer_death(1, plane_gen=4)  # stale generation: dropped
    assert m._evicted == set()

    m._on_peer_death(1, plane_gen=5)  # current: maps through the SNAPSHOT
    assert m._evicted == {"rep_b"}


def test_not_enough_participants(harness):
    h = harness()
    m = h.manager
    h.client._quorum.return_value = quorum_result(
        max_rank=0, max_world_size=1, replica_world_size=1
    )
    m.start_quorum()
    m.wait_quorum()
    assert m.num_participants() == 1  # < min_replica_size=2

    h.client.should_commit.return_value = False
    assert not m.should_commit()
    # local vote must have been False
    assert h.client.should_commit.call_args.args[2] is False


def test_fixed_with_spares_demotion(harness):
    h = harness(world_size_mode=WorldSizeMode.FIXED_WITH_SPARES)
    m = h.manager
    # 3 healthy replicas, min_replica_size=2 -> the third is a spare
    h.client._quorum.return_value = quorum_result(
        max_rank=2, max_world_size=3, replica_rank=2, replica_world_size=3
    )
    m.start_quorum()
    m.wait_quorum()
    assert m.num_participants() == 2
    assert m.participating_rank() is None  # demoted to spare
    t = np.ones(2, dtype=np.float32)
    m.allreduce(t).wait()
    np.testing.assert_allclose(t, 0)  # spare contributes zeros


def test_quorum_timeout_propagates(harness):
    h = harness()
    m = h.manager
    h.client._quorum.return_value = quorum_result(max_rank=1)
    m.start_quorum(timeout=timedelta(seconds=7))
    m.wait_quorum()
    assert h.client._quorum.call_args.kwargs["timeout"] == timedelta(seconds=7)


def test_pipelined_averaging_latches_midway_error(harness):
    """Data plane dies at bucket 2 of a pipelined host-path averaging run:
    the REAL Manager must latch the error, short-circuit the remaining
    bucket ops, still hand back a structurally complete tree, and veto the
    commit (manager.py wrap_future/error-latch semantics)."""
    import jax.numpy as jnp

    from torchft_tpu.collectives import PeerGoneError, ReduceOp
    from torchft_tpu.ddp import allreduce_gradients

    h = harness()
    m = h.manager
    h.client._quorum.return_value = quorum_result(max_rank=1)
    m.start_quorum()

    calls = {"n": 0}
    real_allreduce = h.collectives.allreduce

    def flaky(arrays, op=ReduceOp.SUM, divisor=1):
        calls["n"] += 1
        if calls["n"] == 2:
            raise PeerGoneError(0, "peer died mid-bucket")
        return real_allreduce(arrays, op, divisor)

    h.collectives.allreduce = flaky

    grads = {f"g{i}": jnp.full((16,), float(i)) for i in range(4)}
    out = allreduce_gradients(m, grads, bucket_bytes=64)

    assert m.errored() is not None  # latched
    assert calls["n"] == 2  # buckets after the failure never hit the wire
    assert set(out) == set(grads)
    for i in range(4):
        assert np.asarray(out[f"g{i}"]).shape == (16,)

    h.client.should_commit.return_value = False
    assert m.should_commit() is False


def test_step_after_a_latched_error_packs_into_new_buffers(harness, monkeypatch):
    """The exchange keeps its bucket buffers between steps, but not past an
    error: the op thread may still hold them. The step after the latch
    allocates anew, averages correctly and commits; the one after it reuses."""
    import jax
    import jax.numpy as jnp

    from torchft_tpu import ddp
    from torchft_tpu.collectives import PeerGoneError, ReduceOp
    from torchft_tpu.telemetry import tracing

    # as on the chip: device_put copies, so device leaves make buckets keepable
    monkeypatch.setattr(ddp, "_put_copies", lambda src: isinstance(src, jax.Array))
    h = harness()
    m = h.manager
    h.client._quorum.return_value = quorum_result(max_rank=1)
    real_allreduce = h.collectives.allreduce
    calls = {"n": 0, "fail_at": None}

    def flaky(arrays, op=ReduceOp.SUM, divisor=1):
        calls["n"] += 1
        if calls["n"] == calls["fail_at"]:
            raise PeerGoneError(0, "peer died mid-bucket")
        return real_allreduce(arrays, op, divisor)

    h.collectives.allreduce = flaky

    def step(value, commits):
        m.start_quorum()
        grads = {f"g{i}": jnp.full((16,), value + i) for i in range(4)}
        out = ddp.allreduce_gradients(m, grads, bucket_bytes=64)
        h.client.should_commit.return_value = commits
        assert m.should_commit() is commits
        attrs = tracing.TRACER.recent("exchange")[-1]["attrs"]
        return out, attrs["buckets_reused"], attrs["buckets"]

    assert step(1.0, True)[1:] == (0, 4)
    assert step(2.0, True)[1:] == (4, 4)
    calls["fail_at"] = calls["n"] + 2
    _, reused, _ = step(3.0, False)
    assert reused == 4 and m not in ddp._KEPT
    out, reused, _ = step(4.0, True)
    assert reused == 0 and m.errored() is None
    for i in range(4):  # two participants, the dummy plane adds nothing
        np.testing.assert_array_equal(np.asarray(out[f"g{i}"]), (4.0 + i) / 2)
    assert step(5.0, True)[1:] == (4, 4)


def test_vote_rpc_failure_reaches_the_caller_and_commits_nothing(harness):
    """A lost vote is the caller's to see: the step is not counted, its
    pending work is drained all the same, and the next quorum forms."""
    h = harness()
    m = h.manager
    h.client._quorum.return_value = quorum_result(max_rank=1)
    h.client.should_commit.side_effect = TimeoutError("vote lost")

    m.start_quorum()
    t = np.array([2.0, 4.0], dtype=np.float32)
    work = m.allreduce(t)
    with pytest.raises(TimeoutError, match="vote lost"):
        m.should_commit()
    assert work.done() and m._pending_work == []
    assert m.current_step() == 0 and m.batches_committed() == 0
    commits = len(telemetry.EVENTS.recent("commit"))

    h.client.should_commit.side_effect = None
    h.client.should_commit.return_value = True
    m.start_quorum()
    assert m.errored() is None and m.num_participants() == 2
    m.allreduce(t).wait()
    assert m.should_commit()
    # the step the lost vote was for is the one that commits now
    assert h.client.should_commit.call_args.args[1] == 0
    assert m.current_step() == 1 and m.batches_committed() == 2
    assert len(telemetry.EVENTS.recent("commit")) == commits + 1


def test_start_quorum_retries_after_timeout(harness):
    """A timed-out quorum must not poison the Manager: the next
    start_quorum is the caller's retry and starts fresh (a loaded host
    can blow one deadline without ending the training process)."""
    h = harness()
    m = h.manager

    slow = {"n": 0}

    def quorum_side_effect(**kwargs):
        slow["n"] += 1
        if slow["n"] == 1:
            raise TimeoutError("quorum deadline exceeded")
        return quorum_result(max_rank=1)

    h.client._quorum.side_effect = quorum_side_effect

    m.start_quorum()
    with pytest.raises(TimeoutError):
        m.wait_quorum()

    # retry succeeds on a fresh quorum future
    m.start_quorum()
    m.wait_quorum()
    assert m.num_participants() == 2


def test_the_divisor_travels_with_the_op_and_normalize_leaves_the_bytes(harness):
    """Host path: the Manager hands its participant count to the data plane
    with the op (SUM + divisor) and never divides the buffers itself."""
    from torchft_tpu.collectives import ReduceOp, Work

    h = harness()
    m = h.manager
    h.client._quorum.return_value = quorum_result(max_rank=1)
    m.start_quorum()
    seen = []

    def recording(arrays, op=ReduceOp.SUM, divisor=1):
        seen.append((op, divisor))
        return Work.completed(arrays)  # a plane that "forgot" to divide

    h.collectives.allreduce = recording
    t = np.array([2.0, 4.0], dtype=np.float32)
    assert m.allreduce_many([t]).wait()[0] is t
    assert seen == [(ReduceOp.SUM, 2)]
    np.testing.assert_array_equal(t, [2.0, 4.0])  # nobody else divided
    assert m.avg_in_ring_ops() == 0


@pytest.mark.parametrize("cma", ["1", "0"])
def test_a_spares_zeros_leave_the_participants_average_unperturbed(
    store_server, harness, monkeypatch, cma
):
    """Three groups on the native ring, two participants and a spare: the
    ring divides by the PARTICIPANTS (2), not the world (3), the spare's
    zeros add nothing, and all three hold np.divide(a0 + a1, 2) bit for
    bit — averaged inside the ring, no pass on the host."""
    from concurrent.futures import ThreadPoolExecutor

    from torchft_tpu.collectives import CollectivesTcp

    monkeypatch.setenv("TORCHFT_DP_CMA", cma)
    hs = [
        harness(
            collectives=CollectivesTcp(
                hostname="localhost", timeout=timedelta(seconds=20)
            ),
            world_size_mode=WorldSizeMode.FIXED_WITH_SPARES,
        )
        for _ in range(3)
    ]
    data = [
        (np.random.default_rng(40 + r).standard_normal(100003) * 5).astype(np.float32)
        for r in range(3)
    ]

    def run(r):
        h = hs[r]
        q = quorum_result(
            max_rank=r, max_world_size=3, replica_rank=r, replica_world_size=3
        )
        q.store_address = f"{store_server.address()}/spare{cma}"
        h.client._quorum.return_value = q
        h.manager.start_quorum()
        buf = data[r].copy()
        out = h.manager.allreduce_many([buf]).wait()[0]
        assert h.manager.errored() is None
        return out, h.manager.is_participating(), h.manager.avg_in_ring_ops()

    with ThreadPoolExecutor(max_workers=3) as ex:
        outs = list(ex.map(run, range(3)))
    assert [p for _, p, _ in outs] == [True, True, False]
    expect = data[0] + data[1]
    np.divide(expect, 2, out=expect)
    for out, _, in_ring in outs:
        np.testing.assert_array_equal(out.view(np.uint32), expect.view(np.uint32))
        assert in_ring == 1


@pytest.mark.parametrize("native", [True, False], ids=["native-ring", "python-ring"])
def test_the_trace_says_where_the_average_was_taken(
    store_server, harness, tmp_path, native
):
    """Two groups exchange four buckets under a profiler session. On the
    native f32 path every ``tft.exchange.ring`` carries ``divisor`` 2, no
    ``tft.exchange.average`` runs, and the ``exchange`` span and
    ``tft.exchange.counters`` count ``buckets_avg_in_ring`` = ``buckets``;
    on the Python ring the divisor stat is 0, the counter 0, and the
    fallback pass appears under its own name, once a bucket."""
    import glob
    import os
    from concurrent.futures import ThreadPoolExecutor

    import jax

    from torchft_tpu import ddp
    from torchft_tpu.collectives import CollectivesTcp
    from torchft_tpu.telemetry import tracing

    hs = [
        harness(
            collectives=CollectivesTcp(
                hostname="localhost", timeout=timedelta(seconds=20),
                native_plane=native,
            )
        )
        for _ in range(2)
    ]

    def run(r):
        h = hs[r]
        q = quorum_result(max_rank=r, replica_rank=r)
        q.store_address = f"{store_server.address()}/where{int(native)}"
        h.client._quorum.return_value = q
        h.manager.start_quorum()
        grads = {f"g{i}": np.full((1024,), float(r + i), np.float32) for i in range(4)}
        out = ddp.allreduce_gradients(h.manager, grads, bucket_bytes=4096)
        assert h.manager.errored() is None
        return out

    tracing.TRACER.clear()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with ThreadPoolExecutor(max_workers=2) as ex:
            outs = list(ex.map(run, range(2)))
    finally:
        jax.profiler.stop_trace()
    for out in outs:
        for i in range(4):
            np.testing.assert_array_equal(np.asarray(out[f"g{i}"]), i + 0.5)

    spans = [s for s in tracing.TRACER.recent() if s["name"] == "exchange"]
    assert len(spans) == 2
    for s in spans:
        assert s["attrs"]["buckets"] == 4
        assert s["attrs"]["buckets_avg_in_ring"] == (4 if native else 0)

    (path,) = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    by_name = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("tft.exchange"):
                        by_name.setdefault(ev.name, []).append(dict(ev.stats))
    rings = by_name["tft.exchange.ring"]
    assert len(rings) == 8
    assert {r["divisor"] for r in rings} == {2 if native else 0}
    averages = by_name.get("tft.exchange.average", [])
    assert len(averages) == (0 if native else 8)
    assert all(a["divisor"] == 2 and a["bytes"] == 4096 for a in averages)
    counters = by_name["tft.exchange.counters"]
    assert [c["buckets_avg_in_ring"] for c in counters] == [4 if native else 0] * 2
    # every ring is followed by its account, and the step's sums are the
    # accounts' (both groups' here: one process holds them)
    accounts = by_name["tft.exchange.ring.account"]
    assert len(accounts) == 8 and all(a["bytes"] == 4096 for a in accounts)
    (plane,) = {a["plane"] for a in accounts}
    assert plane in (("cma", "tcp-striped") if native else ("python-ring",))
    for c in counters:
        # a bucket the native ring read from its source is not packed (plane
        # tcp copies the chunk it sends first, half of it at world 2; plane cma
        # nothing); the first may be decided while the quorum is still
        # configuring the plane, and is packed then
        from_source = c["buckets_from_source"]
        assert from_source in ((3, 4) if native else (0,))
        ring_copied = 0 if plane == "cma" else 2048
        assert c["pack_bytes"] == (4 - from_source) * 4096 + from_source * ring_copied
        assert c["h2d_bytes"] == 0  # host leaves
        # 2 x (w - 1) / w of a bucket is pulled, (w - 1) / w reduced natively
        assert c["ring_pull_bytes"] == (4 * 4096 if plane == "cma" else 0)
        assert c["ring_reduce_bytes"] == (4 * 2048 if native else 0)
        assert (c["ring_pump_s"] > 0) == (plane != "cma")
    for total, fields in (
        ("ring_wait_s", ("desc_wait_s", "ack_wait_s")), ("ring_pull_s", ("pull_s",)),
        ("ring_reduce_s", ("reduce_s",)), ("ring_pump_s", ("pump_s",)),
    ):
        assert sum(c[total] for c in counters) == pytest.approx(
            sum(a[f] for a in accounts for f in fields), abs=1e-9
        )


# ---------------------------------------------------------------------------
# committed state: what the two drivers of the commit barrier keep of a run
# in which a step is lost, against a plain run that never took that batch
# ---------------------------------------------------------------------------


class FaultyDummy(CollectivesDummy):
    """Raises ``PeerGoneError`` in the first allreduce after ``fail_next``
    is set — the failed-op face of a peer dying mid-exchange."""

    fail_next = False

    def allreduce(self, arrays, op=None, divisor=1):
        if self.fail_next:
            self.fail_next = False
            raise PeerGoneError(0, "peer 0 died mid-op")
        return super().allreduce(arrays, divisor=divisor)


class TestCommittedStateSkipsTheLostStep:
    STEPS = 5
    LOST = 2  # 0-based index of the batch whose step does not commit
    N = 2  # participants in the harness's quorum: the average divides by it

    @pytest.fixture(scope="class")
    def train_step(self):
        import jax.numpy as jnp
        import optax

        from torchft_tpu.models.transformer import TransformerConfig
        from torchft_tpu.parallel.mesh import MeshConfig, make_mesh
        from torchft_tpu.parallel.train_step import TrainStep

        cfg = TransformerConfig(
            vocab_size=32, d_model=16, n_layers=1, n_heads=2, head_dim=8,
            d_ff=32, dtype=jnp.float32,
        )
        return TrainStep(cfg, optax.adam(1e-2), make_mesh(MeshConfig(dp=1)))

    def _trainer(self, h, train_step):
        """(step(batch), state(), plain(batches)) for ``FTTrainer``."""
        import jax
        import jax.numpy as jnp

        from torchft_tpu.parallel.ft import FTTrainer

        rng = np.random.default_rng(7)
        batches = [
            jnp.asarray(rng.integers(0, 32, (2, 4)), jnp.int32)
            for _ in range(self.STEPS)
        ]
        trainer = FTTrainer(h.manager, train_step)
        trainer.init(jax.random.PRNGKey(0))

        def plain(kept):
            ts = train_step
            params = ts.init_params(jax.random.PRNGKey(0))
            opt_state = ts.init_opt(params)
            for tokens in kept:
                _, grads = ts.grads(params, ts.shard_batch(tokens))
                grads = jax.tree_util.tree_map(lambda g: g / self.N, grads)
                params, opt_state = ts.apply(params, opt_state, grads)
            return params, opt_state

        return batches, trainer.step, lambda: (trainer.params, trainer.opt_state), plain

    def _optimizer(self, h):
        """The same three for ``ManagedOptimizer`` on a least-squares fit."""
        import jax
        import jax.numpy as jnp
        import optax

        from torchft_tpu.optim import ManagedOptimizer

        rng = np.random.default_rng(7)
        batches = [
            (jnp.asarray(rng.normal(size=(8, 4)), jnp.float32),
             jnp.asarray(rng.normal(size=(8,)), jnp.float32))
            for _ in range(self.STEPS)
        ]
        tx = optax.adam(1e-2)
        grad_fn = jax.jit(jax.grad(lambda p, x, y: jnp.mean((x @ p["w"] - y) ** 2)))
        init = {"w": jnp.ones(4, jnp.float32)}
        opt = ManagedOptimizer(h.manager, tx)
        opt.init(init)

        def step(batch):
            opt.begin_step()
            opt.step(grad_fn(opt.params, *batch))

        @jax.jit
        def update(params, opt_state, grads):
            updates, opt_state = tx.update(grads, opt_state, params)
            return optax.apply_updates(params, updates), opt_state

        def plain(kept):
            params, opt_state = init, tx.init(init)
            for batch in kept:
                grads = jax.tree_util.tree_map(
                    lambda g: g / self.N, grad_fn(params, *batch)
                )
                params, opt_state = update(params, opt_state, grads)
            return params, opt_state

        return batches, step, lambda: (opt.params, opt.opt_state), plain

    @pytest.mark.parametrize("fault", ["veto", "peer_gone", "vote_raises"])
    @pytest.mark.parametrize("driver", ["trainer", "optimizer"])
    def test_committed_state_is_the_plain_runs(
        self, store_server, train_step, driver, fault
    ):
        import jax

        h = ManagerHarness(store_server, collectives=FaultyDummy(rank=0, world_size=1))
        try:
            m = h.manager
            h.client._quorum.return_value = quorum_result(max_rank=1)
            votes = []

            def vote_fn(rank, step, vote, timeout=None, **kw):
                votes.append((step, vote))
                if len(votes) == self.LOST + 1:
                    if fault == "vote_raises":
                        raise TimeoutError("vote lost")
                    if fault == "veto":
                        return False  # another rank of the group voted no
                return vote

            h.client.should_commit.side_effect = vote_fn
            batches, step, state, plain = (
                self._trainer(h, train_step) if driver == "trainer"
                else self._optimizer(h)
            )
            for i, batch in enumerate(batches):
                h.collectives.fail_next = fault == "peer_gone" and i == self.LOST
                if fault == "vote_raises" and i == self.LOST:
                    with pytest.raises(TimeoutError, match="vote lost"):
                        step(batch)
                else:
                    step(batch)

            # one vote a step; the lost step's is cast for the step the next
            # one commits, and only a failed exchange votes no by itself
            assert [s for s, _ in votes] == [0, 1, 2, 2, 3]
            assert [v for _, v in votes] == [
                not (fault == "peer_gone" and i == self.LOST)
                for i in range(self.STEPS)
            ]
            assert m.current_step() == self.STEPS - 1
            assert m.batches_committed() == self.N * (self.STEPS - 1)
            kept = [b for i, b in enumerate(batches) if i != self.LOST]
            got, want = state(), plain(kept)
            assert jax.tree_util.tree_structure(got) == jax.tree_util.tree_structure(want)
            for a, b in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        finally:
            h.shutdown()
