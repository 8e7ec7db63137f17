"""Coordination-core tests.

Ports the reference's in-file Rust test scenarios to the C++ core:
  * quorum_compute table tests  (src/lighthouse.rs:582-1001)
  * compute_quorum_results tables (src/manager.rs:720-850)
  * live lighthouse/manager e2e    (src/lighthouse.rs:910-952,
    src/manager.rs:504-549)
"""

import threading
import time
import urllib.request
from datetime import timedelta

import pytest

from torchft_tpu import _native
from torchft_tpu.coordination import (
    LighthouseClient,
    LighthouseServer,
    ManagerClient,
    ManagerServer,
)


def member(rid, step=0, shrink_only=False, world_size=1):
    return {
        "replica_id": rid,
        "address": f"addr_{rid}",
        "store_address": f"store_{rid}",
        "step": step,
        "world_size": world_size,
        "shrink_only": shrink_only,
    }


def state(now, participants, heartbeats, prev=None, **opt):
    return {
        "now": now,
        "participants": [
            {"joined_ms": j, "member": m} for j, m in participants
        ],
        "heartbeats": [{"replica_id": r, "at_ms": t} for r, t in heartbeats],
        "prev_quorum": prev,
        "opt": {
            "min_replicas": opt.get("min_replicas", 1),
            "join_timeout_ms": opt.get("join_timeout_ms", 60000),
            "heartbeat_timeout_ms": opt.get("heartbeat_timeout_ms", 5000),
        },
    }


def quorum(qid, members):
    return {"quorum_id": qid, "participants": members, "created": 0}


class TestQuorumCompute:
    def test_empty(self):
        r = _native.quorum_compute(state(1000, [], []))
        assert r["quorum"] is None

    def test_join_timeout_waits_for_stragglers(self):
        # two participants + one extra heartbeating replica (2 of 3 passes
        # the split-brain guard), within join_timeout -> wait
        # (src/lighthouse.rs test_quorum_join_timeout)
        s = state(
            1000,
            [(1000, member("a")), (1000, member("b"))],
            [("a", 1000), ("b", 1000), ("c", 1000)],
            join_timeout_ms=60000,
        )
        r = _native.quorum_compute(s)
        assert r["quorum"] is None
        assert "straggler" in r["reason"]

        # after the join timeout has elapsed the quorum forms without c
        s = state(
            70000,
            [(1000, member("a")), (1000, member("b"))],
            [("a", 69999), ("b", 69999), ("c", 69999)],
            join_timeout_ms=60000,
        )
        r = _native.quorum_compute(s)
        assert r["quorum"] is not None
        assert [m["replica_id"] for m in r["quorum"]] == ["a", "b"]

    def test_split_brain_beats_straggler_wait(self):
        # 1 participant of 2 heartbeating is rejected by the split-brain
        # guard before the straggler wait is even considered
        s = state(
            1000,
            [(1000, member("a"))],
            [("a", 1000), ("b", 1000)],
            join_timeout_ms=60000,
        )
        r = _native.quorum_compute(s)
        assert r["quorum"] is None
        assert "at least half" in r["reason"]

    def test_all_joined_skips_join_timeout(self):
        s = state(
            1000,
            [(1000, member("a")), (1000, member("b"))],
            [("a", 1000), ("b", 1000)],
            join_timeout_ms=60000,
        )
        r = _native.quorum_compute(s)
        assert r["quorum"] is not None
        assert len(r["quorum"]) == 2

    def test_heartbeat_expiry_excludes_replica(self):
        # a's heartbeat is stale -> unhealthy -> below min_replicas
        s = state(
            10000,
            [(1000, member("a"))],
            [("a", 1000)],
            heartbeat_timeout_ms=5000,
        )
        r = _native.quorum_compute(s)
        assert r["quorum"] is None
        assert "min_replicas" in r["reason"]

    def test_min_replicas(self):
        s = state(
            1000,
            [(1000, member("a"))],
            [("a", 1000)],
            min_replicas=2,
        )
        r = _native.quorum_compute(s)
        assert r["quorum"] is None

    def test_fast_quorum_when_prev_members_all_healthy(self):
        # prev quorum {a, b}; both are healthy participants again; extra
        # heartbeating straggler c does NOT delay the fast path
        # (src/lighthouse.rs:174-187)
        s = state(
            1000,
            [(999, member("a")), (999, member("b"))],
            [("a", 1000), ("b", 1000), ("c", 1000)],
            prev=quorum(1, [member("a"), member("b")]),
            join_timeout_ms=60000,
        )
        r = _native.quorum_compute(s)
        assert r["quorum"] is not None
        assert "Fast quorum" in r["reason"]
        assert [m["replica_id"] for m in r["quorum"]] == ["a", "b"]

    def test_no_fast_quorum_when_prev_member_missing(self):
        s = state(
            1000,
            [(999, member("a"))],
            [("a", 1000), ("b", 1000)],
            prev=quorum(1, [member("a"), member("b")]),
            join_timeout_ms=60000,
        )
        r = _native.quorum_compute(s)
        assert r["quorum"] is None  # waiting for straggler b

    def test_split_brain_guard(self):
        # 2 participants out of 5 heartbeating: 2 <= 5//2 -> rejected
        # (src/lighthouse.rs:202-213)
        s = state(
            100000,
            [(1, member("a")), (1, member("b"))],
            [(r, 100000) for r in ["a", "b", "c", "d", "e"]],
            join_timeout_ms=1,
        )
        r = _native.quorum_compute(s)
        assert r["quorum"] is None
        assert "at least half" in r["reason"]

        # 3 of 5 passes
        s = state(
            100000,
            [(1, member("a")), (1, member("b")), (1, member("c"))],
            [(r, 100000) for r in ["a", "b", "c", "d", "e"]],
            join_timeout_ms=1,
        )
        r = _native.quorum_compute(s)
        assert r["quorum"] is not None

    def test_shrink_only_filters_joiners(self):
        # shrink_only quorum keeps only prev members; c is excluded even
        # though healthy (src/lighthouse.rs:167-172 + 1036-1140 scenario)
        s = state(
            1000,
            [
                (999, member("a", shrink_only=True)),
                (999, member("b")),
                (999, member("c")),
            ],
            [("a", 1000), ("b", 1000), ("c", 1000)],
            prev=quorum(1, [member("a"), member("b")]),
        )
        r = _native.quorum_compute(s)
        assert r["quorum"] is not None
        assert [m["replica_id"] for m in r["quorum"]] == ["a", "b"]

    def test_candidates_sorted_by_replica_id(self):
        s = state(
            1000,
            [(1000, member("z")), (1000, member("a")), (1000, member("m"))],
            [("z", 1000), ("a", 1000), ("m", 1000)],
        )
        r = _native.quorum_compute(s)
        assert [m["replica_id"] for m in r["quorum"]] == ["a", "m", "z"]


class TestComputeQuorumResults:
    def test_first_step_primary_and_recovery(self):
        # max_step == 0: non-primary replicas bootstrap from the primary
        # (src/manager.rs:403-416 + test_compute_quorum_results_first_step)
        q = quorum(1, [member("a", 0), member("b", 0)])
        ra = _native.compute_quorum_results(q, "a", 0)
        rb = _native.compute_quorum_results(q, "b", 0)
        assert ra["heal"] is False
        assert ra["recover_dst_ranks"] == [1]
        assert ra["store_address"] == "store_a"
        assert rb["heal"] is True
        assert rb["recover_src_rank"] == 0
        assert rb["recover_src_manager_address"] == "addr_a"
        assert rb["max_world_size"] == 2
        assert rb["replica_world_size"] == 2

    def test_mixed_step_recovery_assignment(self):
        q = quorum(7, [member("a", 5), member("b", 3), member("c", 5)])
        ra = _native.compute_quorum_results(q, "a", 0)
        rb = _native.compute_quorum_results(q, "b", 0)
        rc = _native.compute_quorum_results(q, "c", 0)
        assert ra["max_step"] == 5
        assert ra["max_world_size"] == 2  # cohort {a, c}
        assert ra["recover_dst_ranks"] == [1]
        assert rb["heal"] is True
        assert rb["recover_src_rank"] == 0
        assert rb["max_rank"] is None  # b not in the max cohort
        assert rc["recover_dst_ranks"] == []
        assert rc["max_rank"] == 1

    def test_rank_offsets_recovery_source(self):
        # local rank shifts the round-robin so different local ranks pull
        # from different sources (src/manager.rs:434-447)
        q = quorum(7, [member("a", 5), member("b", 3), member("c", 5)])
        rb0 = _native.compute_quorum_results(q, "b", 0)
        rb1 = _native.compute_quorum_results(q, "b", 1)
        assert rb0["recover_src_rank"] == 0
        assert rb1["recover_src_rank"] == 2

    def test_primary_store_striped_by_rank(self):
        q = quorum(7, [member("a", 5), member("c", 5)])
        r0 = _native.compute_quorum_results(q, "a", 0)
        r1 = _native.compute_quorum_results(q, "a", 1)
        assert r0["store_address"] == "store_a"
        assert r1["store_address"] == "store_c"

    def test_replica_not_in_quorum(self):
        q = quorum(1, [member("a", 0)])
        with pytest.raises(RuntimeError):
            _native.compute_quorum_results(q, "zz", 0)

    def test_group_heal_is_plane_consistent(self):
        """Participation gating must agree across a group's rank planes —
        otherwise plane 0 would average real gradients while plane 1
        averages zeros and replicated/sharded state diverges (extension
        beyond the reference's per-rank gate, manager.py:268). At the
        step-0 bootstrap every group heals from ONE source (the cohort's
        first) rather than the rank-striped primary: striping would make
        every group heal somewhere, zeroing every contribution and turning
        the first committed step into a pure weight-decay update (round-2
        advisor finding)."""
        q = quorum(
            1, [member("a", 0, world_size=2), member("b", 0, world_size=2)]
        )
        # bootstrap source group: no plane heals, contributes real grads
        for rank in (0, 1):
            ra = _native.compute_quorum_results(q, "a", rank)
            assert ra["group_heal"] is False, rank
            assert ra["heal"] is False, rank
            assert ra["recover_dst_ranks"] == [1], rank
        # every other group heals on EVERY plane, from the same source
        for rank in (0, 1):
            rb = _native.compute_quorum_results(q, "b", rank)
            assert rb["group_heal"] is True, rank
            assert rb["heal"] is True, rank
            assert rb["recover_src_rank"] == 0, rank
        # store striping is untouched by the bootstrap rule
        assert _native.compute_quorum_results(q, "a", 0)["store_address"] == "store_a"
        assert _native.compute_quorum_results(q, "a", 1)["store_address"] == "store_b"

    def test_participant_ids_in_rank_order(self):
        q = quorum(7, [member("z", 5), member("a", 5), member("m", 3)])
        r = _native.compute_quorum_results(q, "a", 0)
        ids = [s if isinstance(s, str) else s.decode() for s in r["participant_ids"]]
        assert ids == ["a", "m", "z"]

    def test_group_heal_matches_heal_for_single_rank_groups(self):
        q0 = quorum(1, [member("a", 0), member("b", 0)])
        qk = quorum(7, [member("a", 5), member("b", 3)])
        for q in (q0, qk):
            for rid in ("a", "b"):
                r = _native.compute_quorum_results(q, rid, 0)
                assert r["group_heal"] == r["heal"], (rid, r)


class TestLighthouseE2E:
    def test_quorum_fast_latency(self):
        # parity with lighthouse_test.py:44-47 — single-replica quorum with
        # join_timeout_ms=100 resolves quickly
        lh = LighthouseServer(bind="[::]:0", min_replicas=1, join_timeout_ms=100)
        try:
            c = LighthouseClient(lh.address(), connect_timeout=timedelta(seconds=5))
            t0 = time.monotonic()
            q = c.quorum(member("a"), timeout=timedelta(seconds=5))
            dt = time.monotonic() - t0
            assert [m["replica_id"] for m in q["participants"]] == ["a"]
            assert q["quorum_id"] == 1
            assert dt < 1.0
            c.close()
        finally:
            lh.shutdown()

    def test_heartbeat(self):
        lh = LighthouseServer(bind="[::]:0", min_replicas=1)
        try:
            c = LighthouseClient(lh.address(), connect_timeout=timedelta(seconds=5))
            c.heartbeat("a")
            c.close()
        finally:
            lh.shutdown()

    def test_dashboard_status(self):
        lh = LighthouseServer(bind="[::]:0", min_replicas=1, join_timeout_ms=100)
        try:
            c = LighthouseClient(lh.address(), connect_timeout=timedelta(seconds=5))
            c.quorum(member("dash_replica"), timeout=timedelta(seconds=5))
            addr = lh.address()
            with urllib.request.urlopen(addr + "/status", timeout=5) as resp:
                body = resp.read().decode()
            assert "dash_replica" in body
            assert "quorum_id" in body
            with urllib.request.urlopen(addr + "/", timeout=5) as resp:
                assert resp.status == 200
            with urllib.request.urlopen(addr + "/status.json", timeout=5) as resp:
                assert b"quorum_id" in resp.read()
            # Prometheus exposition (beyond the reference: SURVEY §5.5
            # notes it has no metrics export)
            with urllib.request.urlopen(addr + "/metrics", timeout=5) as resp:
                metrics = resp.read().decode()
            assert "torchft_quorum_id" in metrics
            assert "torchft_participants 1" in metrics
            assert 'torchft_member_step{replica_id="dash_replica"} 0' in metrics
            # round-5 FT runtime state (review #9): eviction/flush counters,
            # per-member plane + recovering flags
            assert "torchft_evictions_total 0" in metrics
            assert "torchft_flush_requests_total" in metrics
            assert "torchft_recovering_members 0" in metrics
            assert 'torchft_member_info{replica_id="dash_replica"' in metrics
            c.close()
        finally:
            lh.shutdown()

    def test_status_json_ft_runtime_fields(self):
        """Round-5 review #9: /status.json exposes the FT runtime state —
        per-member plane + recovering flag, eviction and flush counters —
        and an eviction shows up in both counters and the recent list."""
        import json as _json

        lh = LighthouseServer(
            bind="[::]:0", min_replicas=1, join_timeout_ms=100
        )
        try:
            c = LighthouseClient(
                lh.address(), connect_timeout=timedelta(seconds=5)
            )
            m = member("json_replica")
            m["plane"] = "cma"
            c.quorum(m, timeout=timedelta(seconds=5))
            with urllib.request.urlopen(
                lh.address() + "/status.json", timeout=5
            ) as resp:
                st = _json.loads(resp.read())
            assert st["evictions_total"] == 0
            assert st["flush_requests_total"] == 0
            assert st["max_step"] == 0
            assert st["members"] == [
                {
                    "replica_id": "json_replica",
                    "step": 0,
                    "plane": "cma",
                    "recovering": False,
                    "commit_failures": 0,
                }
            ]
            assert st["recent_evictions"] == []

            # an eviction (reporter must differ from victim; probe of the
            # fake address fails -> victim evicted) lands in the counters.
            # both members must (re-)request CONCURRENTLY: the split-brain
            # guard refuses to drop a still-heartbeating member, so a
            # sequential second join would wait out the lease instead
            two = member("second_replica")
            two["plane"] = "tcp-striped"
            import threading

            c2 = LighthouseClient(
                lh.address(), connect_timeout=timedelta(seconds=5)
            )
            # newcomer FIRST (parks: fast-quorum needs the prev member),
            # then the incumbent re-request completes the pair — if the
            # incumbent went first, its fast-quorum would re-publish the
            # solo quorum before the newcomer registers
            t = threading.Thread(
                target=lambda: c2.quorum(two, timeout=timedelta(seconds=10))
            )
            t.start()
            time.sleep(0.3)
            c.quorum(m, timeout=timedelta(seconds=10))
            t.join()
            evicted = c2.evict(
                reporter="second_replica",
                victim="json_replica",
                timeout=timedelta(seconds=5),
            )
            assert evicted
            with urllib.request.urlopen(
                lh.address() + "/status.json", timeout=5
            ) as resp:
                st = _json.loads(resp.read())
            assert st["evictions_total"] == 1
            assert len(st["recent_evictions"]) == 1
            assert "json_replica < second_replica" in st["recent_evictions"][0]
            c.close()
            c2.close()
        finally:
            lh.shutdown()

    def test_quorum_id_bumps_only_on_membership_change(self):
        lh = LighthouseServer(bind="[::]:0", min_replicas=1, join_timeout_ms=100)
        try:
            c = LighthouseClient(lh.address(), connect_timeout=timedelta(seconds=5))
            q1 = c.quorum(member("a", step=1), timeout=timedelta(seconds=5))
            q2 = c.quorum(member("a", step=2), timeout=timedelta(seconds=5))
            assert q1["quorum_id"] == q2["quorum_id"]  # same member set
            c.close()
        finally:
            lh.shutdown()

    def test_commit_failures_flush_bumps_quorum_id(self):
        # data-plane flush: a member with latched commit failures forces a
        # quorum_id bump even though membership is unchanged, so every group
        # re-rendezvouses its collectives into a fresh epoch
        lh = LighthouseServer(bind="[::]:0", min_replicas=1, join_timeout_ms=100)
        try:
            c = LighthouseClient(lh.address(), connect_timeout=timedelta(seconds=5))
            q1 = c.quorum(member("a", step=1), timeout=timedelta(seconds=5))
            flushing = dict(member("a", step=1), commit_failures=1)
            q2 = c.quorum(flushing, timeout=timedelta(seconds=5))
            assert q2["quorum_id"] == q1["quorum_id"] + 1
            # flush consumed: a clean re-request keeps the new id
            q3 = c.quorum(member("a", step=2), timeout=timedelta(seconds=5))
            assert q3["quorum_id"] == q2["quorum_id"]
            c.close()
        finally:
            lh.shutdown()


class TestManagerE2E:
    def _setup(self, n_replicas=2, world_size=1, min_replicas=2):
        lh = LighthouseServer(
            bind="[::]:0", min_replicas=min_replicas, join_timeout_ms=100
        )
        mgrs = [
            ManagerServer(
                replica_id=f"rep_{i}",
                lighthouse_addr=lh.address(),
                hostname="localhost",
                bind="[::]:0",
                store_addr=f"store_{i}",
                world_size=world_size,
            )
            for i in range(n_replicas)
        ]
        return lh, mgrs

    def test_quorum_and_commit(self):
        lh, mgrs = self._setup()
        try:
            results = {}

            def run(i):
                c = ManagerClient(mgrs[i].address(), connect_timeout=timedelta(seconds=10))
                results[i] = c._quorum(
                    rank=0, step=0, checkpoint_metadata=f"m{i}",
                    shrink_only=False, timeout=timedelta(seconds=10),
                )
                results[(i, "commit")] = c.should_commit(
                    0, 0, True, timeout=timedelta(seconds=10)
                )
                c.close()

            ts = [threading.Thread(target=run, args=(i,)) for i in range(2)]
            for t in ts:
                t.start()
            for t in ts:
                t.join()

            assert results[0].quorum_id == results[1].quorum_id
            assert results[0].replica_world_size == 2
            assert results[(0, "commit")] is True
            assert results[(1, "commit")] is True
            # exactly one of the two bootstraps from the other at step 0
            assert results[0].heal != results[1].heal
        finally:
            for m in mgrs:
                m.shutdown()
            lh.shutdown()

    @pytest.mark.parametrize("world_size", [1, 2])
    def test_one_lighthouse_rpc_per_group_per_quorum_round(self, world_size):
        """What the lighthouse serves per step: ONE ``lh.quorum`` call a
        group a round, however many ranks the group has (the native
        ``quorum.fanout`` histogram takes one observation per call)."""
        groups, rounds = 2, 3
        lh, mgrs = self._setup(n_replicas=groups, world_size=world_size)
        before = _native.lathist_snapshot()["quorum.fanout"]["count"]
        try:
            for step in range(rounds):

                def run(i, rank, step=step):
                    c = ManagerClient(
                        mgrs[i].address(), connect_timeout=timedelta(seconds=10)
                    )
                    c._quorum(
                        rank=rank, step=step, checkpoint_metadata="",
                        shrink_only=False, timeout=timedelta(seconds=10),
                    )
                    c.close()

                ts = [
                    threading.Thread(target=run, args=(i, r))
                    for i in range(groups)
                    for r in range(world_size)
                ]
                for t in ts:
                    t.start()
                for t in ts:
                    t.join()
            after = _native.lathist_snapshot()["quorum.fanout"]["count"]
            assert after - before == groups * rounds
        finally:
            for m in mgrs:
                m.shutdown()
            lh.shutdown()

    def test_should_commit_one_failure_rejects_all(self):
        # world_size=2 ranks on one manager; one False vote fails the round
        # (src/manager.rs:295-347 semantics)
        lh = LighthouseServer(bind="[::]:0", min_replicas=1, join_timeout_ms=100)
        mgr = ManagerServer(
            replica_id="rep_0", lighthouse_addr=lh.address(),
            hostname="localhost", bind="[::]:0", store_addr="s",
            world_size=2,
        )
        try:
            out = {}

            def vote(rank, val):
                c = ManagerClient(mgr.address(), connect_timeout=timedelta(seconds=10))
                out[rank] = c.should_commit(rank, 0, val, timeout=timedelta(seconds=10))
                c.close()

            ts = [
                threading.Thread(target=vote, args=(0, True)),
                threading.Thread(target=vote, args=(1, False)),
            ]
            for t in ts:
                t.start()
            for t in ts:
                t.join()
            assert out[0] is False
            assert out[1] is False

            # next round is reset and can succeed
            ts = [
                threading.Thread(target=vote, args=(0, True)),
                threading.Thread(target=vote, args=(1, True)),
            ]
            for t in ts:
                t.start()
            for t in ts:
                t.join()
            assert out[0] is True and out[1] is True
        finally:
            mgr.shutdown()
            lh.shutdown()

    def test_checkpoint_metadata_lookup(self):
        lh, mgrs = self._setup(n_replicas=1, min_replicas=1)
        try:
            c = ManagerClient(mgrs[0].address(), connect_timeout=timedelta(seconds=10))
            c._quorum(
                rank=0, step=0, checkpoint_metadata="the-meta",
                shrink_only=False, timeout=timedelta(seconds=10),
            )
            assert c._checkpoint_metadata(0, timeout=timedelta(seconds=5)) == "the-meta"
            with pytest.raises(RuntimeError):
                c._checkpoint_metadata(99, timeout=timedelta(seconds=5))
            c.close()
        finally:
            mgrs[0].shutdown()
            lh.shutdown()

    def test_quorum_timeout_enforced(self):
        # 1 of 2 local ranks joins -> quorum can't proceed; 10ms deadline
        # must raise TimeoutError in well under a second
        # (manager_integ_test.py:356-368 parity)
        lh = LighthouseServer(bind="[::]:0", min_replicas=1, join_timeout_ms=100)
        mgr = ManagerServer(
            replica_id="rep_0", lighthouse_addr=lh.address(),
            hostname="localhost", bind="[::]:0", store_addr="s",
            world_size=2,
        )
        try:
            c = ManagerClient(mgr.address(), connect_timeout=timedelta(seconds=10))
            t0 = time.monotonic()
            with pytest.raises(TimeoutError):
                c._quorum(
                    rank=0, step=0, checkpoint_metadata="",
                    shrink_only=False, timeout=timedelta(milliseconds=10),
                )
            assert time.monotonic() - t0 < 1.0
            c.close()
        finally:
            mgr.shutdown()
            lh.shutdown()

    def test_soft_kill(self):
        lh, mgrs = self._setup(n_replicas=1, min_replicas=1)
        try:
            c = ManagerClient(mgrs[0].address(), connect_timeout=timedelta(seconds=10))
            c.kill("test")  # TORCHFT_TPU_SOFT_KILL set by conftest
            c.close()
        finally:
            mgrs[0].shutdown()
            lh.shutdown()

    def test_manager_requires_lighthouse(self):
        with pytest.raises((RuntimeError, TimeoutError)):
            ManagerServer(
                replica_id="rep_0",
                lighthouse_addr="http://localhost:1",  # nothing listening
                hostname="localhost", bind="[::]:0", store_addr="s",
                world_size=1,
                connect_timeout=timedelta(milliseconds=200),
            )


class TestEviction:
    """Survivor-reported eviction (lh.evict): active dead-peer detection
    that beats the passive heartbeat-lease floor the reference shares
    (src/lighthouse.rs:119-128 only ages out leases)."""

    def _quorum_pair(self, lh, mgrs):
        """Drive both managers through one quorum so prev_quorum exists."""
        results = {}

        def run(i):
            c = ManagerClient(mgrs[i].address(), connect_timeout=timedelta(seconds=10))
            results[i] = c._quorum(
                rank=0, step=1, checkpoint_metadata="",
                shrink_only=False, timeout=timedelta(seconds=10),
            )
            c.close()

        ts = [threading.Thread(target=run, args=(i,)) for i in range(len(mgrs))]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        return results

    def test_false_report_does_not_evict_live_peer(self):
        lh = LighthouseServer(bind="[::]:0", min_replicas=2, join_timeout_ms=100)
        mgrs = [
            ManagerServer(
                replica_id=f"rep_{i}", lighthouse_addr=lh.address(),
                hostname="localhost", bind="[::]:0", store_addr=f"s{i}",
                world_size=1,
            )
            for i in range(2)
        ]
        try:
            self._quorum_pair(lh, mgrs)
            c = LighthouseClient(lh.address(), connect_timeout=timedelta(seconds=5))
            # rep_1 is alive and listening — the probe succeeds, report is
            # a no-op
            assert c.evict("rep_0", "rep_1") is False
            # the next quorum still contains both members
            res = self._quorum_pair(lh, mgrs)
            assert res[0].replica_world_size == 2
            assert sorted(res[0].participant_ids) == ["rep_0", "rep_1"]
            c.close()
        finally:
            for m in mgrs:
                m.shutdown()
            lh.shutdown()

    def test_dead_peer_evicted_without_lease_wait(self):
        # long heartbeat lease: only eviction (not expiry) can explain a
        # fast quorum without the victim
        lh = LighthouseServer(
            bind="[::]:0", min_replicas=1, join_timeout_ms=60000,
            heartbeat_timeout_ms=60000,
        )
        mgrs = [
            ManagerServer(
                replica_id=f"rep_{i}", lighthouse_addr=lh.address(),
                hostname="localhost", bind="[::]:0", store_addr=f"s{i}",
                world_size=1,
            )
            for i in range(2)
        ]
        try:
            c = LighthouseClient(lh.address(), connect_timeout=timedelta(seconds=5))
            # with min_replicas=1 the first quorum could close on whoever
            # asks first; once the lighthouse has a heartbeat from both,
            # one alone is not more than half of the healthy and has to
            # wait for the other
            for rid in ("rep_0", "rep_1"):
                c.heartbeat(rid)
            first = self._quorum_pair(lh, mgrs)
            assert sorted(first[0].participant_ids) == ["rep_0", "rep_1"]
            mgrs[1].shutdown()  # SIGKILL stand-in: socket gone, no goodbyes
            assert c.evict("rep_0", "rep_1") is True
            # survivor re-quorums without the victim: the lease and the
            # join wait are 60 s and this RPC gives up after 10, so a
            # reply at all says that neither was waited for
            mc = ManagerClient(mgrs[0].address(), connect_timeout=timedelta(seconds=10))
            r = mc._quorum(
                rank=0, step=2, checkpoint_metadata="",
                shrink_only=False, timeout=timedelta(seconds=10),
            )
            assert r.replica_world_size == 1
            assert r.participant_ids == ["rep_0"]
            mc.close()
            c.close()
        finally:
            for m in mgrs:
                m.shutdown()
            lh.shutdown()

    def test_evict_guards(self):
        lh = LighthouseServer(bind="[::]:0", min_replicas=1, join_timeout_ms=100)
        mgr = ManagerServer(
            replica_id="rep_0", lighthouse_addr=lh.address(),
            hostname="localhost", bind="[::]:0", store_addr="s0",
            world_size=1,
        )
        try:
            c = LighthouseClient(lh.address(), connect_timeout=timedelta(seconds=5))
            # no quorum yet
            with pytest.raises(RuntimeError):
                c.evict("rep_0", "rep_1")
            self._quorum_pair(lh, [mgr])
            # reporter not a member
            with pytest.raises(RuntimeError):
                c.evict("stranger", "rep_0")
            # victim not a member
            with pytest.raises(RuntimeError):
                c.evict("rep_0", "stranger")
            # self-report
            with pytest.raises(RuntimeError):
                c.evict("rep_0", "rep_0")
            c.close()
        finally:
            mgr.shutdown()
            lh.shutdown()

    def test_manager_forwards_evict(self):
        lh = LighthouseServer(bind="[::]:0", min_replicas=2, join_timeout_ms=100)
        mgrs = [
            ManagerServer(
                replica_id=f"rep_{i}", lighthouse_addr=lh.address(),
                hostname="localhost", bind="[::]:0", store_addr=f"s{i}",
                world_size=1,
            )
            for i in range(2)
        ]
        try:
            self._quorum_pair(lh, mgrs)
            mgrs[1].shutdown()
            mc = ManagerClient(mgrs[0].address(), connect_timeout=timedelta(seconds=10))
            assert mc.evict("rep_1") is True
            mc.close()
        finally:
            for m in mgrs:
                m.shutdown()
            lh.shutdown()
