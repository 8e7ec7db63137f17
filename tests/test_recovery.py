"""Recovery-envelope test: the wall-clock bound the reference encodes in
assertions (lighthouse_test.py:44-47 quorum < 0.4s; manager_integ_test.py:
325-368 deadline enforcement < 1s) — here measured on the full kill/heal
path with real process kills (torchft_tpu/faultinject/recovery.py).

Bounds are deliberately loose multiples of the configured detection
cadence (1s op timeout, 1s heartbeat lease) so the test is about the
*mechanism* (bounded detection + flush re-quorum + heal), not scheduler
luck.
"""

import pytest

from torchft_tpu.faultinject.recovery import measure_recovery

# multi-process soak tier: excluded from the default run (pyproject
# addopts); execute with `pytest -m soak`
pytestmark = pytest.mark.soak


def test_recovery_envelope():
    r = measure_recovery(
        total_steps=25,
        kill_at_step=6,
        step_sleep=0.05,
        op_timeout=1.0,
        heartbeat_timeout_ms=1000,
        timeout_s=120.0,
    )
    # survivor: one wedged op (<= op timeout) + flush re-quorum; 6s allows
    # a heartbeat-lease wait plus CI scheduling noise
    assert r.survivor_blackout_s < 6.0, r
    # rejoiner: exec + store bootstrap + quorum join + live heal + 1 step
    assert r.rejoin_to_commit_s < 20.0, r
    # the envelope in step units: the survivor must keep committing —
    # after the blackout it may not silently skip further steps
    assert r.steady_step_s > 0


def test_recovery_1of4_north_star_shape():
    """The north star: survive killing 1-of-4 replica groups. The
    three survivors must keep committing through the blackout and the
    victim must rejoin and commit."""
    r = measure_recovery(
        total_steps=25,
        kill_at_step=6,
        step_sleep=0.05,
        op_timeout=1.0,
        heartbeat_timeout_ms=1000,
        timeout_s=120.0,
        num_groups=4,
    )
    assert r.survivor_blackout_s < 6.0, r
    assert r.rejoin_to_commit_s < 20.0, r


def test_recovery_1of4_one_step_envelope():
    """Round-4: with the death watch (socket-FIN-driven evict + early
    re-quorum overlapping the doomed step), killing 1-of-4 groups must
    cost the survivors at most ONE committed step (the reference's
    product promise, README.md:29-47). No clock decides it. The lease and
    the join wait are longer than the harness waits for anything
    (``timeout_s``), so the lighthouse counts the killed group among the
    living until somebody evicts it: without an eviction the survivors'
    next quorum cannot form and the run ends in the harness's
    TimeoutError. With it, the envelope is a count: the survivor's
    attempts that did not commit.

    What it cannot tell apart: the watch switched off while a failed op
    still reports its dead peer. That run also loses one attempt, which
    blocks for the op deadline (tests/test_manager.py holds the watch's
    own re-quorum). The deadline stays at 5 s because the watch does not
    end every doomed step: a survivor that left the step early leaves the
    two others in a reduce that only the deadline ends. On a busy host
    that can follow the doomed step's own abort, two attempts lost, so
    one retry is allowed, LOGGED, and every run's envelope lands in the
    failure message: a degrading envelope shows up as retry noise in CI
    history instead of being masked (round-4 review weak #6)."""
    import warnings

    runs = []
    for attempt in range(2):
        r = measure_recovery(
            total_steps=25,
            kill_at_step=6,
            step_sleep=0.05,
            op_timeout=5.0,
            heartbeat_timeout_ms=300_000,
            join_timeout_ms=300_000,
            timeout_s=120.0,
            num_groups=4,
        )
        runs.append(r.as_dict())
        # whatever the count, the eviction is what let the run end
        assert r.evictions_total >= 1 and r.requorum_without_victim, runs
        if r.survivor_failed_attempts <= 1:
            break
        warnings.warn(
            f"recovery envelope attempt {attempt} lost more than 1 attempt: "
            f"{runs[-1]} (retrying once; a persistent retry pattern here "
            "means the envelope is degrading)",
            stacklevel=1,
        )
    assert runs[-1]["survivor_failed_attempts"] <= 1, {"all_attempts": runs}
