"""Shard placement: routing policies, sticky affinity, backpressure.

``service/placement.py`` is pure host-side arithmetic — these tests pin
its decisions exactly (deterministic: equal loads resolve to the lowest
shard id) and then watch the broker apply them: least-backlog balances a
burst, sticky affinity survives preemption and resume, ``num_shards=1``
degenerates to shard 0 everywhere, and ``max_pending`` backpressure stays
a service-wide (not per-shard) cap that raises :class:`QueueFull`
deterministically.
"""

import threading
import time

import pytest

from repro.core import RunRequest, Settings, run_queue
from repro.jobs import synthetic_job
from repro.service import (QueueFull, ServiceConfig, StreamingTuner)
from repro.service.placement import (PLACEMENT_POLICIES, choose_shard,
                                     shard_meshes, shard_shardings)
from tests.test_batched_harness import _assert_outcomes_equal


# --------------------------------------------------------------------------- #
# choose_shard: the pure policy functions
# --------------------------------------------------------------------------- #
def test_least_backlog_picks_min_lowest_id_ties():
    assert choose_shard("least_backlog", [3, 1, 2]) == 1
    assert choose_shard("least_backlog", [2, 1, 1]) == 1   # tie -> lowest
    assert choose_shard("least_backlog", [0, 0, 0]) == 0


def test_round_robin_ignores_loads():
    assert choose_shard("round_robin", [5, 0], rr=0) == 0
    assert choose_shard("round_robin", [5, 0], rr=1) == 1
    assert choose_shard("round_robin", [5, 0, 0], rr=7) == 1


def test_sticky_home_short_circuits_every_policy():
    for policy in PLACEMENT_POLICIES:
        assert choose_shard(policy, [9, 0], home=0) == 0
    with pytest.raises(ValueError, match="out of range"):
        choose_shard("least_backlog", [1, 1], home=2)


def test_single_shard_is_always_zero():
    for policy in PLACEMENT_POLICIES:
        assert choose_shard(policy, [7]) == 0
    with pytest.raises(ValueError):
        choose_shard("least_backlog", [])


def test_unknown_policy_raises():
    with pytest.raises(ValueError, match="unknown placement_policy"):
        choose_shard("hash", [1, 2])
    with pytest.raises(ValueError, match="placement_policy"):
        ServiceConfig(placement_policy="hash")


def test_shard_meshes_modulo_device_mapping():
    import jax
    devs = jax.devices()
    meshes = shard_meshes(len(devs) + 2)
    assert [m.devices.ravel()[0] for m in meshes[:len(devs)]] == devs
    assert meshes[len(devs)].devices.ravel()[0] == devs[0]   # wraps
    for sh in shard_shardings(2):
        assert sh.is_fully_replicated       # placement, never partitioning


# --------------------------------------------------------------------------- #
# The broker applying the policies
# --------------------------------------------------------------------------- #
def _jobs():
    return [synthetic_job(i, name=f"syn{i}") for i in range(2)]


def _reqs(jobs, n, seed0=130):
    return [RunRequest(jobs[r % 2], seed=seed0 + r, budget_b=1.5)
            for r in range(n)]


def test_broker_least_backlog_balances_burst():
    """A pre-pump burst alternates shards: each submit sees the loads the
    previous one left behind (lowest id breaking the initial tie)."""
    jobs = _jobs()
    s = Settings(policy="la0", la=0, k_gh=2)
    svc = StreamingTuner(jobs, s, ServiceConfig(lane_slots=2,
                                                queue_capacity=2,
                                                step_quota=6,
                                                num_shards=2))
    tickets = [svc.submit(q) for q in _reqs(jobs, 6)]
    assert [t.shard for t in tickets] == [0, 1, 0, 1, 0, 1]
    svc.drain()


def test_broker_round_robin_rotates():
    jobs = _jobs()
    s = Settings(policy="la0", la=0, k_gh=2)
    svc = StreamingTuner(jobs, s, ServiceConfig(
        lane_slots=2, queue_capacity=2, step_quota=6, num_shards=3,
        placement_policy="round_robin"))
    tickets = [svc.submit(q) for q in _reqs(jobs, 6, seed0=200)]
    assert [t.shard for t in tickets] == [0, 1, 2, 0, 1, 2]
    svc.drain()


def test_single_shard_service_places_everything_on_zero():
    jobs = _jobs()
    s = Settings(policy="la0", la=0, k_gh=2)
    svc = StreamingTuner(jobs, s, ServiceConfig(lane_slots=2,
                                                queue_capacity=2,
                                                step_quota=6))
    tickets = [svc.submit(q) for q in _reqs(jobs, 4, seed0=260)]
    assert all(t.shard == 0 for t in tickets)
    svc.drain()


def test_sticky_affinity_survives_preempt_and_resume():
    """A preempted ticket re-queues to its home shard and resumes there:
    its whole shard-tagged event stream names one shard, and its final
    Outcome is byte-identical to the uninterrupted oracle."""
    jobs = _jobs()
    s = Settings(policy="lynceus", la=1, k_gh=2, refit="frozen")
    reqs = _reqs(jobs, 5, seed0=320)
    reqs[0] = RunRequest(jobs[0], seed=320, budget_b=5.0)  # long victim
    seq = run_queue(reqs, s)
    svc = StreamingTuner(jobs, s, ServiceConfig(
        lane_slots=1, queue_capacity=3, step_quota=3, high_water=0,
        num_shards=2, trace=True))
    victim = svc.submit(reqs[0], priority=5)
    svc.pump()                           # seats the low-prio victim
    tickets = [victim] + [svc.submit(q) for q in reqs[1:]]
    svc.pump()
    svc.drain()
    assert victim.preemptions >= 1
    _assert_outcomes_equal(seq, [t.result() for t in tickets])
    home = victim.shard
    seen = {e.data["shard"] for e in svc.flight_record()
            if e.ticket == victim.id and "shard" in e.data}
    assert seen == {home}
    # resume happened on the home engine, nowhere else
    resumes = [e for e in svc.flight_record()
               if e.kind == "resume" and e.ticket == victim.id]
    assert resumes and all(e.data["shard"] == home for e in resumes)


def test_backpressure_is_service_wide_and_deterministic():
    """``max_pending`` caps outstanding tickets across ALL shards: the
    third submit raises QueueFull even though shard 1's backlog alone is
    below the cap; block=True then makes room by pumping inline."""
    jobs = _jobs()
    s = Settings(policy="la0", la=0, k_gh=2)
    reqs = _reqs(jobs, 4, seed0=380)
    svc = StreamingTuner(jobs, s, ServiceConfig(
        lane_slots=2, queue_capacity=2, step_quota=32, max_pending=2,
        num_shards=2))
    t0 = svc.submit(reqs[0])
    t1 = svc.submit(reqs[1])
    assert {t0.shard, t1.shard} == {0, 1}
    with pytest.raises(QueueFull):
        svc.submit(reqs[2], block=False)
    t2 = svc.submit(reqs[2], block=True)
    assert t0.done() or t1.done()
    t3 = svc.submit(reqs[3], block=True)
    svc.drain()
    for t in (t0, t1, t2, t3):
        assert t.state == "done"


@pytest.mark.parametrize("window", ["before_seat", "device_queue"])
def test_least_backlog_counts_staged_tickets_while_pump_runs(window):
    """Submitting to a live service: a ticket the pump has staged is out of
    its admission buffer before it holds a slot — briefly before seating,
    or for a whole segment while it waits in the device queue.  Placement
    must still count it, or the next submit piles onto the same shard.
    Shard 0's engine is held inside that window while the next ticket is
    submitted."""
    jobs = _jobs()
    s = Settings(policy="la0", la=0, k_gh=2)
    reqs = _reqs(jobs, 4, seed0=440)
    reqs[:2] = [RunRequest(q.job, q.seed, budget_b=5.0)  # 10 and 8 steps
                for q in reqs[:2]]
    svc = StreamingTuner(jobs, s, ServiceConfig(
        lane_slots=1, queue_capacity=2, step_quota=1, num_shards=2))
    eng = svc._engines.shards[0]
    entered, release = threading.Event(), threading.Event()

    if window == "before_seat":
        run_segment = eng.run_segment

        def hooked(staged, *a):
            if staged and not entered.is_set():
                entered.set()
                release.wait(60)
            return run_segment(staged, *a)
        eng.run_segment = hooked
        svc.start()
        first = [svc.submit(reqs[0])]
    else:
        seat = eng._seat

        def hooked(staged):
            rest, n = seat(staged)
            if rest and not entered.is_set():
                entered.set()
                release.wait(60)
            return rest, n
        eng._seat = hooked
        first = [svc.submit(q) for q in reqs[:2]]    # one per shard
        assert [t.shard for t in first] == [0, 1]
        svc.start()
        deadline = time.perf_counter() + 60
        while not all(e.in_flight() for e in svc._engines.shards):
            assert time.perf_counter() < deadline
            time.sleep(0.01)
        first.append(svc.submit(reqs[2]))     # tie -> shard 0's queue
        assert first[-1].shard == 0
    try:
        assert entered.wait(60), "the pump never staged into shard 0"
        late = svc.submit(reqs[3])
        release.set()
        assert late.shard == 1
        for t in first + [late]:
            t.result(timeout=120)
    finally:
        release.set()
        svc.stop()
