"""ServiceMetrics edge cases: empty windows, single samples, wraparound.

The recorder feeds the streaming benchmark gates, so its degenerate states
must read sensibly rather than divide by zero or report phantom work: a
fresh (or reset) recorder is all-zeros, one sample pins every percentile,
the bounded latency window really forgets old samples while the mean keeps
full history, and a reset taken mid-flight never yields a negative
outstanding count.
"""

import numpy as np
import pytest

from repro.service.metrics import MetricsRecorder


def test_empty_snapshot_is_all_zeros_and_finite():
    m = MetricsRecorder(lane_slots=4).snapshot()
    assert m.segments == 0 and m.steps == 0 and m.busy_slot_steps == 0
    assert m.submitted == 0 and m.resolved == 0 and m.outstanding == 0
    assert m.lane_occupancy == 0.0
    assert m.runs_per_second == 0.0
    assert m.explorations_per_second == 0.0
    assert m.queue_depth_mean == 0.0 and m.queue_depth_max == 0
    assert m.latency_mean_s == 0.0
    assert m.latency_p50_s == 0.0 and m.latency_p95_s == 0.0
    for f in m.__dataclass_fields__:
        assert np.isfinite(getattr(m, f))


def test_single_sample_pins_percentiles_and_means():
    rec = MetricsRecorder(lane_slots=2)
    rec.record_submit()
    rec.record_segment(steps=5, busy_slot_steps=7, wall_seconds=2.0,
                       queue_depth=3, boot_draw_points=640)
    rec.record_resolve(latency_seconds=0.25, nex=12)
    m = rec.snapshot()
    assert m.boot_draw_points == 640
    assert m.latency_p50_s == m.latency_p95_s == m.latency_mean_s == 0.25
    assert m.outstanding == 0
    assert m.lane_occupancy == pytest.approx(7 / (5 * 2))
    assert m.runs_per_second == pytest.approx(0.5)
    assert m.explorations_per_second == pytest.approx(6.0)
    assert m.queue_depth_mean == 3.0 and m.queue_depth_max == 3


def test_bounded_window_wraparound_forgets_old_latencies():
    """Percentiles run over the most recent ``latency_window`` samples only
    — after wraparound the early (here: huge) latencies must vanish from
    p50/p95 while the full-history mean still remembers them."""
    rec = MetricsRecorder(lane_slots=1, latency_window=4)
    lat = [100.0, 100.0, 100.0, 1.0, 2.0, 3.0, 4.0]
    for v in lat:
        rec.record_submit()
        rec.record_resolve(v, nex=1)
    m = rec.snapshot()
    assert m.latency_p50_s == pytest.approx(np.percentile([1, 2, 3, 4], 50))
    assert m.latency_p95_s == pytest.approx(np.percentile([1, 2, 3, 4], 95))
    assert m.latency_p95_s < 5.0, "evicted sample leaked into the window"
    assert m.latency_mean_s == pytest.approx(np.mean(lat))
    assert m.resolved == len(lat)


def test_window_exactly_full_keeps_every_sample():
    """Boundary case: exactly ``latency_window`` samples — nothing evicted,
    percentiles over all of them (an off-by-one window would drop one)."""
    rec = MetricsRecorder(lane_slots=1, latency_window=4)
    for v in (1.0, 2.0, 3.0, 4.0):
        rec.record_submit()
        rec.record_resolve(v, nex=1)
    m = rec.snapshot()
    assert m.latency_p50_s == pytest.approx(2.5)
    assert m.latency_mean_s == pytest.approx(2.5)


def test_reset_mid_flight_never_reports_negative_outstanding():
    """reset() while runs are in flight zeroes the submit counter; their
    later resolutions must read as zero outstanding, not negative."""
    rec = MetricsRecorder(lane_slots=1)
    for _ in range(3):
        rec.record_submit()
    rec.reset()
    rec.record_resolve(0.1, nex=2)      # in-flight run lands post-reset
    m = rec.snapshot()
    assert m.outstanding == 0
    assert m.resolved == 1


def test_reset_zeroes_everything():
    rec = MetricsRecorder(lane_slots=2, latency_window=8)
    rec.record_submit()
    rec.record_segment(3, 4, 1.0, 2)
    rec.record_resolve(0.5, nex=7)
    rec.reset()
    m = rec.snapshot()
    assert (m.segments, m.submitted, m.resolved, m.explorations) == (0,) * 4
    assert m.latency_p95_s == 0.0 and m.serve_seconds == 0.0


def test_latency_floor_survives_reset():
    """The floor is the deadline-admission bound — a lifetime property,
    not a window counter.  If reset() dropped it, a post-warmup
    ``reset_metrics()`` would make ``deadline_policy="reject"`` silently
    admit every unmeetable deadline until the next resolution re-primed
    it."""
    rec = MetricsRecorder(lane_slots=1)
    rec.record_submit()
    rec.record_resolve(0.25, nex=3)
    assert rec.latency_floor() == pytest.approx(0.25)
    rec.reset()
    assert rec.latency_floor() == pytest.approx(0.25)
    m = rec.snapshot()
    assert m.resolved == 0                       # window did reset
    assert m.latency_floor_s == pytest.approx(0.25)
    rec.record_submit()
    rec.record_resolve(0.1, nex=1)               # a faster run lowers it
    assert rec.latency_floor() == pytest.approx(0.1)


def test_p99_and_floor_in_snapshot():
    rec = MetricsRecorder(lane_slots=1)
    lat = [float(i) for i in range(1, 101)]
    for v in lat:
        rec.record_submit()
        rec.record_resolve(v, nex=1)
    m = rec.snapshot()
    assert m.latency_p99_s == pytest.approx(np.percentile(lat, 99))
    assert m.latency_p95_s <= m.latency_p99_s
    assert m.latency_floor_s == pytest.approx(1.0)
    d = m.to_dict()
    assert d["latency_p99_s"] == m.latency_p99_s
    assert set(d) == set(m.__dataclass_fields__)


def test_zero_wall_segments_do_not_divide_by_zero():
    """Segments can complete in ~0 wall seconds on mocked clocks; rate
    denominators must degrade to zero, not raise."""
    rec = MetricsRecorder(lane_slots=2)
    rec.record_segment(steps=1, busy_slot_steps=2, wall_seconds=0.0,
                       queue_depth=0)
    m = rec.snapshot()
    assert m.runs_per_second == 0.0
    assert m.explorations_per_second == 0.0
    assert m.lane_occupancy == pytest.approx(1.0)


def test_invalid_window_rejected():
    with pytest.raises(ValueError, match="latency_window"):
        MetricsRecorder(lane_slots=1, latency_window=0)


# --------------------------------------------------------------------------- #
# Aggregation across shard recorders (MetricsRecorder.aggregate)
# --------------------------------------------------------------------------- #
import os  # noqa: E402

try:
    if os.environ.get("REPRO_NO_HYPOTHESIS"):
        raise ImportError("fallback forced by REPRO_NO_HYPOTHESIS")
    from hypothesis import given, settings, strategies as st
except ImportError:          # no-network CI: deterministic fallback
    from _hypothesis_fallback import given, settings, st


def _drive(rec: MetricsRecorder, rng: np.random.Generator,
           events: int) -> None:
    """Random but well-formed per-shard history."""
    for _ in range(events):
        k = int(rng.integers(0, 8))
        if k == 0:
            rec.record_submit()
        elif k == 1:
            rec.record_resolve(float(rng.uniform(0.01, 2.0)),
                               int(rng.integers(1, 9)))
        elif k == 2:
            rec.record_cancel()
        elif k == 3:
            rec.record_segment(int(rng.integers(1, 9)),
                               int(rng.integers(0, 17)),
                               float(rng.uniform(0.001, 0.1)),
                               int(rng.integers(0, 5)),
                               int(rng.integers(0, 3073)))
        elif k == 4:
            rec.record_preempt()
        elif k == 5:
            rec.record_resume()
        elif k == 6:
            rec.record_slo_miss()
        else:
            rec.record_deadline_reject()


def test_aggregate_of_one_recorder_is_its_snapshot():
    """Degenerate fleet: aggregating a single shard must reproduce its
    own snapshot field for field (the num_shards=1 service's metrics()
    are byte-identical to the pre-sharding broker's)."""
    rng = np.random.default_rng(7)
    rec = MetricsRecorder(lane_slots=3)
    _drive(rec, rng, 200)
    assert MetricsRecorder.aggregate([rec]) == rec.snapshot()


def test_aggregate_outstanding_not_double_counted():
    """THE aggregation bug this API was designed against: a shard reset
    mid-flight clamps its own outstanding at 0, so summing per-shard
    clamped values overcounts the fleet.  The aggregate must clamp once,
    over raw summed counters."""
    a, b = MetricsRecorder(lane_slots=2), MetricsRecorder(lane_slots=2)
    for _ in range(3):
        a.record_submit()
    a.reset()                        # 3 in flight, counters zeroed
    for _ in range(3):
        a.record_resolve(0.1, 1)     # pre-reset submits resolving now
    b.record_submit()
    b.record_submit()
    assert a.snapshot().outstanding == 0      # per-shard clamp active
    assert b.snapshot().outstanding == 2
    agg = MetricsRecorder.aggregate([a, b])
    # raw sums: submitted 2, resolved 3 -> clamped once -> 0; the naive
    # sum of clamped per-shard values would report 2 phantom tickets.
    assert agg.outstanding == 0
    assert agg.outstanding < (a.snapshot().outstanding
                              + b.snapshot().outstanding)


def test_aggregate_rejects_empty_fleet():
    with pytest.raises(ValueError, match="at least one"):
        MetricsRecorder.aggregate([])


@settings(max_examples=12, deadline=None)
@given(seed=st.integers(0, 999), shards=st.integers(1, 4))
def test_aggregate_properties(seed, shards):
    """Over random per-shard histories with mid-stream resets: counters
    sum raw, outstanding clamps once (never above the naive per-shard
    sum), the latency floor is the fleet min, percentiles pool the
    windows, and the depth max is the fleet max."""
    rng = np.random.default_rng(seed)
    recs = [MetricsRecorder(lane_slots=int(rng.integers(1, 5)))
            for _ in range(shards)]
    for rec in recs:
        _drive(rec, rng, int(rng.integers(0, 80)))
        if rng.random() < 0.3:       # the clamp-activating wrinkle
            rec.reset()
            _drive(rec, rng, int(rng.integers(0, 40)))
    per = [r.snapshot() for r in recs]
    agg = MetricsRecorder.aggregate(recs)
    for f in ("segments", "steps", "busy_slot_steps", "boot_draw_points",
              "submitted", "resolved", "cancelled", "preempted", "resumed",
              "slo_missed", "deadline_rejected", "explorations"):
        assert getattr(agg, f) == sum(getattr(m, f) for m in per), f
    assert agg.lane_slots == sum(m.lane_slots for m in per)
    assert agg.serve_seconds == pytest.approx(
        sum(m.serve_seconds for m in per))
    assert agg.queue_depth_max == max(m.queue_depth_max for m in per)
    assert agg.outstanding == max(
        agg.submitted - agg.resolved - agg.cancelled, 0)
    assert agg.outstanding <= sum(m.outstanding for m in per)
    floors = [m.latency_floor_s for m in per if m.latency_floor_s > 0]
    assert agg.latency_floor_s == (min(floors) if floors else 0.0)
    pooled = [x for r in recs for x in r._latencies]
    if pooled:
        assert agg.latency_p50_s == float(np.percentile(
            np.asarray(pooled, np.float64), 50))
        assert agg.latency_p99_s == float(np.percentile(
            np.asarray(pooled, np.float64), 99))
    else:
        assert agg.latency_p50_s == 0.0
    for f in agg.__dataclass_fields__:
        assert np.isfinite(getattr(agg, f)), f
