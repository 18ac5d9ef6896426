"""Observability layer: recorder mechanics, spans, exporters, forensics —
and the zero-perturbation pin: a trace-on service replays the trace-off
service bit for bit, ``spend_trajectory`` included.

The flight recorder watches the streaming service's lifecycle; it must
never join the decision path.  These tests pin both halves: the obs
machinery itself (ring bounds, full-history counts, JSONL round trips,
the validators' teeth against known-bad sequences) and the contract that
turning it on changes nothing the determinism contract covers.
"""

import json
import threading

import pytest

from repro.core import RunRequest, Settings, run_queue
from repro.obs import (EVENT_KINDS, PHASES, PINNED_OUTCOME_FIELDS,
                       TERMINAL_KINDS, Event, FlightRecorder, diff_outcomes,
                       dump_divergence, metrics_to_prometheus, phase_span,
                       read_trace_jsonl, validate_lifecycle, validate_trace,
                       write_trace_jsonl)
from repro.service import ServiceConfig, StreamingTuner
from tests.test_batched_harness import (_assert_outcomes_equal,
                                        _distinct_geometry_jobs)


# --------------------------------------------------------------------------- #
# FlightRecorder mechanics
# --------------------------------------------------------------------------- #
def test_recorder_ring_bounds_and_full_history_counts():
    rec = FlightRecorder(capacity=4)
    for i in range(10):
        rec.emit("submit", ticket=i)
    assert len(rec) == 4
    assert rec.dropped == 6
    assert [e.ticket for e in rec.events()] == [6, 7, 8, 9]
    # counts survive ring eviction — that is the counter-balance side
    assert rec.counts() == {"submit": 10}
    rec.clear()
    assert len(rec) == 0 and rec.counts() == {} and rec.dropped == 0
    rec.emit("submit", ticket=99)
    assert rec.events()[0].seq == 11, "seq must never be reused after clear"


def test_recorder_rejects_unknown_kind_and_bad_capacity():
    rec = FlightRecorder()
    with pytest.raises(ValueError, match="unknown event kind"):
        rec.emit("teleport", ticket=1)
    with pytest.raises(ValueError, match="capacity"):
        FlightRecorder(capacity=0)


def test_disabled_recorder_is_a_no_op():
    rec = FlightRecorder(enabled=False)
    rec.emit("submit", ticket=1)
    rec.emit("nonsense-not-even-validated")   # disabled: not even checked
    assert len(rec) == 0 and rec.counts() == {}


def test_recorder_seq_and_time_monotone_under_threads():
    rec = FlightRecorder(capacity=10_000)

    def hammer(tid):
        for _ in range(200):
            rec.emit("stage", ticket=tid)

    threads = [threading.Thread(target=hammer, args=(i,)) for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert validate_trace(rec.events()) == []
    assert rec.counts()["stage"] == 800


def test_event_jsonl_round_trip(tmp_path):
    rec = FlightRecorder()
    rec.emit("seat", ticket=3, slot=1, segment=2, via="host")
    rec.emit("dispatch", segment=2, steps=5, busy=8)
    path = rec.dump_jsonl(tmp_path / "trace.jsonl")
    back = read_trace_jsonl(path)
    assert back == rec.events()
    assert back[0].data == {"via": "host"}
    # and the writer helper produces the identical artifact
    p2 = write_trace_jsonl(rec.events(), tmp_path / "t2.jsonl")
    assert p2.read_text() == path.read_text()
    assert json.loads(path.read_text().splitlines()[0])["kind"] == "seat"


def test_terminal_kinds_are_event_kinds():
    assert TERMINAL_KINDS <= EVENT_KINDS
    assert "span" in EVENT_KINDS and "dispatch" in EVENT_KINDS


# --------------------------------------------------------------------------- #
# phase_span
# --------------------------------------------------------------------------- #
def test_phase_span_times_and_attributes_compiles():
    rec = FlightRecorder()
    with phase_span(rec, "dispatch", segment=0, compiles=True):
        pass
    (e,) = rec.events()
    assert e.kind == "span" and e.data["phase"] == "dispatch"
    assert e.data["dur_s"] >= 0.0
    # cache deltas: nothing compiled inside an empty body
    assert e.data["episode_compiles"] == 0
    assert e.data["selector_compiles"] == 0


def test_phase_span_emits_even_when_body_raises():
    rec = FlightRecorder()
    with pytest.raises(RuntimeError):
        with phase_span(rec, "device_block"):
            raise RuntimeError("crashed dispatch")
    (e,) = rec.events()
    assert e.data["phase"] == "device_block"


def test_phase_span_rejects_unknown_phase_and_skips_disabled():
    with pytest.raises(ValueError, match="unknown phase"):
        with phase_span(FlightRecorder(), "warp"):
            pass
    rec = FlightRecorder(enabled=False)
    with phase_span(rec, "seat"):
        pass
    with phase_span(None, "seat"):
        pass
    assert len(rec) == 0


def test_phase_vocabulary_matches_cycle_order():
    assert PHASES == ("stage", "seat", "inject", "dispatch", "device_block",
                      "harvest", "outcome")


# --------------------------------------------------------------------------- #
# Validators' teeth (known-bad sequences must be flagged)
# --------------------------------------------------------------------------- #
def _ev(seq, kind, ticket=None, **data):
    return Event(seq=seq, t=float(seq), kind=kind, ticket=ticket, data=data)


def test_validate_trace_flags_schema_violations():
    bad = [
        Event(seq=1, t=1.0, kind="nope"),
        Event(seq=1, t=0.5, kind="submit"),          # seq + time regress
        Event(seq=2, t=0.6, kind="span", data={"phase": "warp"}),
        Event(seq=3, t=0.7, kind="dispatch"),        # no segment/steps
        Event(seq=4, t=0.8, kind="seat"),            # no ticket
    ]
    issues = validate_trace(bad)
    for frag in ("unknown kind", "seq not increasing", "backwards",
                 "unknown phase", "without a segment", "without a ticket"):
        assert any(frag in i for i in issues), frag


def test_validate_lifecycle_flags_ordering_violations():
    seat_without_admit = [_ev(1, "seat", ticket=1)]
    resolve_after_cancel = [
        _ev(1, "submit", ticket=1), _ev(2, "admit", ticket=1),
        _ev(3, "cancel_request", ticket=1), _ev(4, "cancel", ticket=1),
        _ev(5, "resolve", ticket=1),
    ]
    cancel_unrequested = [_ev(1, "submit", ticket=1),
                          _ev(2, "cancel", ticket=1)]
    resume_unpreempted = [
        _ev(1, "submit", ticket=1), _ev(2, "admit", ticket=1),
        _ev(3, "stage", ticket=1), _ev(4, "seat", ticket=1),
        _ev(5, "resume", ticket=1),
    ]
    assert any("from state 'new'" in i
               for i in validate_lifecycle(seat_without_admit))
    assert any("after a terminal" in i
               for i in validate_lifecycle(resolve_after_cancel))
    assert any("without a prior cancel_request" in i
               for i in validate_lifecycle(cancel_unrequested))
    assert any("without a prior preempt" in i
               for i in validate_lifecycle(resume_unpreempted))


def test_validate_lifecycle_accepts_the_full_happy_path():
    good = [
        _ev(1, "submit", ticket=1), _ev(2, "admit", ticket=1),
        _ev(3, "stage", ticket=1), _ev(4, "inject", ticket=1),
        _ev(5, "seat", ticket=1), _ev(6, "evict", ticket=1),
        _ev(7, "preempt", ticket=1), _ev(8, "stage", ticket=1),
        _ev(9, "seat", ticket=1), _ev(10, "resume", ticket=1),
        _ev(11, "harvest", ticket=1), _ev(12, "resolve", ticket=1),
    ]
    assert validate_lifecycle(good, require_terminal=True) == []
    # an undrained ticket only fails under require_terminal
    pending = good[:5]
    assert validate_lifecycle(pending) == []
    assert any("never reached a terminal" in i
               for i in validate_lifecycle(pending, require_terminal=True))


# --------------------------------------------------------------------------- #
# Exporters + forensics
# --------------------------------------------------------------------------- #
def test_prometheus_rendering_types_and_values():
    from repro.service.metrics import MetricsRecorder
    rec = MetricsRecorder(lane_slots=2)
    rec.record_submit()
    rec.record_resolve(0.5, nex=4)
    text = metrics_to_prometheus(rec.snapshot())
    assert "# TYPE lynceus_service_resolved counter" in text
    assert "# TYPE lynceus_service_lane_occupancy gauge" in text
    assert "lynceus_service_resolved 1" in text
    assert "lynceus_service_latency_floor_s 0.5" in text
    # every line is either a TYPE annotation or "<series> <float>"
    for line in text.strip().splitlines():
        if not line.startswith("# TYPE "):
            name, value = line.split()
            assert name.startswith("lynceus_service_")
            float(value)


def test_diff_outcomes_and_divergence_artifact(tmp_path):
    class O:
        def __init__(self, nex, spent):
            self.explored, self.recommended, self.cno = (1, 2), 2, 0.5
            self.nex, self.spent, self.budget = nex, spent, 3.0
            self.found_optimum, self.censored = True, set()
            self.trajectory, self.spend_trajectory = (0.5,), (spent,)

    a, b = O(2, 1.0), O(3, 1.5)
    assert diff_outcomes([a], [a]) == []
    diffs = diff_outcomes([a], [b])
    assert any("nex differs" in d for d in diffs)
    assert any("spend_trajectory differs" in d for d in diffs)

    rec = FlightRecorder()
    rec.emit("submit", ticket=1)
    p0 = dump_divergence("unit", expected=[a], actual=[b], recorder=rec,
                         context={"suite": "test_obs"}, out_dir=tmp_path)
    p1 = dump_divergence("unit", expected=[a], actual=[b],
                         out_dir=tmp_path)
    assert p0 != p1, "repeated failures must not overwrite each other"
    art = json.loads(p0.read_text())
    assert art["diffs"] and art["context"] == {"suite": "test_obs"}
    assert art["expected"][0]["nex"] == 2 and art["actual"][0]["nex"] == 3
    assert art["flight_record"][0]["kind"] == "submit"
    assert set(art["expected"][0]) == set(PINNED_OUTCOME_FIELDS)


# --------------------------------------------------------------------------- #
# The zero-perturbation pin + an end-to-end traced service
# --------------------------------------------------------------------------- #
_JOBS = _distinct_geometry_jobs()
_REQS = [RunRequest(_JOBS[r % 3], seed=770 + r,
                    budget_b=4.0 if r % 2 == 0 else 1.5) for r in range(6)]
_SETTINGS = Settings(policy="lynceus", la=1, k_gh=2, refit="frozen")


def _drive(cfg: ServiceConfig) -> tuple[list, StreamingTuner]:
    svc = StreamingTuner(_JOBS, _SETTINGS, cfg)
    tickets = []
    for i, r in enumerate(_REQS):
        tickets.append(svc.submit(r, priority=i % 2))
        if i % 2:
            svc.pump()
    svc.drain()
    return [t.result() for t in tickets], svc


def test_trace_on_replays_trace_off_bit_for_bit():
    """The acceptance pin: a full streaming run with tracing enabled
    yields outcomes bit-identical to the trace-off run AND to the
    sequential oracle — spend_trajectory included via the shared
    comparator.  The recorder watches; it never perturbs."""
    base = dict(lane_slots=2, queue_capacity=3, step_quota=6, high_water=0)
    off, _ = _drive(ServiceConfig(**base))
    on, svc = _drive(ServiceConfig(**base, trace=True))
    _assert_outcomes_equal(off, on, recorder=svc.recorder,
                           tag="trace_on_vs_off")
    _assert_outcomes_equal(run_queue(_REQS, _SETTINGS), on,
                           recorder=svc.recorder, tag="trace_on_vs_oracle")
    assert len(svc.flight_record()) > 0


def test_traced_service_record_is_valid_and_complete(tmp_path):
    """End-to-end over the real service: the trace passes both validators
    (terminal for every ticket), covers every lifecycle stage the drive
    exercised, spans cover every phase, and the JSONL dump round-trips."""
    cfg = ServiceConfig(lane_slots=2, queue_capacity=3, step_quota=6,
                        high_water=0, trace=True, trace_capacity=8192)
    outs, svc = _drive(cfg)
    events = svc.flight_record()
    assert validate_trace(events) == []
    assert validate_lifecycle(events, require_terminal=True) == []
    counts = svc.recorder.counts()
    assert counts["submit"] == counts["admit"] == len(_REQS)
    assert counts["resolve"] == counts["harvest"] == len(_REQS)
    assert counts["dispatch"] >= 1
    phases = {e.data["phase"] for e in events if e.kind == "span"}
    assert phases == set(PHASES)
    # dispatch spans carry compile attribution (deltas are >= 0; the
    # programs may already sit in the global cache from earlier tests)
    disp = [e for e in events if e.kind == "span"
            and e.data["phase"] == "dispatch"]
    assert all(e.data["episode_compiles"] >= 0
               and e.data["selector_compiles"] >= 0 for e in disp)
    path = svc.dump_trace(tmp_path / "svc.jsonl")
    assert read_trace_jsonl(path) == events


def test_untraced_service_records_nothing():
    outs, svc = _drive(ServiceConfig(lane_slots=2, queue_capacity=3,
                                     step_quota=6))
    assert svc.flight_record() == []
    assert svc.recorder.counts() == {}


def test_trace_profiler_requires_trace():
    with pytest.raises(ValueError, match="trace_profiler requires"):
        ServiceConfig(trace_profiler=True)
    cfg = ServiceConfig(lane_slots=2, queue_capacity=3, step_quota=6,
                        trace=True, trace_profiler=True)
    svc = StreamingTuner(_JOBS[:1], _SETTINGS, cfg)
    t = svc.submit(_REQS[0])
    svc.drain()
    assert t.result().nex > 0       # profiler scopes are naming only


def test_obs_report_renders_a_real_trace(tmp_path, capsys):
    """scripts/obs_report.py over a real drained-service trace: exit 0,
    every section present, and the validator gate trips on a corrupted
    trace (nonzero exit)."""
    import pathlib
    import sys
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent
                           / "scripts"))
    import obs_report
    cfg = ServiceConfig(lane_slots=2, queue_capacity=3, step_quota=6,
                        trace=True)
    _, svc = _drive(cfg)
    path = svc.dump_trace(tmp_path / "trace.jsonl")
    argv = sys.argv
    try:
        sys.argv = ["obs_report.py", str(path), "--require-terminal"]
        assert obs_report.main() == 0
        out = capsys.readouterr().out
        for frag in ("0 issue(s)", "per-ticket timeline",
                     "per-slot occupancy", "phase spans"):
            assert frag in out
        # corrupt the trace: resolve for a ticket that never submitted
        with path.open("a") as f:
            f.write(json.dumps({"seq": 10**6, "t": 10.0**6,
                                "kind": "resolve", "ticket": 424242}) + "\n")
        sys.argv = ["obs_report.py", str(path)]
        assert obs_report.main() == 1
    finally:
        sys.argv = argv


# --------------------------------------------------------------------------- #
# Device scopes: op metadata only
# --------------------------------------------------------------------------- #
def _scopes_off(monkeypatch):
    """Trace every program as if no scope were applied (fresh caches, so
    nothing traced with the scopes is reused)."""
    import contextlib

    import jax

    from repro.core import lookahead, optimizer, trees
    for mod in (lookahead, optimizer, trees):
        monkeypatch.setattr(mod, "scope",
                            lambda name: contextlib.nullcontext())
    jax.clear_caches()


@pytest.mark.parametrize("program", ["episode/segment",
                                     "episode/segment/bucketed",
                                     "selector/lynceus/native/fused",
                                     "selector/lynceus/native/timeout"])
def test_scopes_leave_the_program_signature_unchanged(program, monkeypatch):
    """A named scope adds no jaxpr equation: the ``repro.analysis``
    signature of each audited program is the same with and without
    them."""
    import jax

    from repro.analysis import registered_programs, signature
    spec = next(p for p in registered_programs() if p.name == program)
    jax.clear_caches()
    fn, example, _ = spec.build()
    scoped = signature(fn, *example)
    _scopes_off(monkeypatch)
    fn, example, _ = spec.build()
    assert signature(fn, *example) == scoped


def test_scopes_leave_served_outcomes_bit_identical(monkeypatch):
    """The same queue served with and without the scopes gives the same
    outcomes, ``spend_trajectory`` included (exact refit, so every fit
    scope is on the path)."""
    s = Settings(policy="lynceus", la=1, k_gh=2, n_trees=3, depth=2,
                 refit="exact", timeout=True)
    scoped = run_queue(_REQS[:3], s)
    _scopes_off(monkeypatch)
    _assert_outcomes_equal(scoped, run_queue(_REQS[:3], s),
                           tag="scopes_on_vs_off")


def test_scope_vocabulary():
    from repro.obs import SCOPES, scope
    assert SCOPES == ("lynceus", "segment", "speculate", "fit",
                      "fit/bootstrap", "fit/split_stats", "fit/split_choice",
                      "fit/route", "select_step")
    with pytest.raises(ValueError, match="unknown scope"):
        scope("fit/warp")
