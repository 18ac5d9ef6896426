"""The benchmark's own machinery on the CPU: traffic generation, metric
arithmetic, finding cells and metrics by name, the work count, the trace
reduction and the refusals of ``bench/run.py``."""

import json
import math
import os
import pathlib
import shutil
import subprocess
import sys

import numpy as np
import pytest

import _tiny  # noqa: F401  (puts bench/ on the import path)
import data
import run
import traffic
import work
import xplane

FIXTURES = pathlib.Path(__file__).resolve().parent / "fixtures"


@pytest.fixture(scope="module")
def tiny_jobs(tmp_path_factory):
    root = _tiny.make_root(tmp_path_factory.mktemp("tiny"))
    return root, data.make_jobs(root, data.load_json(
        root / "bench" / "configs" / "tiny.json"))


def test_open_schedule_reproduces_from_seed(tiny_jobs):
    _, jobs = tiny_jobs
    mix = _tiny.CELLS["tiny.open"]
    a = traffic.open_schedule(mix, jobs, 2 ** 31 + 17, 20.0)
    b = traffic.open_schedule(mix, jobs, 2 ** 31 + 17, 20.0)
    c = traffic.open_schedule(mix, jobs, 2 ** 31 + 18, 20.0)
    assert a == b and a != c
    assert [d for d, _ in a] == [d for d, _ in c]
    due = [d for d, _ in a]
    assert due == sorted(due) and 0 <= due[0] and due[-1] < 20.0
    # Bursts: several requests share a due time, about rate x seconds.
    assert len(set(due)) < len(due)
    assert 0.5 * 80 < len(a) < 1.5 * 80


def test_closed_requests_depend_only_on_seed_client_index(tiny_jobs):
    _, jobs = tiny_jobs
    mix = _tiny.CELLS["tiny.closed"]
    r1 = traffic.request(mix, jobs, 5, 2, 7)
    assert r1 == traffic.request(mix, jobs, 5, 2, 7)
    assert r1 != traffic.request(mix, jobs, 5, 2, 8)
    space = jobs[r1.job].space
    assert len(set(r1.bootstrap)) == space.bootstrap_size() == 2
    assert r1.b in mix["budgets"] and 0 <= r1.seed < 2 ** 31


def test_every_seed_gets_the_same_sizes_in_another_order(tiny_jobs):
    _, jobs = tiny_jobs
    mix = _tiny.CELLS["tiny.open"]
    a = traffic.open_schedule(mix, jobs, 11, 20.0)
    b = traffic.open_schedule(mix, jobs, 12, 20.0)
    kinds = lambda s: sorted((r.job, r.b) for _, r in s)
    bursts = lambda s: sorted(np.unique([d for d, _ in s],
                                        return_counts=True)[1])
    assert len(a) == len(b) and kinds(a) == kinds(b)
    assert bursts(a) == bursts(b)
    assert [d for d, _ in a] == [d for d, _ in b]
    assert [(r.job, r.b) for _, r in a] != [(r.job, r.b) for _, r in b]
    mix = _tiny.CELLS["tiny.closed"]
    streams = lambda seed: sorted(
        tuple((r.job, r.b) for r in (traffic.request(mix, jobs, seed, c, k)
                                     for k in range(5)))
        for c in range(mix["clients"]))
    assert streams(11) == streams(12)


def test_zipf_popularity_favours_the_first_jobs(tiny_jobs):
    _, jobs = tiny_jobs
    mix = dict(_tiny.CELLS["tiny.open"], popularity={"zipf": 2.0})
    picks = [traffic.request(mix, jobs, 1, 0, i).job for i in range(400)]
    assert picks.count(0) > picks.count(1) > 0


class _Outcome:
    def __init__(self, nex):
        self.nex = nex


def _record(due, settled, status, nex=None, boot=3):
    rec = run.Record(traffic.Request(0, 1, 1.0, tuple(range(boot))),
                     due=due, settled=settled, status=status)
    rec.outcome = None if nex is None else _Outcome(nex)
    return rec


def _reader(name):
    return run.reader(run.ROOT, name)


def _percentile(ctx, q):
    return data.load_module(run.ROOT / "bench" / "metrics"
                            / "_latency.py").percentile(ctx, q)


def test_latency_is_timed_from_the_due_time():
    t0 = 100.0
    recs = [_record(0.5, t0 + 1.5, "done"), _record(2.0, t0 + 2.25, "done"),
            _record(None, t0 + 9.0, "done")]        # not due in the window
    ctx = {"records": recs, "t0": t0}
    assert _percentile(ctx, 50) == 0.25
    assert _percentile(ctx, 95) == 1.0


def test_failed_requests_count_as_missing():
    t0 = 0.0
    recs = [_record(0.0, 0.1, "done") for _ in range(19)]
    recs.append(_record(0.0, None, "failed"))
    ctx = {"records": recs, "t0": t0}
    assert _percentile(ctx, 95) == pytest.approx(0.1)
    recs.append(_record(0.0, 0.2, "refused"))
    assert math.isinf(_percentile(ctx, 95))
    recs += [_record(0.0, None, "failed") for _ in range(20)]
    assert math.isinf(_percentile(ctx, 50))


def test_decisions_come_from_resolved_and_partial_outcomes():
    recs = [_record(None, 1.0, "done", nex=10),       # 7 decisions
            _record(None, 2.0, "cancelled", nex=5),   # partial: 2
            _record(None, 2.0, "cancelled"),          # never seated: 0
            _record(None, 2.0, "cancelled", nex=3)]   # bootstrap only: 0
    assert [r.decisions for r in recs] == [7, 2, 0, 0]
    ctx = {"records": recs, "window_s": 4.5}
    assert _reader("decision_latency_ms")(ctx) == pytest.approx(500.0)
    assert _reader("decision_latency_ms")({"records": recs[2:],
                                           "window_s": 1.0}) is None


def test_new_files_are_found_by_name(tmp_path):
    """A configuration, a traffic mix and a per-layer metric added as new
    files, with entries in BENCHMARK.json, need no edit of any file."""
    root = _tiny.make_root(tmp_path)
    before = {p: p.read_bytes() for p in (root / "bench").rglob("*.py")}
    shutil.copy(root / "bench" / "configs" / "tiny.json",
                root / "bench" / "configs" / "tiny-2.json")
    (root / "bench" / "traffic" / "trickle.json").write_text(json.dumps(
        {"loop": "open", "rate_per_s": 0.5, "burst_mean": 1,
         "budgets": [1], "popularity": "uniform"}))
    (root / "bench" / "metrics" / "steps_seen.py").write_text(
        "def read(ctx):\n    return ctx['m1'].steps - ctx['m0'].steps\n")
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "tiny-2", "source": "test",
                            "file": "bench/configs/tiny-2.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "tiny2.trickle", "config": "tiny-2",
                              "traffic": "trickle", "chips": 1,
                              "why": "test"})
    spec["per_layer"].append({"name": "steps_seen", "unit": "steps",
                              "better": "higher", "source": "program_counter",
                              "layer": "segment", "moves": "setup_s",
                              "workloads": ["tiny2.trickle"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    cell = run.load_cell(root, "tiny2.trickle")
    assert cell.mix["rate_per_s"] == 0.5
    assert cell.config["name"] == "tiny"
    assert "steps_seen" in [m["name"] for m in cell.per_layer]

    class M:
        def __init__(self, steps):
            self.steps = steps
    assert run.reader(root, "steps_seen")({"m0": M(3), "m1": M(10)}) == 7
    assert {p: p.read_bytes() for p in before} == before


def test_work_count_matches_a_hand_count():
    # M=4 points, F=2, one tree of depth 1, lookahead 1 with 2 nodes:
    # states 1 + 4*2 = 9, two calls (the root and level 1).
    w = work.select_step(m=4, f=2, n_trees=1, depth=1, la=1, k_gh=2)
    per_point = 1 * (2 * 1 + 1) + (1 + 1) + (3 * 1 + 2) + 4 + 55
    assert w["states"] == 9 and w["calls"] == 2
    assert w["ops"] == 9 * 4 * per_point == 9 * 4 * 69
    per_state = 1 * 1 * 1 * 8 + 1 * 2 * 4 + 4 * 6 + 16
    assert w["bytes"] == 9 * per_state + 2 * (2 + 1) * 4 * 4
    t, bound = work.roofline_s(w, {"vpu_f32_ops_per_s": 1e9,
                                   "bytes_per_s": 1e9})
    assert bound == "compute" and t == pytest.approx(w["ops"] / 1e9)
    t, bound = work.roofline_s(w, {"vpu_f32_ops_per_s": 1e12,
                                   "bytes_per_s": 1e9})
    assert bound == "memory" and t == pytest.approx(w["bytes"] / 1e9)


def test_peak_table_names_its_source_and_the_v5e():
    peaks = data.load_json(run.ROOT / "bench" / "peaks.json")
    assert "TPU v5e" in peaks["source"]
    v5e = peaks["devices"]["TPU v5 lite"]
    assert v5e["flops_per_s"] == 197e12 and v5e["bytes_per_s"] == 819e9
    assert "bench/vpu_peak.py" in peaks["source"]
    assert 1e12 < v5e["vpu_f32_ops_per_s"] < v5e["flops_per_s"]
    with pytest.raises(KeyError):
        run.peaks_for(run.ROOT, "cpu")


def test_trace_reduction_on_synthetic_events():
    ms = 1_000_000
    raw = {"device": {"/device:TPU:0": [
        ("%while.1 = while(...)", 10 * ms, 40 * ms),
        ("%fusion.1 = fusion(...)", 10 * ms, 25 * ms),
        ("%custom-call.2 = custom-call(...) select_step", 25 * ms, 40 * ms),
        ("%fusion.2 = fusion(...)", 70 * ms, 90 * ms),
        ("%fusion.3 = fusion(...)", 95 * ms, 130 * ms)]},
        "host": [("bench/window", 0, 100 * ms),
                 ("lynceus/harvest", 40 * ms, 60 * ms),
                 ("lynceus/seat", 60 * ms, 70 * ms)]}
    red = xplane.reduce(raw, {"select_step": "select_step"})
    assert red["window_s"] == pytest.approx(0.1)
    assert red["busy_s"] == pytest.approx(0.030 + 0.020 + 0.005)
    assert red["kernels"]["select_step"] == pytest.approx(0.015)
    assert red["idle_gaps"][0] == ["lynceus/harvest", pytest.approx(0.030)]
    assert red["idle_gaps"][1] == ["none", pytest.approx(0.010)]
    # Self time: the while holds its body, so it owns none of it.
    ops = dict(red["device_ops"])
    assert red["device_ops"][0] == ["fusion.2", pytest.approx(0.020)]
    assert ops["while.1"] == pytest.approx(0.0)
    assert ops["fusion.3"] == pytest.approx(0.005)


def test_trace_reduction_on_a_recorded_chip_trace():
    """The first 400 ms of a traced scout69.open-bursty window recorded on
    a TPU v5e, cut down to its TPU op line and the host's ``lynceus/`` and
    ``bench/`` spans, op names shortened to their HLO instruction (and a
    custom call's target)."""
    raw = xplane.load(FIXTURES / "scout69_chip.xplane.pb")
    assert list(raw["device"]) == ["/device:TPU:0"]
    red = xplane.reduce(raw, run.KERNELS)
    expect = data.load_json(FIXTURES / "scout69_chip.expect.json")
    assert red["window_s"] == pytest.approx(expect["window_s"])
    assert red["busy_s"] == pytest.approx(expect["busy_s"])
    assert 0 < red["busy_s"] <= red["window_s"]
    assert red["kernels"]["select_step"] == pytest.approx(
        expect["select_step_s"])
    assert 0 < red["kernels"]["select_step"] < red["busy_s"]
    assert [g[0] for g in red["idle_gaps"]] == expect["gap_names"]
    assert all(n.startswith(("lynceus/", "none")) for n in
               expect["gap_names"])


def test_bench_tables_are_the_repos_job_tables():
    from repro.jobs import tensorflow_jobs
    mine = data.make_jobs(run.ROOT, {"tables": [{"family": "tensorflow",
                                                 "seed": 0}]})
    for a, b in zip(mine, tensorflow_jobs(0), strict=True):
        assert a.name == b.name and a.t_max == b.t_max
        assert np.array_equal(a.runtime, b.runtime)
        assert np.array_equal(a.unit_price, b.unit_price)
        assert np.array_equal(a.space.points, b.space.points)
        assert np.array_equal(a.space.thresholds, b.space.thresholds)
        assert a.budget(3.0) == b.budget(3.0)


def _run_cli(cwd, env_extra):
    env = dict(os.environ, **env_extra)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "tf384.lone",
         "--seed", "1", "--seconds", "1", "--trace", "0"], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300)


def test_run_refuses_without_a_tpu():
    out = _run_cli(run.ROOT, {"JAX_PLATFORMS": "cpu"})
    assert out.returncode != 0 and out.stdout.strip() == ""
    assert "needs a TPU" in out.stderr


def test_run_refuses_without_the_program(tmp_path):
    spec = json.loads((run.ROOT / "BENCHMARK.json").read_text())
    for p in spec["paths"]:
        shutil.copytree(run.ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(run.ROOT / "BENCHMARK.json", tmp_path)
    out = _run_cli(tmp_path, {"JAX_PLATFORMS": "cpu"})
    assert out.returncode != 0 and out.stdout.strip() == ""
