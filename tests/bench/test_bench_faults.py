"""Whole runs of the harness on the CPU at a tiny size: correct as they
are, and not correct with the timed path broken underneath them."""

import dataclasses

import jax
import numpy as np
import pytest

import _tiny
import run

SEED = 2 ** 31 + 12345


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return _tiny.make_root(tmp_path_factory.mktemp("tiny"))


def _run(root, cell_name, seconds=3.0):
    return run.run_cell(run.load_cell(root, cell_name), SEED, seconds, False)


def test_a_whole_run_is_correct(root):
    res = _run(root, "tiny.closed")
    assert res["correct"], res["check"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["metrics"]["decision_latency_ms"]["value"] > 0
    assert list(res)[-1] == "check"


@pytest.fixture
def fresh_programs():
    jax.clear_caches()
    yield
    jax.clear_caches()


def test_altered_probe_is_not_correct(root, monkeypatch, fresh_programs):
    """The selector's pick altered where it is produced: every lane probes
    the next point after the one the lookahead chose."""
    from repro.core import lookahead
    real = lookahead.select_next_batched

    def shifted(*args, **kw):
        idx, ok, diag = real(*args, **kw)
        return (idx + 1) % args[1].shape[-1], ok, diag
    monkeypatch.setattr(lookahead, "select_next_batched", shifted)
    assert not _run(root, "tiny.closed")["correct"]


def test_unchanged_state_is_not_correct(root, monkeypatch):
    """Every segment returns the lanes' state unchanged: no probe is ever
    chosen, so nothing can be checked."""
    from repro.service import engine
    real = engine._episode_segment

    def frozen(*args):
        args = list(args)
        args[5] = np.int32(0)                 # step quota 0: no step runs
        return real(*args)
    monkeypatch.setattr(engine, "_episode_segment", frozen)
    res = _run(root, "tiny.closed")
    assert not res["correct"] and res["check"]["steps_checked"]["value"] == 0


def test_dropped_results_are_not_correct(root, monkeypatch):
    """Half of the finished runs are left out of what a segment hands back
    in the window: their requests never settle and count as failed."""
    from repro.service import engine
    real = engine.SegmentEngine.run_segment
    warm_up = run.Service.warm_up

    def halved(self, *args, **kw):
        resolved, leftover, dropped, evicted, rep = real(self, *args, **kw)
        return ([rt for rt in resolved if rt[0].id % 2], leftover, dropped,
                evicted, rep)

    def warm_then_break(self, seed):
        warm_up(self, seed)
        monkeypatch.setattr(engine.SegmentEngine, "run_segment", halved)
    monkeypatch.setattr(run.Service, "warm_up", warm_then_break)
    monkeypatch.setattr(run, "SETTLE_LIMIT_S", 3.0)
    res = _run(root, "tiny.open", seconds=2.0)
    assert not res["correct"] and res["failed"] > 0


def test_wrong_timeout_cap_is_not_correct(root, monkeypatch, fresh_programs):
    """The program bills a probe cut at its timeout at a cap with the
    posterior slack 1.25 for the configuration's 1.0: the picks and the
    bills' structure stay sound, and only the cap's check sees it."""
    init = run.Service.__init__

    def planted(self, cell, trace):
        init(self, cell, trace)
        self.settings = dataclasses.replace(self.settings,
                                            timeout_kappa=1.25)
    monkeypatch.setattr(run.Service, "__init__", planted)
    res = _run(root, "tiny.closed")
    assert not res["correct"]
    share = res["check"]["cap_miss_share"]
    assert share["value"] > share["limit"], res["check"]


def test_a_per_layer_metric_with_nothing_to_read_is_an_error(root,
                                                             monkeypatch):
    """A traced run on the CPU has no select_step kernel in its trace, so
    the kernel's metrics read nothing: the run fails rather than leave
    them out of its line."""
    monkeypatch.setattr(run, "peaks_for", lambda root, kind: {
        "vpu_f32_ops_per_s": 1e12, "bytes_per_s": 1e11})
    cell = run.load_cell(root, "tiny.closed")
    with pytest.raises(run.MissingMetric, match="select_step_roofline"):
        run.run_cell(cell, SEED, 1.0, True)
