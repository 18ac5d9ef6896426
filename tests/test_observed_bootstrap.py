"""The forest fits' bootstrap drawn only at the observed points.

``trees.observed_bootstrap_weights`` must give the bits of
``bootstrap_weights(key, B, M) * obs`` wherever it is given enough passes,
so every caller's fits, and every served probe, are what the full-width
draw gives.  The segment sizes the passes from its *active* lanes only
(``lookahead.draw_passes``), and counts the points it drew
(``boot_draw_points``).  Tests that need several passes on the small test
spaces shrink the pass size (``_DRAW_CHUNK``) for their own traces.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import RunRequest, Settings, run_queue, run_queue_batched
from repro.core import lookahead, trees
from repro.core.space import DiscreteSpace
from repro.jobs import synthetic_job
from repro.service import ServiceConfig, StreamingTuner
from tests.test_batched_harness import (_assert_outcomes_equal,
                                        _distinct_geometry_jobs)

N_TREES = 10


@pytest.fixture
def small_passes(monkeypatch):
    """Passes of 4 points, traced afresh (jit caches hold traces made at
    the real size), so the synthetic spaces' runs cross many passes."""
    monkeypatch.setattr(trees, "_DRAW_CHUNK", 4)
    jax.clear_caches()
    yield 4
    jax.clear_caches()


def _bits(a):
    return np.asarray(a, np.float32).view(np.uint32)


def _mask(m, chosen):
    obs = np.zeros(m, bool)
    obs[list(chosen)] = True
    return obs


def _case(name):
    """(obs mask, points the passes must cover, space width) per case."""
    m = 200
    ch = trees.draw_chunk(m)
    rng = np.random.default_rng(7)
    pick = lambda n: rng.choice(m, n, replace=False)
    if name == "none":
        return _mask(m, []), 0, m
    if name == "all":
        return np.ones(m, bool), m, m
    if name in ("below_pass", "at_pass", "past_pass", "two_passes"):
        n = {"below_pass": ch - 1, "at_pass": ch, "past_pass": ch + 1,
             "two_passes": 2 * ch + 1}[name]
        return _mask(m, pick(n)), n, m
    if name == "speculative":
        # A lane's points plus la = 2 speculated outside them, sized from
        # the lane's count plus la, as the segment sizes it.
        lane = pick(ch - 1)
        extra = [i for i in range(m) if i not in set(lane)][-2:]
        return _mask(m, list(lane) + extra), ch - 1 + 2, m
    if name == "padded":
        # A 200-point space padded to a 256-wide bucket: observed points
        # lie among the valid ones, the padding is never observed.
        bucket = 256
        return _mask(bucket, pick(70)), 70, bucket
    raise ValueError(name)


CASES = ("none", "all", "below_pass", "at_pass", "past_pass", "two_passes",
         "speculative", "padded")


@pytest.mark.parametrize("case", CASES)
def test_observed_draw_equals_full_width(case):
    obs, need, m = _case(case)
    key = jax.random.PRNGKey(11)
    n_chunks = trees.draw_chunks(jnp.int32(need), m)
    got = trees.observed_bootstrap_weights(key, N_TREES, jnp.asarray(obs),
                                           n_chunks)
    want = trees.bootstrap_weights(key, N_TREES, m) * obs[None, :]
    np.testing.assert_array_equal(_bits(got), _bits(want))

    # The fit, dead-tree guard included (every tree dead at n = 0), is
    # the same bits with the observed draw as with the full-width one.
    space = DiscreteSpace.from_grid({"a": list(range(m // 8)),
                                     "b": list(range(8))})
    left = trees.make_left_table(space.points, space.thresholds)
    y = jnp.asarray(np.random.default_rng(3).uniform(0.1, 2.0, m),
                    jnp.float32)
    args = (key, y, jnp.asarray(obs), jnp.asarray(space.points), left,
            jnp.asarray(space.thresholds))
    fit = jax.jit(trees.fit_forest, static_argnames=("n_trees", "depth"))
    full = fit(*args, n_trees=N_TREES, depth=3)
    part = fit(*args, n_trees=N_TREES, depth=3, n_chunks=n_chunks)
    for a, b in zip(jax.tree.leaves(full), jax.tree.leaves(part)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("m", [1, 24, 64, 65, 69, 200, 384, 512])
def test_passes_cover_the_space(m):
    ch = trees.draw_chunk(m)
    most = int(trees.draw_chunks(jnp.int32(m), m))
    assert most * ch >= m and most * ch - m < most
    assert ch <= trees._DRAW_CHUNK
    # More points than the space asks for no more passes than it has.
    assert int(trees.draw_chunks(jnp.int32(m + 5), m)) == most
    assert int(trees.draw_chunks(jnp.int32(0), m)) == 0


def test_idle_lane_does_not_widen_the_draw(small_passes):
    """A seat left idle keeps its last run's full mask; the step's passes
    follow the active lane alone, and the active lane's pick and
    diagnostics are the full-width draw's."""
    job = synthetic_job(2)
    s = Settings(policy="lynceus", la=2, k_gh=2, refit="exact")
    m = job.space.n_points
    rng = np.random.default_rng(5)
    mask = np.zeros((2, m), bool)
    mask[0, rng.choice(m, 5, replace=False)] = True
    mask[1] = True                                   # stale, idle seat
    active = jnp.asarray([True, False])
    n_chunks = lookahead.draw_passes(jnp.asarray(mask), active, s)
    assert int(n_chunks) == -(-(5 + s.la) // small_passes) < \
        int(trees.draw_chunks(jnp.int32(m), m))

    y = np.where(mask, job.host_view().cost[None, :], 0.0)
    points, left, thresholds, u = lookahead.space_arrays(job.space,
                                                         job.unit_price)
    keys = jax.vmap(jax.random.PRNGKey)(jnp.arange(2))
    args = (keys, jnp.asarray(y, jnp.float32), jnp.asarray(mask),
            jnp.asarray([3.0, 3.0], jnp.float32), points, left, thresholds,
            u, jnp.float32(job.t_max), s)
    full = lookahead.select_next_batched(*args)
    part = lookahead.select_next_batched(*args, n_chunks=n_chunks)
    for a, b in zip(jax.tree.leaves(full), jax.tree.leaves(part)):
        np.testing.assert_array_equal(np.asarray(a)[0], np.asarray(b)[0])


@pytest.mark.parametrize("shape", ["native", "bucketed", "fused"])
def test_segment_matches_oracle_across_passes(small_passes, shape):
    """The compacting segment draws at the active lanes' width; every
    served Outcome, ``spend_trajectory`` included, is the oracle's (whose
    draws are full width)."""
    if shape == "bucketed":
        jobs = _distinct_geometry_jobs()
    else:
        jobs = [synthetic_job(i, name=f"syn{i}") for i in range(2)]
    kw = {"fused_selector": "interpret"} if shape == "fused" else {}
    s = Settings(policy="lynceus", la=2 if shape == "native" else 1,
                 k_gh=2, refit="exact", timeout=True, **kw)
    reqs = [RunRequest(jobs[r % len(jobs)], seed=40 + r,
                       budget_b=4.0 if r % 2 == 0 else 1.5)
            for r in range(3 if shape == "fused" else 5)]
    seq = run_queue(reqs, s)
    assert max(o.nex for o in seq) > 2 * small_passes
    _assert_outcomes_equal(seq, run_queue_batched(reqs, s, lane_slots=2))


def test_boot_draw_points_counts_each_steps_passes(small_passes):
    """One seat, one request: every step's draw covers the run's observed
    count plus la, rounded up to whole passes, and the service's counter
    sums those widths over the steps, across segment boundaries."""
    job = synthetic_job(1)
    s = Settings(policy="lynceus", la=2, k_gh=2, refit="exact")
    req = RunRequest(job, seed=9, budget_b=4.0)
    svc = StreamingTuner([job], s, ServiceConfig(lane_slots=1,
                                                 queue_capacity=2,
                                                 step_quota=4))
    ticket = svc.submit(req)
    svc.drain()
    out = ticket.result()
    m = svc.metrics()
    assert m.steps == m.busy_slot_steps and m.segments > 1
    n_boot = len(req.resolved_bootstrap())
    space = job.space.n_points
    width = lambda n: small_passes * int(trees.draw_chunks(jnp.int32(n),
                                                           space))
    assert m.boot_draw_points == sum(width(n_boot + k + s.la)
                                     for k in range(m.steps))
    assert out.nex - n_boot in (m.steps, m.steps - 1)
    assert 0 < m.boot_draw_points < m.steps * space
