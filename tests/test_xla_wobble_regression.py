"""Regression pins for the XLA per-geometry fusion-wobble defenses.

ROADMAP context: XLA recompiles the selector for every batch geometry
(R = 1 oracle, R = chunk harness, in/out of the episode while_loop), and its
fusion choices perturb transcendental- and matmul-derived floats in the last
ulps.  PR 1 hardened every *decision* against that: the budget filter
compares in z-space (pure IEEE arithmetic, no device erf), split gains are
computed cancellation-free with a noise floor that snaps rounding noise to
exact zeros, and every argmax runs on `quantize_scores`-rounded values.
These tests freeze a small job where the un-quantized scores are known to
tie exactly — every config identical — so any regression in the defenses
shows up as a decision flip across compilation contexts or jit cache
clears, not as a one-ulp curiosity.
"""

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (GeometryBucket, Settings, acquisition as acq,
                        lookahead, make_batch_selector, make_selector,
                        optimize, trees)
from repro.core.space import DiscreteSpace
from repro.jobs.tables import JobTable


def _tied_job(m_a=5, m_b=4):
    """Every config has the same runtime and price: every model prediction,
    EI score and split gain ties exactly — the adversarial case for
    geometry-dependent tie-breaking."""
    space = DiscreteSpace.from_grid({"a": list(range(m_a)),
                                     "b": list(range(m_b))})
    runtime = np.full(space.n_points, 0.7)
    price = np.full(space.n_points, 1.3)
    return JobTable("tied", space, runtime, price, t_max=0.7)


def _obs(job, n=5, seed=0):
    rng = np.random.default_rng(seed)
    idx = rng.choice(job.space.n_points, n, replace=False)
    y = np.zeros(job.space.n_points, np.float32)
    mask = np.zeros(job.space.n_points, bool)
    y[idx] = job.cost.astype(np.float32)[idx]
    mask[idx] = True
    return y, mask


def test_tied_scores_decide_identically_across_geometries_and_cache_clears():
    job = _tied_job()
    s = Settings(policy="lynceus", la=1, k_gh=2, refit="exact")
    y, mask = _obs(job)
    beta = job.budget(3.0)
    key = jax.random.PRNGKey(0)

    def picks():
        sel1 = make_selector(job.space, job.unit_price, job.t_max, s)
        selb = make_batch_selector(job.space, job.unit_price, job.t_max, s)
        i1, v1, _ = sel1(key, y, mask, beta)
        # R = 3 identical lanes: every lane must pick what the oracle picks
        ib, vb, _ = selb(jnp.broadcast_to(jnp.asarray(key), (3, 2)),
                         np.broadcast_to(y, (3,) + y.shape),
                         np.broadcast_to(mask, (3,) + mask.shape),
                         np.full(3, beta, np.float32))
        assert bool(v1) and bool(np.asarray(vb).all())
        return [int(i1)] + np.asarray(ib).tolist()

    first = picks()
    assert len(set(first)) == 1, "R=1 and R=3 geometries disagree on a tie"
    jax.clear_caches()                      # force full recompilation
    assert picks() == first, "tie decision changed across jit cache clears"


def test_optimize_trace_stable_across_cache_clears():
    job = _tied_job()
    s = Settings(policy="la0", la=0, k_gh=2)
    a = optimize(job, s, budget_b=2.0, seed=3)
    jax.clear_caches()
    b = optimize(job, s, budget_b=2.0, seed=3)
    assert a.explored == b.explored
    assert a.spent == b.spent


def test_budget_filter_is_zspace_not_cdf():
    """The Gamma filter must threshold pure IEEE z-scores against a
    host-side quantile — never a device-evaluated cdf transcendental, whose
    vectorization differs per geometry.  Pinned structurally through the
    determinism auditor: no erf-family primitive anywhere in the traced
    program (including sub-jaxprs — a ``"erf" not in str(jaxpr)`` pin would
    miss one buried in a jit call and false-positive on variable names)."""
    from repro.analysis import ForbiddenPrimitivesRule, audit

    findings = audit(
        lambda m, s, b: acq.budget_ok(m, s, b, 0.99),
        (jnp.ones(4), jnp.ones(4), jnp.float32(3.0)),
        [ForbiddenPrimitivesRule(("erf", "erfc", "erf_inv"),
                                 reason="budget filter must stay z-space")])
    assert findings == [], [str(f) for f in findings]
    # and the boundary is inclusive: z exactly at the quantile is in Gamma
    q = np.float32(acq.normal_quantile(0.99))
    mu = jnp.asarray([0.0], jnp.float32)
    sigma = jnp.asarray([1.0], jnp.float32)
    assert bool(acq.budget_ok(mu, sigma, q, 0.99)[0])


def test_split_gain_noise_floor_makes_constant_node_fits_reproducible():
    """Constant observed values: every candidate split's gain is pure
    rounding noise (ml - mr is a catastrophic cancellation), and the noise
    floor snaps those gains to *exact zeros* — so the argmax faces exact
    ties that break by lowest index identically in every compilation
    context, instead of ranking noise whose ordering shifts with fusion.
    Pinned by refitting across a jit cache clear and in a vmapped (batched)
    geometry: structure and leaves must agree bit for bit, and every leaf
    must predict the shared constant."""
    job = _tied_job()
    y, mask = _obs(job, n=6, seed=1)
    points, left, thr = (jnp.asarray(job.space.points),
                         trees.make_left_table(job.space.points,
                                               job.space.thresholds),
                         jnp.asarray(job.space.thresholds))

    def fit():
        params, _ = trees.fit_forest(jax.random.PRNGKey(0), jnp.asarray(y),
                                     jnp.asarray(mask), points, left, thr,
                                     n_trees=4, depth=3)
        return jax.tree.map(np.asarray, params)

    first = fit()
    obs_val = y[mask][0]
    np.testing.assert_allclose(first.leaf, obs_val, rtol=1e-6)
    jax.clear_caches()
    again = fit()
    for a, b in zip(first, again):
        np.testing.assert_array_equal(a, b)
    # batched geometry: two identical lanes through one vmapped program
    vfit = jax.jit(jax.vmap(
        lambda yy, mm: trees.fit_forest(jax.random.PRNGKey(0), yy, mm,
                                        points, left, thr, n_trees=4,
                                        depth=3)[0]))
    pair = vfit(jnp.broadcast_to(jnp.asarray(y), (2,) + y.shape),
                jnp.broadcast_to(jnp.asarray(mask), (2,) + mask.shape))
    for lane in range(2):
        for a, b in zip(first, jax.tree.map(lambda t: np.asarray(t[lane]),
                                            pair)):
            np.testing.assert_array_equal(a, b)


def test_timeout_cap_deterministic_across_geometries():
    """τ is billed, not just compared, so it must be bit-identical between
    the R = 1 and R = k selector programs on identical lane state — the
    coarse sigma quantization inside timeout_cap is what guarantees it."""
    job = _tied_job()
    s = Settings(policy="la0", la=0, k_gh=2, timeout=True)
    y, mask = _obs(job)
    cens = np.zeros_like(mask)
    beta = job.budget(3.0)
    key = jax.random.PRNGKey(7)
    sel1 = make_selector(job.space, job.unit_price, job.t_max, s)
    selb = make_batch_selector(job.space, job.unit_price, job.t_max, s)
    _, _, d1 = sel1(key, y, mask, beta, cens)
    _, _, db = selb(jnp.broadcast_to(jnp.asarray(key), (4, 2)),
                    np.broadcast_to(y, (4,) + y.shape),
                    np.broadcast_to(mask, (4,) + mask.shape),
                    np.full(4, beta, np.float32),
                    np.broadcast_to(cens, (4,) + cens.shape))
    t1 = float(np.asarray(d1["timeout"]))
    tb = np.asarray(db["timeout"])
    assert (tb == np.float32(t1)).all()
    jax.clear_caches()
    _, _, d2 = sel1(key, y, mask, beta, cens)
    assert float(np.asarray(d2["timeout"])) == t1


# --------------------------------------------------------------------------- #
# Geometry buckets: fixed-width padded selector programs
# --------------------------------------------------------------------------- #
def test_padded_selector_jaxpr_identical_across_bucket_members():
    """The one-compile-per-bucket claim, pinned structurally: two member
    spaces of one bucket — different native [M, F, T] — trace the *same*
    padded selector program (space tensors are traced arguments, so equal
    bucket shapes mean equal programs; any pad-width leak into the trace
    would show up here as a signature diff and as a recompile in
    production).  Compared via the auditor's canonical program signature,
    not ``str(jaxpr)`` — the pretty-printer's variable names and param
    ordering are not stable across jax versions."""
    spaces = [DiscreteSpace.from_grid({"a": list(range(5)),
                                       "b": list(range(3))}),
              DiscreteSpace.from_grid({"a": list(range(4)),
                                       "b": list(range(6)),
                                       "c": [0.0, 1.0]})]
    assert spaces[0].geometry != spaces[1].geometry
    bucket = GeometryBucket.for_spaces(spaces)
    s = Settings(policy="lynceus", la=1, k_gh=2, refit="frozen")

    from repro.analysis import signature

    def padded_signature(space):
        ps = space.pad_to(bucket)
        pts, left, thr, u = lookahead.space_arrays(ps, np.ones(space.n_points))
        valid = jnp.asarray(ps.valid)
        key = jnp.zeros((1, 2), jnp.uint32)
        y = jnp.zeros((1, bucket.m), jnp.float32)
        mask = jnp.zeros((1, bucket.m), bool)
        beta = jnp.ones((1,), jnp.float32)
        return signature(
            lambda *a: lookahead.select_next_batched(*a, s, None, valid),
            key, y, mask, beta, pts, left, thr, u, jnp.float32(1.0))

    assert padded_signature(spaces[0]) == padded_signature(spaces[1])


def test_tied_scores_native_vs_padded_bucket_across_cache_clears():
    """The PR-1 adversarial tie job, run native and padded into a larger
    bucket: every decision (pick + Γ flag + billed τ) must agree bit for
    bit, before and after a full jit-cache clear — the padded program is
    a new compilation geometry, which is exactly what the quantized
    decision stack must be invariant to."""
    job = _tied_job()
    m = job.space.n_points
    bucket = GeometryBucket(m=32, f=4, t=8)
    s = Settings(policy="lynceus", la=1, k_gh=2, refit="exact", timeout=True)
    y, mask = _obs(job)
    cens = np.zeros_like(mask)
    beta = job.budget(3.0)
    key = jax.random.PRNGKey(0)
    yp = np.zeros(bucket.m, np.float32)
    mp = np.zeros(bucket.m, bool)
    yp[:m], mp[:m] = y, mask
    cp = np.zeros(bucket.m, bool)

    def decisions():
        nat = make_selector(job.space, job.unit_price, job.t_max, s)
        pad = make_selector(job.space.pad_to(bucket), job.unit_price,
                            job.t_max, s)
        i0, v0, d0 = nat(key, y, mask, beta, cens)
        i1, v1, d1 = pad(key, yp, mp, beta, cp)
        assert int(i0) == int(i1), "padded pick differs from native"
        assert bool(v0) == bool(v1)
        t0 = float(np.asarray(d0["timeout"]))
        assert t0 == float(np.asarray(d1["timeout"])), "billed τ diverged"
        return int(i0), bool(v0), t0

    first = decisions()
    jax.clear_caches()                      # force full recompilation
    assert decisions() == first, "decision changed across jit cache clears"
