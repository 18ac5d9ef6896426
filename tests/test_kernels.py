"""Pallas kernels vs. pure-jnp oracles: shape/dtype sweeps in interpret mode."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import trees
from repro.core.acquisition import gauss_hermite
from repro.core.space import DiscreteSpace
from repro.kernels.decode_attention.kernel import decode_attention_call
from repro.kernels.decode_attention.ref import decode_attention_ref
from repro.kernels.flash_attention.kernel import flash_attention_call
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.gh_ei.kernel import gh_ei_call
from repro.kernels.gh_ei.ref import gh_ei_ref
from repro.kernels.ssm_scan.kernel import ssm_scan_call
from repro.kernels.ssm_scan.ref import ssm_scan_ref
from repro.kernels.tree_predict.kernel import tree_predict_call
from repro.kernels.tree_predict.ref import tree_predict_ref

RNG = np.random.default_rng(0)


def _tol(dtype):
    return dict(atol=5e-2, rtol=5e-2) if dtype == jnp.bfloat16 else \
        dict(atol=2e-5, rtol=2e-5)


# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("b,h,kh,s,t,d", [
    (2, 4, 2, 128, 128, 64),
    (1, 4, 1, 64, 128, 32),      # MQA, cross lengths
    (1, 6, 6, 128, 128, 16),     # MHA, odd head count
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal,window,softcap", [
    (True, None, None), (True, 32, None), (True, None, 30.0),
    (False, None, None),
])
def test_flash_attention_sweep(b, h, kh, s, t, d, dtype, causal, window,
                               softcap):
    q = jnp.asarray(RNG.normal(size=(b, h, s, d)), dtype)
    k = jnp.asarray(RNG.normal(size=(b, kh, t, d)), dtype)
    v = jnp.asarray(RNG.normal(size=(b, kh, t, d)), dtype)
    out = flash_attention_call(q, k, v, causal=causal, window=window,
                               softcap=softcap, bq=64, bk=64, interpret=True)
    ref = attention_ref(q, k, v, causal=causal, window=window,
                        softcap=softcap)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))


# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("b,h,kh,t,d,pos,window", [
    (2, 4, 2, 256, 64, 100, None),
    (1, 8, 1, 512, 32, 900, None),    # ring rollover (pos > t)
    (2, 4, 4, 256, 64, 300, 64),      # sliding window
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_sweep(b, h, kh, t, d, pos, window, dtype):
    q = jnp.asarray(RNG.normal(size=(b, h, d)), dtype)
    k = jnp.asarray(RNG.normal(size=(b, kh, t, d)), dtype)
    v = jnp.asarray(RNG.normal(size=(b, kh, t, d)), dtype)
    out = decode_attention_call(q, k, v, pos, window=window, bk=128,
                                interpret=True)
    ref = decode_attention_ref(q, k, v, pos, window=window)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))


# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("depth,n_trees,bm", [(2, 4, 16), (4, 10, 32),
                                              (5, 7, 64)])
def test_tree_predict_sweep(depth, n_trees, bm):
    space = DiscreteSpace.from_grid({"a": list(range(5)),
                                     "b": [0.0, 2.0, 7.0],
                                     "c": list(range(6))})
    y = jnp.asarray(RNG.normal(size=(space.n_points,)).astype(np.float32))
    mask = jnp.asarray(RNG.random(space.n_points) < 0.6)
    left = trees.make_left_table(space.points, space.thresholds)
    params, _ = trees.fit_forest(
        jax.random.PRNGKey(depth), y, mask, jnp.asarray(space.points), left,
        jnp.asarray(space.thresholds), n_trees=n_trees, depth=depth)
    x = jnp.asarray(space.points)
    mu_k, sig_k = tree_predict_call(x, params.feat, params.thr, params.leaf,
                                    bm=bm, interpret=True)
    mu_r, sig_r = tree_predict_ref(x, params.feat, params.thr, params.leaf)
    np.testing.assert_allclose(np.asarray(mu_k), np.asarray(mu_r), atol=1e-5)
    np.testing.assert_allclose(np.asarray(sig_k), np.asarray(sig_r),
                               atol=1e-5)


def test_tree_predict_consistent_with_core_forest():
    """Kernel must agree with the engine's own tabular predictions."""
    space = DiscreteSpace.from_grid({"a": list(range(8)),
                                     "b": list(range(8))})
    y = jnp.asarray(RNG.normal(size=(space.n_points,)).astype(np.float32))
    mask = jnp.asarray(RNG.random(space.n_points) < 0.5)
    left = trees.make_left_table(space.points, space.thresholds)
    params, assign = trees.fit_forest(
        jax.random.PRNGKey(0), y, mask, jnp.asarray(space.points), left,
        jnp.asarray(space.thresholds), n_trees=10, depth=4)
    preds = jnp.take_along_axis(params.leaf, assign, axis=1)
    mu_core, sig_core = trees.forest_mu_sigma(preds, 1e-6)
    mu_k, sig_k = tree_predict_call(jnp.asarray(space.points), params.feat,
                                    params.thr, params.leaf, bm=32,
                                    interpret=True)
    np.testing.assert_allclose(np.asarray(mu_k), np.asarray(mu_core),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(sig_k), np.asarray(sig_core),
                               atol=1e-5)


# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("m,k_gh,bm", [(97, 3, 32), (512, 5, 128), (33, 2, 64)])
def test_gh_ei_sweep(m, k_gh, bm):
    mu = jnp.asarray(RNG.uniform(1, 5, m), jnp.float32)
    sig = jnp.asarray(RNG.uniform(0.1, 2, m), jnp.float32)
    u = jnp.asarray(RNG.uniform(0.5, 3, m), jnp.float32)
    xi, _ = gauss_hermite(k_gh)
    a = gh_ei_call(mu, sig, u, 2.5, 1.2, 10.0, jnp.asarray(xi), bm=bm,
                   interpret=True)
    r = gh_ei_ref(mu, sig, u, 2.5, 1.2, 10.0, jnp.asarray(xi))
    np.testing.assert_allclose(np.asarray(a[0]), np.asarray(r[0]), atol=1e-5)
    np.testing.assert_array_equal(np.asarray(a[1]), np.asarray(r[1]))
    np.testing.assert_allclose(np.asarray(a[2]), np.asarray(r[2]), atol=1e-5)


def test_gh_ei_wrapper_censoring_pre_adjust():
    """The ops.gh_ei censoring path == censored_adjust then the plain call;
    an all-False mask reproduces the uncensored result bit for bit."""
    from repro.core import acquisition as acq
    from repro.kernels.gh_ei.ops import gh_ei

    m = 64
    mu = jnp.asarray(RNG.uniform(1, 5, m), jnp.float32)
    sig = jnp.asarray(RNG.uniform(0.1, 2, m), jnp.float32)
    u = jnp.asarray(RNG.uniform(0.5, 3, m), jnp.float32)
    y = jnp.asarray(RNG.uniform(2, 8, m), jnp.float32)
    cens = jnp.asarray(np.arange(m) % 7 == 0)
    xi, _ = gauss_hermite(3)
    xi = jnp.asarray(xi)

    plain = gh_ei(mu, sig, u, 2.5, 1.2, 10.0, xi, force="ref")
    none_c = gh_ei(mu, sig, u, 2.5, 1.2, 10.0, xi, force="ref",
                   cens=jnp.zeros(m, bool), y_cens=y)
    for a, b in zip(plain, none_c):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    censored = gh_ei(mu, sig, u, 2.5, 1.2, 10.0, xi, force="ref",
                     cens=cens, y_cens=y)
    mu_adj, sig_adj = acq.censored_adjust(mu, sig, y, cens, 0.5)
    expect = gh_ei(mu_adj, sig_adj, u, 2.5, 1.2, 10.0, xi, force="ref")
    for a, b in zip(censored, expect):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("b,l,h,n,p,chunk", [
    (2, 128, 3, 16, 8, 32), (1, 64, 2, 8, 8, 64), (1, 96, 1, 4, 16, 16),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssm_scan_sweep(b, l, h, n, p, chunk, dtype):
    k = jnp.asarray(RNG.normal(size=(b, l, h, n)) * 0.3, dtype)
    v = jnp.asarray(RNG.normal(size=(b, l, h, p)), dtype)
    q = jnp.asarray(RNG.normal(size=(b, l, h, n)) * 0.3, dtype)
    ld = -jnp.asarray(RNG.uniform(0.01, 0.5, (b, l, h)), jnp.float32)
    g = jnp.asarray(RNG.uniform(0, 1, (b, l, h)), jnp.float32)
    out = ssm_scan_call(k, v, q, ld, g, chunk=chunk, interpret=True)
    ref = ssm_scan_ref(k, v, q, ld, g, chunk=chunk)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=(5e-2 if dtype == jnp.bfloat16 else 1e-4),
                               rtol=5e-2)


# --------------------------------------------------------------------------- #
# Shared dispatch policy (kernels/dispatch.py)
# --------------------------------------------------------------------------- #
_DISPATCHED_OPS = ("flash_attention", "decode_attention", "ssm_scan",
                   "tree_predict", "gh_ei", "select_step")


def test_dispatch_decision_identical_across_ops(monkeypatch):
    """One auto policy for every op: Pallas on TPU *and* GPU, ref elsewhere
    — no per-op drift back to the old copy-pasted TPU-only force blocks."""
    from repro.kernels import dispatch
    for backend, want in [("tpu", "pallas"), ("gpu", "pallas"),
                          ("cpu", "ref"), ("METAL", "ref")]:
        monkeypatch.setattr(dispatch.jax, "default_backend",
                            lambda b=backend: b)
        monkeypatch.setattr(dispatch, "_degraded_logged", set())
        decisions = {op: dispatch.resolve_mode(None, op=op)
                     for op in _DISPATCHED_OPS}
        assert set(decisions.values()) == {want}, (backend, decisions)
    for mode in dispatch.MODES:            # force always wins
        assert dispatch.resolve_mode(mode, op="x") == mode
    with pytest.raises(ValueError, match="force"):
        dispatch.resolve_mode("cuda", op="x")


def test_dispatch_logs_degrade_once_per_op(monkeypatch, caplog):
    import logging
    from repro.kernels import dispatch
    monkeypatch.setattr(dispatch.jax, "default_backend", lambda: "cpu")
    monkeypatch.setattr(dispatch, "_degraded_logged", set())
    with caplog.at_level(logging.INFO, logger="repro.kernels"):
        for _ in range(3):
            dispatch.resolve_mode(None, op="gh_ei")
        dispatch.resolve_mode(None, op="tree_predict")
    degrades = [r for r in caplog.records if "degrading" in r.message]
    assert len(degrades) == 2              # once per op, not per call


# --------------------------------------------------------------------------- #
# Fused selector step vs the unfused program: bit parity incl. diagnostics
# --------------------------------------------------------------------------- #
def _selector_job(seed=0):
    from repro.jobs.tables import JobTable
    rng = np.random.default_rng(seed)
    space = DiscreteSpace.from_grid({"a": list(range(5)),
                                     "b": list(range(3))})
    runtime = rng.uniform(0.1, 1.0, space.n_points)
    price = rng.uniform(0.5, 2.0, space.n_points)
    return JobTable("j", space, runtime, price,
                    t_max=float(np.median(runtime)))


def _selector_obs(job, n=6, seed=0):
    rng = np.random.default_rng(seed)
    idx = rng.choice(job.space.n_points, n, replace=False)
    y = np.zeros(job.space.n_points, np.float32)
    mask = np.zeros(job.space.n_points, bool)
    y[idx] = job.cost[idx]
    mask[idx] = True
    cens = np.zeros(job.space.n_points, bool)
    cens[idx[0]] = True
    return y, mask, cens


def _run_selector(job, space, s, y, mask, cens, beta, key):
    """Run the bound selector on ``space`` (native or padded); returns
    (idx, valid, diagnostics as numpy) restricted to native lanes."""
    from repro.core import make_selector
    m = space.n_points
    native = job.space.n_points
    u = np.zeros(m, np.float32)
    u[:native] = job.unit_price
    yp = np.zeros(m, np.float32)
    yp[:native] = y
    mp = np.zeros(m, bool)
    mp[:native] = mask
    cp = None
    if cens is not None:
        cp = np.zeros(m, bool)
        cp[:native] = cens
    sel = make_selector(space, u, job.t_max, s)
    idx, valid, diag = sel(key, yp, mp, beta, cens=cp)
    trim = lambda a: (np.asarray(a)[:native] if np.ndim(a) >= 1
                      else np.asarray(a))
    return int(idx), bool(valid), {k: trim(v) for k, v in diag.items()}


@pytest.mark.parametrize("policy,la", [("bo", 0), ("la0", 0), ("lynceus", 1)])
@pytest.mark.parametrize("timeout", [False, True])
@pytest.mark.parametrize("padded", [False, True])
def test_fused_selector_bit_parity(policy, la, timeout, padded):
    """The fused kernel program must replay the unfused selector bit for bit
    — decision, valid flag, and every diagnostic (incl. the billed timeout
    cap) — on native and geometry-bucket-padded spaces alike."""
    from repro.core import Settings
    from repro.core.space import GeometryBucket
    job = _selector_job(3)
    y, mask, cens = _selector_obs(job, seed=3)
    cens = cens if timeout else None
    space = (job.space.pad_to(GeometryBucket(m=32, f=3, t=6))
             if padded else job.space)
    beta = job.budget(3.0)
    key = jax.random.PRNGKey(7)
    out = {}
    for mode in ("ref", "interpret"):
        s = Settings(policy=policy, la=la, k_gh=2, n_trees=3, depth=3,
                     timeout=timeout, fused_selector=mode)
        out[mode] = _run_selector(job, space, s, y, mask, cens, beta, key)
    (ia, va, da), (ib, vb, db) = out["ref"], out["interpret"]
    assert (ia, va) == (ib, vb)
    assert sorted(da) == sorted(db)
    if timeout:
        assert "timeout" in da
    for k in da:
        np.testing.assert_array_equal(da[k], db[k], err_msg=k)
        assert da[k].tobytes() == db[k].tobytes(), k   # bitwise, not just ==


@pytest.mark.parametrize("emit_full", [False, True])
def test_select_step_kernel_lane_and_block_invariant(emit_full):
    """Under ``vmap`` over two lanes (how the R-lane selector calls it),
    with a state count that is no multiple of the block (state padding,
    several grid blocks, per-lane t_max/floor), each lane's outputs equal
    a one-lane call that holds every state in one block, bit for bit."""
    from repro.core import lookahead
    from repro.core.lookahead import Settings, space_arrays
    from repro.kernels.select_step.kernel import select_step_call
    job = _selector_job(5)
    points, left, thr, u = space_arrays(job.space, job.unit_price)
    m = job.space.n_points
    s = Settings(n_trees=3, depth=3, k_gh=2)
    lanes, s_dim = 2, 1 if emit_full else 21
    rng = np.random.default_rng(5)
    y = jnp.asarray(rng.uniform(0.1, 2.0, (lanes, s_dim, m)), jnp.float32)
    obs = jnp.asarray(rng.random((lanes, s_dim, m)) < 0.4)
    cens = obs & jnp.asarray(rng.random((lanes, s_dim, m)) < 0.2)
    beta = jnp.asarray(rng.uniform(0.5, 3.0, (lanes, s_dim)), jnp.float32)
    bf = jnp.asarray(rng.uniform(0.2, 1.0, (lanes, s_dim)), jnp.float32)
    t_max = jnp.asarray([job.t_max, 0.7 * job.t_max], jnp.float32)
    floor = jnp.asarray([1e-3, 2e-2], jnp.float32)
    xi = jnp.asarray(gauss_hermite(2)[0])
    params = jax.vmap(lambda k, yy, oo: lookahead._fit_batch_params(
        k, yy, oo, points, left, thr, s))(
            jax.random.split(jax.random.PRNGKey(1), lanes), y, obs)
    args = (params.feat, params.thr, params.leaf, y, obs, cens, beta, bf,
            t_max, floor)

    def call(f, th, lf, yy, oo, c, b, bb, t, fl, bs):
        return select_step_call(f, th, lf, yy, oo, b, bb, points, u, t, fl,
                                xi, c, emit_full=emit_full, want_nodes=True,
                                bs=bs, interpret=True)

    got = jax.vmap(lambda *a: call(*a, bs=8))(*args)
    for lane in range(lanes):
        want = call(*(a[lane] for a in args), bs=32)
        assert len(got) == len(want)
        for g, w in zip(got, want):
            g = np.asarray(g[lane])
            assert g.shape == w.shape and g.dtype == w.dtype
            assert g.tobytes() == np.asarray(w).tobytes()
