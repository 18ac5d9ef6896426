"""Budget-aware, long-sighted configuration selection (paper §4, Algs. 1–2).

This module implements ``NextConfig`` / ``ExplorePaths`` as one jit-compiled,
fully batched JAX program.  Where the original Java prototype runs one thread
per exploration-path root, we flatten the whole search frontier into batch
dimensions:

* depth 0: one ensemble fit scores **all M roots** at once (in-breadth rule);
* depth 1: ``M x K`` speculative states (K = Gauss-Hermite nodes) are fit by a
  single ``vmap``-ed call;
* depth 2: ``M x K x K`` states, again one call.

Every state is the same fixed-shape object (the full space with an
observation mask), so the program compiles once per space and is reused for
every optimization step of every simulated run.

Two refit modes:

* ``exact``  — every speculative state re-fits the bagged forest from scratch
  (faithful to the paper, which retrains Weka models per state);
* ``frozen`` — beyond-paper fast path: tree *structures* are frozen to the
  root fit and only the leaf containing the speculated point is updated (an
  exact incremental mean update given the structure).  ~2 orders of magnitude
  cheaper; accuracy/latency trade-off is measured in benchmarks/table3.

Batched entry points
--------------------
``select_next_batched`` selects for R independent runs at once and is the
kernel every harness shares: the sequential oracle is its R = 1 special
case (see ``make_selector``), the lockstep and lane-compacting episodes in
``core/optimizer.py`` its R = chunk case.  Selection is *slot-indexed*:
``u``/``t_max`` may be per-slot ([R, M] / [R]) so a mixed-job work queue
can seat runs of different jobs — different unit prices and SLOs — in the
same compiled program.  docs/KNOBS.md documents every ``Settings`` field;
docs/ARCHITECTURE.md maps the whole selection pipeline onto the paper.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import acquisition as acq
from repro.core import trees
from repro.kernels.dispatch import resolve_mode as _resolve_kernel_mode
from repro.kernels.select_step.kernel import select_step_call
from repro.obs.scopes import scope

__all__ = ["Settings", "select_next", "select_next_batched", "draw_passes",
           "make_selector", "make_batch_selector", "space_arrays",
           "space_valid", "slot_price_rows", "selector_cache_size"]

_EPS = 1e-9


@dataclasses.dataclass(frozen=True)
class Settings:
    """Static knobs of the selector (hashable -> usable as jit static arg)."""

    policy: str = "lynceus"      # lynceus | la0 | bo | rnd (rnd handled by driver)
    la: int = 2                  # lookahead window (paper default 2)
    k_gh: int = 3                # Gauss-Hermite nodes per branch
    gamma: float = 0.9           # future-reward discount (paper §4.3)
    n_trees: int = 10            # bagging ensemble size (paper §5.2)
    depth: int = 4               # tree depth
    conf: float = 0.99           # budget-filter confidence (Alg. 1 line 23)
    refit: str = "exact"         # exact | frozen
    sigma_floor_rel: float = 0.01
    # Timeout-censored exploration (paper §3, mechanism i).  Off by default:
    # with timeout=False the selector traces the exact same program as before
    # the mechanism existed (no censor mask is threaded anywhere).
    timeout: bool = False        # abort deemed-suboptimal runs, learn the bound
    timeout_kappa: float = 1.0   # posterior slack in the predictive cap
    # Constraint cap: τ <= mult·t_max.  3x keeps enough full observations on
    # small spaces for the model to stay sharp (1x censors half the
    # bootstrap on median-t_max tables and costs more CNO than it saves);
    # the predictive cap still aborts incumbent-dominated runs much earlier.
    timeout_tmax_mult: float = 3.0
    cens_sigma_rel: float = 0.5  # posterior sigma floor at censored configs
    # Pallas-fused selector step (kernels/select_step): "auto" picks the
    # fused kernel on TPU/GPU and the traced-identical unfused program
    # elsewhere; "pallas"/"interpret" force the kernel (interpret is the CI
    # mode — runs the kernel body as plain XLA on any backend); "ref" forces
    # the unfused program.  Fusion requires refit="exact": under "auto" a
    # frozen-refit selector silently stays unfused (the frozen incremental
    # update has no kernel), while an explicit "pallas"/"interpret" raises.
    fused_selector: str = "auto"
    # State-axis block size of the fused kernel's grid: each block keeps its
    # whole [fused_block_states, M] candidate sweep in VMEM.
    fused_block_states: int = 32


def _fused_mode(s: Settings) -> str | None:
    """Resolve ``s.fused_selector`` to "pallas" | "interpret" | None (unfused).

    Trace-time only (Settings is static), so the unfused program is traced
    untouched whenever this returns None — including the "auto" default off
    accelerators, where ``kernels.dispatch.resolve_mode`` logs the degrade
    once.
    """
    if s.fused_selector == "ref":
        return None
    if s.fused_selector == "auto":
        if s.refit == "frozen":
            return None
        mode = _resolve_kernel_mode(None, op="select_step")
        return None if mode == "ref" else mode
    if s.fused_selector not in ("pallas", "interpret"):
        raise ValueError(
            f"fused_selector={s.fused_selector!r}: expected 'auto', "
            "'pallas', 'interpret' or 'ref'")
    if s.refit == "frozen":
        raise ValueError(
            "fused_selector='pallas'/'interpret' requires refit='exact': "
            "the frozen incremental leaf update has no fused kernel")
    return s.fused_selector


# --------------------------------------------------------------------------- #
# Model fitting helpers
# --------------------------------------------------------------------------- #
def _sigma_floor(y, obs_mask, rel):
    obs = obs_mask.astype(jnp.float32)
    n = jnp.maximum(obs.sum(), 1.0)
    mean = (y * obs).sum() / n
    var = (((y - mean) ** 2) * obs).sum() / n
    return 1e-6 + rel * jnp.sqrt(jnp.maximum(var, 0.0))


def _fit_root(key, y, obs_mask, cens, points, left, thresholds, floor,
              s: Settings, n_chunks=None):
    """Root ensemble fit.  Censored points (``cens`` not None) enter the fit
    as regular observations at their billed lower bound — they shape split
    structure — and the resulting posterior is corrected at those configs
    (mean clamped to the bound, sigma inflated; see acq.censored_adjust)."""
    params, assign = trees.fit_forest(
        key, y, obs_mask, points, left, thresholds,
        n_trees=s.n_trees, depth=s.depth, n_chunks=n_chunks)
    preds = jnp.take_along_axis(params.leaf, assign, axis=1)   # [B, M]
    mu, sigma = trees.forest_mu_sigma(preds, floor)
    if cens is not None:
        mu, sigma = acq.censored_adjust(mu, sigma, y, cens, s.cens_sigma_rel)
    return params, assign, preds, mu, sigma


# Speculative states fit per block of the state axis.  A level holds up to
# M·k_gh^la states (3456 per lane for a 384-point space at la=2), each
# fitting n_trees trees over all M points; fitting them all in one vmap
# would hold every state's split statistics in HBM at once.  Blocking
# bounds that to this many states per lane, whatever the space or depth.
_FIT_BLOCK_STATES = 128


def _map_states(fn, keys, y_b, m_b):
    """``vmap(fn)`` over the state axis, evaluated in blocks of
    ``_FIT_BLOCK_STATES`` (``lax.map``'s ``batch_size``: a scan over full
    blocks plus one vmap over the remainder; at most one block is plain
    vmap).  Every state runs the same per-state program either way."""
    return jax.lax.map(lambda a: fn(*a), (keys, y_b, m_b),
                       batch_size=_FIT_BLOCK_STATES)


def _fit_batch_exact(key, y_b, m_b, cens_b, points, left, thresholds, floor,
                     s: Settings, n_chunks=None):
    """y_b, m_b[, cens_b]: [S, M] -> mu, sigma: [S, M].

    Per-state keys derive from ``fold_in(key, state_index)`` rather than
    ``split(key, S)``: a split's threefry counter pairing depends on the
    *total* state count S = M·k^depth, which grows when the space is padded
    to a geometry bucket, while the flattened state index of every native
    root is padding-invariant (``root·k + node``).  fold_in keeps state i's
    key a pure function of (key, i), so a padded lookahead replays the
    native speculative fits bit-for-bit.
    """
    keys = jax.vmap(jax.random.fold_in, (None, 0))(
        key, jnp.arange(y_b.shape[0]))

    def one(k, y, m):
        p, a = trees.fit_forest(k, y, m, points, left, thresholds,
                                n_trees=s.n_trees, depth=s.depth,
                                n_chunks=n_chunks)
        preds = jnp.take_along_axis(p.leaf, a, axis=1)
        return trees.forest_mu_sigma(preds, floor)

    mu, sigma = _map_states(one, keys, y_b, m_b)
    if cens_b is not None:
        mu, sigma = acq.censored_adjust(mu, sigma, y_b, cens_b,
                                        s.cens_sigma_rel)
    return mu, sigma


def _fit_batch_params(key, y_b, m_b, points, left, thresholds, s: Settings,
                      n_chunks=None):
    """Per-state forest *parameters* [S, B, D, W] for the fused kernel.

    The same ``fold_in(key, state_index)`` key schedule as
    :func:`_fit_batch_exact` — the fused kernel re-derives each state's
    leaf assignment by traversal instead of consuming the fit-side gather,
    so only the parameters cross the kernel boundary.
    """
    keys = jax.vmap(jax.random.fold_in, (None, 0))(
        key, jnp.arange(y_b.shape[0]))

    def one(k, y, m):
        p, _ = trees.fit_forest(k, y, m, points, left, thresholds,
                                n_trees=s.n_trees, depth=s.depth,
                                n_chunks=n_chunks)
        return p

    return _map_states(one, keys, y_b, m_b)


def _fit_batch_frozen(root_assign, root_preds, boot_w, sel_b, c_b, floor):
    """Frozen-structure incremental refit.

    root_assign: [B, M] leaf assignment of every space point per tree.
    root_preds:  [B, M] root per-tree predictions.
    boot_w:      [B, M] bootstrap weights used by the root fit.
    sel_b: [S] speculated config per state; c_b: [S] speculated cost.

    For tree b, adding (x_sel, c) with unit weight only changes the leaf that
    contains x_sel: new_value = (sw*old + c) / (sw + 1), where sw is the leaf's
    total bootstrap weight.  Points in other leaves keep their prediction.
    """
    # Leaf weight totals per (tree, leaf-of-sel): gather points sharing a leaf.
    same_leaf = root_assign[:, :, None] == root_assign[:, sel_b][:, None, :]
    # [B, M, S] bool: does point m share sel_b[s]'s leaf in tree b?
    sw = jnp.einsum("bm,bms->bs", boot_w, same_leaf.astype(jnp.float32))
    old = jnp.take_along_axis(root_preds, jnp.broadcast_to(sel_b[None, :],
                              (root_preds.shape[0], sel_b.shape[0])), axis=1)
    new_leaf = (sw * old + c_b[None, :]) / (sw + 1.0)          # [B, S]
    delta = new_leaf - old                                      # [B, S]
    preds = root_preds[:, None, :] + delta[:, :, None] * same_leaf.transpose(0, 2, 1)
    mu = preds.mean(axis=0)                                     # [S, M]
    sigma = jnp.maximum(preds.std(axis=0), floor)
    return mu, sigma


# --------------------------------------------------------------------------- #
# y* (incumbent) per batched state
# --------------------------------------------------------------------------- #
def _ystar(best_feas, y_b, m_b, sigma, valid=None):
    """Per-state y* — :func:`acq.incumbent_fallback`, with ``best_feas``
    tracked incrementally by the speculation branches instead of being
    recomputed from a feasibility mask.  ``valid`` masks padding lanes out
    of the untested-sigma fallback (observed points are native by
    construction, so only that term needs the mask)."""
    return acq.incumbent_fallback(best_feas, y_b, m_b, sigma, valid)


# --------------------------------------------------------------------------- #
# The selector
# --------------------------------------------------------------------------- #
def _recurse(key, y_b, m_b, beta_b, bf_b, depth_left, *, points, left,
             thresholds, u, t_max, floor, s: Settings, frozen_ctx,
             cens_b=None, valid=None, n_chunks=None):
    """Score each state's own argmax-EI_c pick; branch if depth_left > 0.

    Returns (reward [S], cost [S]) — already zeroed for states whose Gamma is
    empty (Alg. 2 "continue").  ``cens_b`` ([S, M] or None) marks the
    parent's censored observations; speculation only ever adds fully-observed
    points, so the mask is constant down the path.  ``valid`` ([M] or None)
    is the run's point-validity mask (padded selector programs): padding
    lanes are never candidates, at any speculation depth.  ``n_chunks``
    is the bootstrap draw's pass count (``trees.fit_forest``).
    """
    k_fit, k_next = jax.random.split(key)
    fused = _fused_mode(s)
    xi, w = acq.gauss_hermite(s.k_gh)
    if fused is not None:
        # Fused hot path (kernels/select_step): the per-state forest *fit*
        # stays outside — identical key schedule to _fit_batch_exact — and
        # the [S, M] sweep (ensemble descent -> censored adjust -> EI_c ->
        # Gamma -> quantized argmax) runs in one Pallas program.
        params = _fit_batch_params(k_fit, y_b, m_b, points, left,
                                   thresholds, s, n_chunks)
        with scope("select_step"):
            out = select_step_call(
                params.feat, params.thr, params.leaf, y_b, m_b, beta_b,
                bf_b, points, u, t_max, floor, jnp.asarray(xi), cens=cens_b,
                valid=valid, conf=s.conf, cens_rel=s.cens_sigma_rel,
                score_mode="eic", use_budget=True, emit_full=False,
                want_nodes=depth_left > 0, bs=s.fused_block_states,
                interpret=(fused == "interpret"))
        sel, has_cand, eic_sel, mu_sel, sig_sel = out[:5]
        r0 = jnp.where(has_cand, eic_sel, 0.0)
        c0 = jnp.where(has_cand, mu_sel, 0.0)
        if depth_left == 0:
            return r0, c0
        c_nodes = out[5]                                    # [S, K]
    else:
        if s.refit == "frozen" and frozen_ctx is not None:
            mu, sigma = _fit_batch_frozen(*frozen_ctx, floor)
            if cens_b is not None:
                mu, sigma = acq.censored_adjust(mu, sigma, y_b, cens_b,
                                                s.cens_sigma_rel)
        else:
            mu, sigma = _fit_batch_exact(k_fit, y_b, m_b, cens_b, points,
                                         left, thresholds, floor, s,
                                         n_chunks)
        ystar = _ystar(bf_b, y_b, m_b, sigma, valid)
        eic = acq.ei_constrained(mu, sigma, ystar[:, None], u[None, :],
                                 t_max)
        untested = ~m_b.astype(bool)
        if valid is not None:
            untested = untested & valid[None, :]
        cand = untested & acq.budget_ok(mu, sigma, beta_b[:, None], s.conf)
        score = acq.quantize_scores(jnp.where(cand, eic, -jnp.inf))
        sel = jnp.argmax(score, axis=1)                         # [S]
        has_cand = jnp.any(cand, axis=1)
        take = lambda a: jnp.take_along_axis(a, sel[:, None], axis=1)[:, 0]
        r0 = jnp.where(has_cand, take(eic), 0.0)
        c0 = jnp.where(has_cand, take(mu), 0.0)
        if depth_left == 0:
            return r0, c0
        c_nodes = acq.gh_cost_nodes(take(mu), take(sigma),
                                    jnp.asarray(xi))            # [S, K]

    # Branch: Gauss-Hermite speculation on the selected config's cost.
    s_dim, m_dim = y_b.shape
    sel_oh = jax.nn.one_hot(sel, m_dim, dtype=bool)             # [S, M]
    y_child = jnp.where(sel_oh[:, None, :], c_nodes[:, :, None],
                        y_b[:, None, :])                        # [S, K, M]
    m_child = jnp.broadcast_to((m_b.astype(bool) | sel_oh)[:, None, :],
                               (s_dim, s.k_gh, m_dim))
    beta_child = beta_b[:, None] - c_nodes
    feas = c_nodes <= (t_max * u[sel])[:, None]
    bf_child = jnp.minimum(bf_b[:, None],
                           jnp.where(feas, c_nodes, jnp.inf))
    flat = lambda a: a.reshape((s_dim * s.k_gh,) + a.shape[2:])
    child_frozen = None
    if s.refit == "frozen" and frozen_ctx is not None:
        ra, rp, bw, _, _ = frozen_ctx
        child_frozen = (ra, rp, bw,
                        flat(jnp.broadcast_to(sel[:, None], (s_dim, s.k_gh))),
                        flat(c_nodes))
    cens_child = None
    if cens_b is not None:
        cens_child = flat(jnp.broadcast_to(cens_b[:, None, :],
                                           (s_dim, s.k_gh, m_dim)))
    r_ch, c_ch = _recurse(
        k_next, flat(y_child), flat(m_child), flat(beta_child),
        flat(bf_child), depth_left - 1, points=points, left=left,
        thresholds=thresholds, u=u, t_max=t_max, floor=floor, s=s,
        frozen_ctx=child_frozen, cens_b=cens_child, valid=valid,
        n_chunks=n_chunks)
    r_ch = r_ch.reshape(s_dim, s.k_gh)
    c_ch = c_ch.reshape(s_dim, s.k_gh)
    # G-H expectation via the pinned fenced dot (acq.gh_expect): a raw `@ w`
    # would let the backend pick the accumulation/FMA shape per compilation
    # context, splitting the fused and unfused selector programs bitwise.
    reward = jnp.where(
        has_cand,
        r0 + acq.no_contract(s.gamma * acq.gh_expect(r_ch, w)), 0.0)
    cost = jnp.where(has_cand, c0 + acq.gh_expect(c_ch, w), 0.0)
    return reward, cost


def _select_next_fused(key, y, obs_mask, beta, points, left, thresholds, u,
                       t_max, s: Settings, cens, valid, mode: str,
                       n_chunks=None):
    """Fused-root twin of :func:`_select_next_impl` (same contract).

    The root forest fit keeps the unfused key schedule (``k_root`` feeds
    ``trees.fit_forest`` directly); the whole [M] sweep — traversal,
    censored adjustment, y*, EI_c, Gamma, policy score, quantized argmax —
    runs as one ``kernels/select_step`` program with ``emit_full=True`` so
    the diagnostics are the kernel's own arrays.  Lookahead recursion and
    the final reward/cost ratio argmax stay outside (they consume the whole
    recursion tree, not one state's sweep).
    """
    m_dim = y.shape[0]
    floor = _sigma_floor(y, obs_mask, s.sigma_floor_rel)
    k_root, k_path = jax.random.split(key)
    params, _ = trees.fit_forest(k_root, y, obs_mask, points, left,
                                 thresholds, n_trees=s.n_trees,
                                 depth=s.depth, n_chunks=n_chunks)

    obs = obs_mask.astype(bool)
    feas_obs = obs & (y <= t_max * u)
    if cens is not None:
        feas_obs = feas_obs & ~cens.astype(bool)
    best_feas = jnp.min(jnp.where(feas_obs, y, jnp.inf))

    if s.policy == "bo":
        score_mode, use_budget = "eic", False
    elif s.policy == "la0" or (s.policy == "lynceus" and s.la == 0):
        score_mode, use_budget = "ratio", True
    elif s.policy == "lynceus":
        score_mode, use_budget = "eic", True
    else:
        raise ValueError(f"unknown policy {s.policy!r}")
    lookahead = s.policy == "lynceus" and s.la > 0
    xi, w = acq.gauss_hermite(s.k_gh)

    with scope("select_step"):
        out = select_step_call(
            params.feat[None], params.thr[None], params.leaf[None], y[None],
            obs[None], jnp.asarray(beta, jnp.float32)[None],
            best_feas[None], points, u, t_max, floor, jnp.asarray(xi),
            cens=None if cens is None else cens.astype(bool)[None],
            valid=valid, conf=s.conf, cens_rel=s.cens_sigma_rel,
            score_mode=score_mode, use_budget=use_budget, emit_full=True,
            want_nodes=lookahead, bs=s.fused_block_states,
            interpret=(mode == "interpret"))
    mu0, sig0, eic0 = out[0][0], out[1][0], out[2][0]
    ystar0, cand0, sel0, has0 = out[3][0], out[4][0], out[5][0], out[6][0]
    diagnostics = {"mu": acq.quantize_scores(mu0),
                   "sigma": acq.quantize_scores(sig0),
                   "ei_c": acq.quantize_scores(eic0),
                   "y_star": acq.quantize_scores(ystar0)}

    def finish(sel, valid_flag):
        if s.timeout:
            diagnostics["timeout"] = acq.timeout_cap(
                best_feas, sig0[sel], u[sel], beta, t_max, s.timeout_kappa,
                s.timeout_tmax_mult)
        return sel, valid_flag, diagnostics

    if not lookahead:
        # bo / la0 / lynceus-la0: the kernel's in-kernel argmax is the pick
        # (cand0 is `untested` for bo, Gamma for the budget-aware scores).
        return finish(sel0, has0)

    # ---- Lynceus lookahead below the fused root sweep. ----
    gamma0 = cand0
    c_nodes = out[7][0]                                     # [M, K]
    reward = eic0
    cost = mu0
    eye = jnp.eye(m_dim, dtype=bool)
    if valid is not None:
        eye = eye & valid.astype(bool)[None, :]
    y1 = jnp.where(eye[:, None, :], c_nodes[:, :, None], y[None, None, :])
    m1 = jnp.broadcast_to((obs[None, :] | eye)[:, None, :],
                          (m_dim, s.k_gh, m_dim))
    beta1 = beta - c_nodes
    feas1 = c_nodes <= (t_max * u)[:, None]
    bf1 = jnp.minimum(best_feas, jnp.where(feas1, c_nodes, jnp.inf))
    flat = lambda a: a.reshape((m_dim * s.k_gh,) + a.shape[2:])
    cens1 = None
    if cens is not None:
        cens1 = flat(jnp.broadcast_to(cens.astype(bool)[None, None, :],
                                      (m_dim, s.k_gh, m_dim)))
    r1, c1 = _recurse(
        k_path, flat(y1), flat(m1), flat(beta1), flat(bf1), s.la - 1,
        points=points, left=left, thresholds=thresholds, u=u, t_max=t_max,
        floor=floor, s=s, frozen_ctx=None, cens_b=cens1, valid=valid,
        n_chunks=n_chunks)
    reward = reward + acq.no_contract(
        s.gamma * acq.gh_expect(r1.reshape(m_dim, s.k_gh), w))
    cost = cost + acq.gh_expect(c1.reshape(m_dim, s.k_gh), w)
    score = acq.quantize_scores(
        jnp.where(gamma0, reward / jnp.maximum(cost, _EPS), -jnp.inf))
    diagnostics["reward"] = acq.quantize_scores(reward)
    diagnostics["path_cost"] = acq.quantize_scores(cost)
    return finish(jnp.argmax(score), jnp.any(gamma0))


def _select_next_impl(key, y, obs_mask, beta, points, left, thresholds, u,
                      t_max, s: Settings, cens=None, valid=None,
                      n_chunks=None):
    """One NextConfig step. Returns (index, valid, diagnostics).

    y: [M] observed costs (value irrelevant where unobserved);
    obs_mask: [M]; beta: scalar remaining budget; u: [M] unit prices;
    cens: [M] censoring mask (only when ``s.timeout``) — observations whose
    y is a billed lower bound from an aborted run, not a completed cost;
    valid: [M] point-validity mask or None — False marks right-padding
    lanes of a geometry-bucketed space (``space.pad_to``).  Padding can
    never be untested-candidate, incumbent fallback, or Gamma member; with
    valid None the traced program is unchanged from the unpadded selector;
    n_chunks: None, or the bootstrap draw's pass count for every fit of
    the step (``trees.fit_forest``), from :func:`draw_passes`.

    With ``s.timeout`` the diagnostics carry ``"timeout"``: the predictive
    cap τ (runtime units) the driver must abort the selected exploration at.

    ``s.fused_selector`` routes the whole step through the Pallas-fused
    kernel (:func:`_select_next_fused`) when resolved on; the body below is
    the unfused program, traced untouched whenever fusion is off.
    """
    fused = _fused_mode(s)
    if fused is not None:
        return _select_next_fused(key, y, obs_mask, beta, points, left,
                                  thresholds, u, t_max, s, cens, valid,
                                  fused, n_chunks)
    m_dim = y.shape[0]
    floor = _sigma_floor(y, obs_mask, s.sigma_floor_rel)
    k_root, k_path = jax.random.split(key)
    params, assign, preds, mu0, sig0 = _fit_root(
        k_root, y, obs_mask, cens, points, left, thresholds, floor, s,
        n_chunks)

    obs = obs_mask.astype(bool)
    feas_obs = obs & (y <= t_max * u)
    if cens is not None:
        # An aborted run never revealed its runtime: it cannot be the
        # feasible incumbent (its billed y is only a lower bound).
        feas_obs = feas_obs & ~cens.astype(bool)
    best_feas = jnp.min(jnp.where(feas_obs, y, jnp.inf))
    ystar0 = _ystar(best_feas, y, obs_mask, sig0, valid)
    eic0 = acq.ei_constrained(mu0, sig0, ystar0, u, t_max)
    untested = ~obs if valid is None else ~obs & valid.astype(bool)
    gamma0 = untested & acq.budget_ok(mu0, sig0, beta, s.conf)
    # Diagnostics are emitted on the quantize_scores grid: ei_c (and the
    # lookahead reward/path_cost below) pass through erf/exp, and mu/sigma
    # through the fit's leaf-mean reductions, all of which XLA rounds
    # differently per compilation context.  Quantized emission is what lets
    # the fused kernel program replay the unfused diagnostics bit for bit.
    diagnostics = {"mu": acq.quantize_scores(mu0),
                   "sigma": acq.quantize_scores(sig0),
                   "ei_c": acq.quantize_scores(eic0),
                   "y_star": acq.quantize_scores(ystar0)}

    def finish(sel, valid):
        if s.timeout:
            diagnostics["timeout"] = acq.timeout_cap(
                best_feas, sig0[sel], u[sel], beta, t_max, s.timeout_kappa,
                s.timeout_tmax_mult)
        return sel, valid, diagnostics

    if s.policy == "bo":
        # CherryPick-style greedy, cost-unaware: argmax EI_c over untested.
        # All selection argmaxes run on quantized scores (see
        # acq.quantize_scores): near-ties must break identically whether the
        # selector is compiled for 1 run or a whole batched chunk.
        score = acq.quantize_scores(jnp.where(untested, eic0, -jnp.inf))
        return finish(jnp.argmax(score), jnp.any(untested))
    if s.policy == "la0" or (s.policy == "lynceus" and s.la == 0):
        # Cost-normalized greedy (paper's LA = 0 variant).
        score = acq.quantize_scores(
            jnp.where(gamma0, eic0 / jnp.maximum(mu0, _EPS), -jnp.inf))
        return finish(jnp.argmax(score), jnp.any(gamma0))
    if s.policy != "lynceus":
        raise ValueError(f"unknown policy {s.policy!r}")

    # ---- Lynceus proper: in-breadth over all roots, lookahead below. ----
    reward = eic0
    cost = mu0
    xi, w = acq.gauss_hermite(s.k_gh)
    c_nodes = acq.gh_cost_nodes(mu0, sig0, jnp.asarray(xi))     # [M, K]
    eye = jnp.eye(m_dim, dtype=bool)
    if valid is not None:
        # A padding root-state must not speculate an observation at its own
        # (padding) point: its diagonal column is invalid.  Native rows keep
        # their diagonal (always valid), so every surviving state's fit is
        # bit-identical — this only keeps the speculative fit tensors
        # mask-dominated for states whose scores are discarded anyway.
        eye = eye & valid.astype(bool)[None, :]
    y1 = jnp.where(eye[:, None, :], c_nodes[:, :, None], y[None, None, :])
    m1 = jnp.broadcast_to((obs[None, :] | eye)[:, None, :],
                          (m_dim, s.k_gh, m_dim))
    beta1 = beta - c_nodes
    feas1 = c_nodes <= (t_max * u)[:, None]
    bf1 = jnp.minimum(best_feas, jnp.where(feas1, c_nodes, jnp.inf))
    flat = lambda a: a.reshape((m_dim * s.k_gh,) + a.shape[2:])
    frozen_ctx = None
    if s.refit == "frozen":
        # Leaf weights approximated as uniform — over *valid* points only:
        # a padding lane sharing the speculated point's leaf must not add
        # phantom weight to the incremental refit.
        boot_w = (jnp.ones_like(preds) if valid is None
                  else jnp.broadcast_to(valid.astype(preds.dtype)[None, :],
                                        preds.shape))
        frozen_ctx = (assign, preds, boot_w,
                      flat(jnp.broadcast_to(jnp.arange(m_dim)[:, None],
                                            (m_dim, s.k_gh))),
                      flat(c_nodes))
    cens1 = None
    if cens is not None:
        cens1 = flat(jnp.broadcast_to(cens.astype(bool)[None, None, :],
                                      (m_dim, s.k_gh, m_dim)))
    r1, c1 = _recurse(
        k_path, flat(y1), flat(m1), flat(beta1), flat(bf1), s.la - 1,
        points=points, left=left, thresholds=thresholds, u=u, t_max=t_max,
        floor=floor, s=s, frozen_ctx=frozen_ctx, cens_b=cens1,
        valid=valid, n_chunks=n_chunks)
    reward = reward + acq.no_contract(
        s.gamma * acq.gh_expect(r1.reshape(m_dim, s.k_gh), w))
    cost = cost + acq.gh_expect(c1.reshape(m_dim, s.k_gh), w)
    score = acq.quantize_scores(
        jnp.where(gamma0, reward / jnp.maximum(cost, _EPS), -jnp.inf))
    diagnostics["reward"] = acq.quantize_scores(reward)
    diagnostics["path_cost"] = acq.quantize_scores(cost)
    return finish(jnp.argmax(score), jnp.any(gamma0))


select_next = jax.jit(_select_next_impl, static_argnames=("s",))


@functools.partial(jax.jit, static_argnames=("s",))
def select_next_batched(keys, y, obs_mask, beta, points, left, thresholds, u,
                        t_max, s: Settings, cens=None, valid=None,
                        n_chunks=None):
    """NextConfig for R independent slots at once (the batched-harness entry).

    keys: [R, 2] PRNG keys; y: [R, M]; obs_mask: [R, M]; beta: [R];
    cens: [R, M] censoring mask or None (required iff ``s.timeout``).
    Returns ([R] indices, [R] valid flags, batched diagnostics).  Per-slot
    results are bitwise independent of R (each slot is the same elementwise/
    per-slice program), which is what lets the sequential oracle run as the
    R = 1 special case of this very kernel.

    Slot indexing: ``u`` may be ``[M]`` (one job's unit prices, shared by
    every slot — the historical layout, traced identically to the pre-slot
    program) or ``[R, M]`` with ``t_max`` ``[R]`` (each slot carries its own
    job's prices and SLO — the mixed-job work-queue layout, where a slot is
    a *seat* that different jobs' runs occupy over time).  The space tensors
    (``points``/``left``/``thresholds``) are shared when every slot lives
    on one space — or *per-slot* (``points [R, M, F]``, ``left
    [R, M, F, T]``, ``thresholds [R, F, T]``) when the queue mixes jobs of
    different native geometries padded into one bucket; ``valid`` is then
    the per-slot ([R, M]) or shared ([M]) point-validity mask of the
    padding (None for unpadded spaces: the traced program is unchanged).

    ``n_chunks`` (a traced scalar, shared by every slot, or None for the
    full-width draw) is the bootstrap draw's pass count
    (``trees.fit_forest``), from :func:`draw_passes`.  Shared and
    unbatched, it keeps the draw a loop whose trip count is one for all
    slots.
    """
    per_slot_u = jnp.ndim(u) == 2
    per_slot_t = jnp.ndim(t_max) == 1
    if per_slot_u != per_slot_t:
        raise ValueError("per-slot u ([R, M]) requires per-slot t_max ([R]) "
                         "and vice versa")
    per_slot_space = jnp.ndim(points) == 3
    if per_slot_space and valid is None:
        raise ValueError("per-slot space tensors ([R, M, F]) come from "
                         "geometry bucketing and require a validity mask")

    def one(k, y_r, m_r, b_r, c_r, u_r, t_r, p_r, l_r, th_r, v_r):
        with scope("speculate"):
            return _select_next_impl(k, y_r, m_r, b_r, p_r, l_r, th_r,
                                     u_r, t_r, s, c_r, v_r, n_chunks)

    sp_ax = 0 if per_slot_space else None
    return jax.vmap(one, in_axes=(0, 0, 0, 0,
                                  None if cens is None else 0,
                                  0 if per_slot_u else None,
                                  0 if per_slot_t else None,
                                  sp_ax, sp_ax, sp_ax,
                                  None if valid is None or jnp.ndim(valid) == 1
                                  else 0))(
        keys, y, obs_mask, beta, cens, u, t_max, points, left, thresholds,
        valid)


def draw_passes(obs_mask, active, s: Settings):
    """The bootstrap draw's pass count (``trees.draw_chunks``) for one
    :func:`select_next_batched` call: enough for every fit whose pick is
    used.  A fit holds its slot's observed points plus at most ``s.la``
    that speculation adds (each level of :func:`_recurse` adds one), so
    the count covers the largest observed count among the ``active``
    slots plus ``s.la``.  A seat left idle keeps the mask of the run it
    last held and its pick is discarded, so it is left out: it must not
    widen every slot's draw.  obs_mask: [R, M]; active: [R] bool."""
    n_obs = jnp.where(active, obs_mask.sum(axis=1, dtype=jnp.int32), 0)
    return trees.draw_chunks(jnp.max(n_obs) + s.la, obs_mask.shape[1])

def slot_price_rows(job_ids, rid, u, t_max):
    """Resolve each lane slot's price row and SLO for slot-indexed selection.

    The segment-exit plumbing of the lane-compacting episode
    (``core/optimizer.py``) made slots long-lived *seats* that different
    runs — of different jobs — occupy over time, including across segment
    boundaries in the streaming service; this helper is the selection-input
    half of that seat reuse, shared so the one-shot and streaming drivers
    cannot drift.

    ``job_ids`` is None for a single-job episode: every slot shares the one
    ``u [M]`` row and scalar ``t_max`` (returned untouched — the lockstep
    selector geometry).  Otherwise ``job_ids`` ([N] int32) maps *run ids*
    to job indices and ``rid`` ([R], already clamped non-negative) holds
    each slot's current run id: slots gather their run's ``u [R, M]`` row
    and ``t_max [R]`` entry, and the per-slot job index ``jid`` rides along
    for the caller's cost/runtime table gathers.

    Returns ``(u_slots, t_max_slots, jid_or_None)`` ready to feed
    :func:`select_next_batched`.
    """
    if job_ids is None:
        return u, t_max, None
    jid = job_ids[rid]                                       # [R]
    return u[jid], t_max[jid], jid


def space_arrays(space, unit_price: np.ndarray):
    """Device-resident space tensors shared by every selector of a space.

    Accepts a native :class:`~repro.core.space.DiscreteSpace` or a
    :class:`~repro.core.space.PaddedSpace`: for the latter, a native-width
    ``unit_price`` row is right-padded with 1.0 (inert — padding lanes are
    masked out of every decision, the value only has to stay finite).
    """
    points = jnp.asarray(space.points)
    thresholds = jnp.asarray(space.thresholds)
    left = trees.make_left_table(np.asarray(space.points),
                                 np.asarray(space.thresholds))
    u = np.asarray(unit_price, dtype=np.float32)
    native = getattr(space, "native", None)
    if native is not None and u.shape[0] != space.n_points:
        # PaddedSpace accepts exactly two row lengths: already bucket-wide,
        # or native-wide (padded here with inert 1.0).  Anything else is a
        # caller bug that must fail loudly, not be backfilled — and a
        # native DiscreteSpace is never padded at all.
        if u.shape[0] != native.n_points:
            raise ValueError(
                f"unit_price has {u.shape[0]} rows; expected the native "
                f"width {native.n_points} or the bucket width "
                f"{space.n_points}")
        u = np.pad(u, (0, space.n_points - u.shape[0]),
                   constant_values=np.float32(1.0))
    return points, left, thresholds, jnp.asarray(u)


def space_valid(space):
    """The point-validity mask of ``space`` as a device array, or None for
    a native (unpadded) space — the selector's ``valid`` argument."""
    valid = getattr(space, "valid", None)
    return None if valid is None else jnp.asarray(valid)


def selector_cache_size() -> int:
    """Number of compiled entries in the shared batched-selector cache.

    One entry per traced geometry (R, M, F, T, u-rank, settings) of the
    *directly invoked* selector — the oracle path (``make_selector`` /
    ``make_batch_selector``).  Selections inside a jitted episode
    (``core/optimizer.py``) are inlined into the episode program and
    counted by ``optimizer.episode_cache_size`` instead; the geometry-
    bucket compile gates assert on both (scripts/ci.sh, benchmarks).
    """
    return int(select_next_batched._cache_size())


def make_batch_selector(space, unit_price: np.ndarray, t_max: float,
                        s: Settings):
    """Bind a space to the batched selector; returns f(keys, y, mask, beta)
    over [R, ...] lane-stacked state."""
    points, left, thresholds, u = space_arrays(space, unit_price)
    valid = space_valid(space)

    def run(keys, y, obs_mask, beta, cens=None):
        return select_next_batched(
            jnp.asarray(keys), jnp.asarray(y, jnp.float32),
            jnp.asarray(obs_mask), jnp.asarray(beta, jnp.float32),
            points, left, thresholds, u, jnp.float32(t_max), s,
            None if cens is None else jnp.asarray(cens), valid)

    return run


def make_selector(space, unit_price: np.ndarray, t_max: float, s: Settings):
    """Bind a space to the jitted selector; returns f(key, y, mask, beta).

    Routed through :func:`select_next_batched` with a single lane rather than
    the unbatched :func:`select_next` program: XLA vectorizes transcendentals
    (the erf inside ``norm.cdf``) differently for rank-1 vs rank-2 operands,
    which perturbs EI in the last ulp and could flip an argmax.  Running the
    sequential oracle as the R = 1 case of the batched kernel makes
    ``optimize`` and ``run_many_batched`` bit-identical by construction.
    """
    batch = make_batch_selector(space, unit_price, t_max, s)

    def run(key, y, obs_mask, beta, cens=None):
        idx, valid, diag = batch(
            jnp.asarray(key)[None], jnp.asarray(y, jnp.float32)[None],
            jnp.asarray(obs_mask)[None],
            jnp.asarray(beta, jnp.float32)[None],
            None if cens is None else jnp.asarray(cens)[None])
        return idx[0], valid[0], jax.tree.map(lambda a: a[0], diag)

    return run
