"""Bagging ensemble of regression trees, in fixed-shape JAX.

This is Lynceus' surrogate model (paper §3: "a bagging ensemble of [10]
decision trees", fit with Weka in the original).  The re-implementation is
designed around one property: **every array shape is static**, so a single
jit-compiled fit can be ``vmap``-ed over thousands of speculative lookahead
states (the paper instead re-fits Weka models thread-per-path).

Representation
--------------
The training set is always the *entire* configuration space ``X ∈ [M, F]``
plus a per-point weight vector: unobserved points simply carry weight 0.
Bootstrap resampling uses Poisson(1) weights per (tree, point) — the standard
fixed-shape approximation of multinomial bootstrap (Oza & Russell, online
bagging); this is the one place we knowingly deviate from Weka's exact
bootstrap, noted in DESIGN.md §9.

Trees are complete binary trees of static ``depth``; level ``l`` holds
``2**l`` nodes stored in per-level arrays ``feat[l, p] / thr[l, p]`` (padded
to width ``2**(depth-1)``).  Degenerate splits use ``thr = +inf`` (everything
routes left), and empty children inherit their parent's mean, so prediction
is total for any input.

Split search is *exact* on discrete spaces: candidate thresholds are the
midpoints between consecutive unique feature values (``space.thresholds``),
and the variance-reduction score for every (node, feature, threshold) triple
is a dense masked reduction — no sorting, no data-dependent shapes.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.acquisition import no_contract as _no_contract
from repro.core.acquisition import quantize_scores as _quantize_scores
from repro.obs.scopes import scope

__all__ = [
    "ForestParams", "bootstrap_weights", "draw_chunk", "draw_chunks",
    "observed_bootstrap_weights", "make_left_table", "fit_forest",
    "predict_forest", "forest_mu_sigma", "fit_predict_mu_sigma",
]

_EPS = 1e-12


def _pinned_sum0(x: jax.Array, axis: int = 0) -> jax.Array:
    """Sum over ``axis`` (default 0) in a fixed balanced pairwise order.

    ``jnp.sum`` / ``@`` leave the accumulation order (and FMA formation) to
    the backend, which re-decides both per compilation context — the same
    weighted targets can produce last-ulp-different leaf means in the unfused
    selector vs the fused Pallas program.  Zero-padding to a power of two and
    repeatedly adding the two halves pins one association that every context
    lowers identically (and stays vectorization-friendly: each step is a
    single elementwise add of contiguous halves).  The association depends
    only on the folded axis' length, never on which axis it is, so a caller
    may fold the minor axis (keeping a long axis lane-dense on TPU) and get
    the same bits as folding it leading.
    """
    axis = axis % x.ndim
    m = x.shape[axis]
    size = 1
    while size < m:
        size *= 2
    if size != m:
        pad = [(0, 0)] * x.ndim
        pad[axis] = (0, size - m)
        x = jnp.pad(x, pad)
    while x.shape[axis] > 1:
        half = x.shape[axis] // 2
        x = (jax.lax.slice_in_dim(x, 0, half, axis=axis)
             + jax.lax.slice_in_dim(x, half, 2 * half, axis=axis))
    return jnp.squeeze(x, axis)


# Fixed iteration count of the Knuth Poisson sampler below.  P(Poisson(1)
# >= 24) ~ 1e-24: the truncation is unobservable, and a static bound keeps
# the whole draw free of data-dependent control flow.
_BOOT_ITERS = 24
_KNUTH_L = np.float32(np.exp(-1.0))
# Points per pass of :func:`observed_bootstrap_weights`.  A pass draws all
# of its points whether or not they are observed, so a smaller pass wastes
# less past the last observed point and a larger one runs fewer passes.
_DRAW_CHUNK = 64


def _poisson_counts(key: jax.Array, n_trees: int, idx: jax.Array
                    ) -> jax.Array:
    """Poisson(1) counts ``[len(idx), n_trees]`` (int32) of points ``idx``:
    point i's under its own ``fold_in(key, i)`` subkey, from a fixed-
    iteration Knuth sampler (count the uniforms whose running product stays
    above e^-1)."""
    point_keys = jax.vmap(jax.random.fold_in, (None, 0))(key, idx)
    u = jax.vmap(lambda k: jax.random.uniform(k, (_BOOT_ITERS, n_trees)))(
        point_keys)                                        # [n, I, B]
    return (jnp.cumprod(u, axis=1) > _KNUTH_L).sum(axis=1)  # [n, B]


def bootstrap_weights(key: jax.Array, n_trees: int, m: int) -> jax.Array:
    """Poisson(1) bootstrap weights ``[n_trees, m]`` — padding-invariant.

    Weight (b, i) is a pure function of ``(key, b, i)`` and never of ``m``:
    each point derives its own ``fold_in(key, i)`` subkey and runs a
    fixed-iteration Knuth sampler on uniforms drawn under that subkey
    alone.  Right-padding a space to a geometry bucket therefore replays
    the native points' draws bit-for-bit — the property the padded
    selector programs in ``core/lookahead.py`` rely on.  A raw
    ``jax.random.poisson(key, (B, M))`` draw does NOT have it: threefry
    pairs counter blocks by the total element count, so every weight
    shifts whenever M changes.  The same property lets
    :func:`observed_bootstrap_weights` draw only the observed points and
    give the same bits where a fit uses them: how many points the served
    segment draws follows its active lanes' observed count, the bits do
    not (``fit_forest``'s ``n_chunks``).

    The running product is compared directly (exactly-rounded float32
    multiplies against a host constant) rather than through log-space sums,
    so no geometry-sensitive transcendental sits upstream of the integer
    weights.
    """
    return _poisson_counts(key, n_trees, jnp.arange(m)).T.astype(jnp.float32)


def draw_chunk(m: int) -> int:
    """Points per pass of :func:`observed_bootstrap_weights` on an
    ``m``-point space: ``_DRAW_CHUNK``, evened out so that the fewest
    passes covering ``m`` overshoot it by less than one point each."""
    passes = -(-m // _DRAW_CHUNK)
    return -(-m // passes)


def draw_chunks(n_points, m: int) -> jax.Array:
    """Passes of :func:`observed_bootstrap_weights` that cover ``n_points``
    (traced) observed points of an ``m``-point space, at most the passes
    that cover all ``m``."""
    ch = draw_chunk(m)
    return jnp.minimum((n_points + ch - 1) // ch, -(-m // ch))


def observed_bootstrap_weights(key: jax.Array, n_trees: int,
                               obs_mask: jax.Array, n_chunks) -> jax.Array:
    """``bootstrap_weights(key, n_trees, m) * obs_mask``, drawn only at the
    observed points: the same bits, ``+0.0`` at every other point.

    The observed points are taken in ascending order, :func:`draw_chunk`
    at a pass, for ``n_chunks`` passes (:func:`draw_chunks`; a traced
    count, so how many points are drawn follows the data without a
    recompile).  Each point's counts come from :func:`_poisson_counts`,
    the same program :func:`bootstrap_weights` runs, and are scattered
    into a zero buffer; a pass's slots past the last observed point are
    dropped.  Observed points beyond ``n_chunks`` passes are not drawn and
    weigh 0: a caller passes enough passes for every fit whose result it
    uses.
    """
    m = obs_mask.shape[0]
    ch = draw_chunk(m)
    obs = obs_mask.astype(bool)
    rank = jnp.cumsum(obs.astype(jnp.int32))   # 1 + rank at observed points
    points = jnp.arange(m)

    def one_pass(c, boot):
        # hit[j, i]: point i is observed point number c·ch + j.  A slot
        # past the last observed point has no hit: its draw lands nowhere.
        hit = obs[None, :] & (rank[None, :]
                              == c * ch + jnp.arange(1, ch + 1)[:, None])
        idx = jnp.sum(jnp.where(hit, points[None, :], 0), axis=1)
        k = _poisson_counts(key, n_trees, idx).T.astype(jnp.float32)
        # The scatter as a one-hot contraction: each point takes one
        # integer count (exact in bfloat16, so at any matmul precision)
        # and every other point a sum of +0.0 products.
        return boot + k @ hit.astype(jnp.float32)

    return jax.lax.fori_loop(0, n_chunks, one_pass,
                             jnp.zeros((n_trees, m), jnp.float32))


class ForestParams(NamedTuple):
    """Ensemble parameters. B = n_trees, D = depth, W = 2**(D-1), L = 2**D."""

    feat: jax.Array   # [B, D, W] int32 — split feature per (level, node)
    thr: jax.Array    # [B, D, W] f32  — split threshold (+inf = all-left)
    leaf: jax.Array   # [B, L]    f32  — leaf values


def make_left_table(points: np.ndarray, thresholds: np.ndarray) -> jnp.ndarray:
    """Precompute LEFT[m, f, t] = (points[m, f] <= thresholds[f, t]).

    Depends only on the space, never on observations, so it is computed once
    and shared by every tree of every speculative state.
    """
    return jnp.asarray(points[:, :, None] <= thresholds[None, :, :],
                       dtype=jnp.float32)


def _fit_one_tree(y: jax.Array, w: jax.Array, points: jax.Array,
                  left: jax.Array, *, depth: int, min_weight: float):
    """Fit a single tree. y, w: [M]; points: [M, F]; left: [M, F, T]."""
    m, f_dims, t_dims = left.shape
    width = 2 ** (depth - 1) if depth > 0 else 1

    assign = jnp.zeros((m,), dtype=jnp.int32)          # node pos at current lvl
    # Integer-valued weight sums (Poisson counts, well under 2^24) are exact
    # float32 in any order and need no pinning; the w·y sums are not, and
    # leaf means and split gains feed the decision path, so they go through
    # the fenced fixed-order fold (w·y fenced so the fold's first add cannot
    # FMA it).  Each level's per-node sums (sw_n, swy_n) are the previous
    # level's per-child sums: one pinned fold per level serves both, and no
    # backend-ordered w·y contraction reaches a node total (a matvec here
    # split the lane-batched harness from the one-run oracle on the CPU).
    with scope("fit/route"):
        sw_n = jnp.sum(w)[None]
        wy = _no_contract(w * y)
        swy_n = _pinned_sum0(wy)[None]
        val = swy_n / jnp.maximum(sw_n, _EPS)

    feat_lvls, thr_lvls = [], []
    for lvl in range(depth):
        n = 2 ** lvl
        with scope("fit/split_stats"):
            onehot = (assign[:, None]
                      == jnp.arange(n)[None, :]).astype(jnp.float32)
            # Left-branch stats per (node, feature, threshold).  Contract
            # the M dimension as one [n, M] @ [M, F*T] matmul per
            # statistic: this keeps intermediates at O(n·F·T) instead of
            # the naive einsum's O(M·F·T) per node, which is what makes the
            # vmap over thousands of speculative states affordable (and
            # MXU-friendly on TPU).
            left_flat = left.reshape(m, f_dims * t_dims)
            stats = jnp.stack([w, wy], axis=0)               # [2, M]
            # Full f32 products: a TPU's default precision would round w·y
            # to bfloat16 first (~2^-9 relative), far above the noise floor
            # and score grid the split argmax relies on.  A no-op on the
            # CPU.
            node_stats = jnp.matmul(
                onehot.T[None, :, :] * stats[:, None, :], left_flat,
                precision=jax.lax.Precision.HIGHEST)
        with scope("fit/split_choice"):
            sl_w, sl_wy = (node_stats.reshape(2, n, f_dims, t_dims)[i]
                           for i in range(2))
            sr_w = sw_n[:, None, None] - sl_w
            sr_wy = swy_n[:, None, None] - sl_wy
            # Variance-reduction gain in its decomposition form,
            #   SSE_p - SSE_l - SSE_r = (w_l w_r / w_p) (mean_l - mean_r)^2,
            # which is algebraically identical to differencing the three
            # SSEs but free of their catastrophic cancellation: the gain's
            # floating-point wobble is *relative* (~1 ulp), not absolute at
            # the scale of ulp(sum w y^2).  That matters because XLA
            # re-fuses this program differently per batch geometry (1-run
            # oracle vs R-run harness), and the split argmax below must not
            # flip between the two.
            ml = sl_wy / jnp.maximum(sl_w, _EPS)
            mr = sr_wy / jnp.maximum(sr_w, _EPS)
            gain = (sl_w * sr_w / jnp.maximum(sw_n[:, None, None], _EPS)
                    * (ml - mr) ** 2)
            # Noise floor: when a node's observed values are
            # (near-)constant, ml - mr is itself a catastrophic
            # cancellation and every "gain" is pure rounding noise with
            # O(1) relative error — snap those to an exact 0 so the argmax
            # ties deterministically instead of ranking noise.  1e-10 of
            # the node's w·mean^2 scale sits ~4 orders above the (1e-7)^2
            # relative noise and far below any meaningful gain.
            scale = (swy_n * swy_n / jnp.maximum(sw_n, _EPS))[:, None, None]
            gain = jnp.where(gain < scale * 1e-10, 0.0, gain)
            valid = (sl_w >= min_weight) & (sr_w >= min_weight)
            gain = jnp.where(valid, gain, -jnp.inf)
            # Quantized argmax (see acquisition.quantize_scores): collapse
            # geometry-dependent last-ulp wobble into exact ties, which
            # break by lowest index identically in every compilation
            # context.
            flat = _quantize_scores(gain).reshape(n, f_dims * t_dims)
            best = jnp.argmax(flat, axis=1)
            best_gain = jnp.max(flat, axis=1)                # == flat[best]
            f_sel = (best // t_dims).astype(jnp.int32)
            degenerate = ~jnp.isfinite(best_gain)
            f_sel = jnp.where(degenerate, 0, f_sel)

            feat_pad = jnp.zeros((width,), jnp.int32).at[:n].set(f_sel)
            feat_lvls.append(feat_pad)
        with scope("fit/route"):
            # Route points: go right iff NOT left of threshold.  Each
            # node's split column of ``left`` comes out of an exact 0/1
            # one-hot contraction and each point reads its own node's row:
            # a per-point gather into the [M, F, T] table is what a TPU
            # lowers worst (it touches the whole table per point).
            split_oh = (jnp.arange(f_dims * t_dims)[None, :]
                        == best[:, None]).astype(jnp.float32)
            node_left = ((split_oh @ left_flat.T > 0.5)
                         | degenerate[:, None])                # [n, M]
            goes_left = jnp.any(node_left & (onehot.T > 0.5), axis=0)
            assign = 2 * assign + (~goes_left).astype(jnp.int32)
            # Child means with parent fallback.
            n2 = 2 * n
            oh2 = (jnp.arange(n2)[:, None]
                   == assign[None, :]).astype(jnp.float32)
            sw2 = oh2 @ w                                    # [2n]
            # One-hot masking (0/1 products are exact) + pinned fold keeps
            # the child means bit-stable across compilation contexts; the
            # matmul above may stay — its integer sums are exact in any
            # order.  The product is laid out [2n, M] and folded over its
            # minor M axis: a minor axis of 2n <= 16 would be padded to the
            # TPU's 128-lane tile, an 8x-16x blow-up across thousands of
            # speculative fits.
            swy2_ = _pinned_sum0(oh2 * wy[None, :], axis=1)
            parent = jnp.repeat(val, 2)
            val = jnp.where(sw2 > min_weight - 1e-9,
                            swy2_ / jnp.maximum(sw2, _EPS), parent)
            sw_n, swy_n = sw2, swy2_
        # Store threshold as an actual value for standalone prediction. We
        # need the numeric threshold: gather from the shared grid is not
        # available here (left is boolean), so thresholds are passed in
        # alongside; see fit_forest which closes over them.
        thr_lvls.append((best, degenerate, n))

    return assign, val, feat_lvls, thr_lvls


def fit_forest(key: jax.Array, y: jax.Array, obs_mask: jax.Array,
               points: jax.Array, left: jax.Array, thresholds: jax.Array, *,
               n_trees: int, depth: int, min_weight: float = 1.0,
               n_chunks=None) -> tuple[ForestParams, jax.Array]:
    """Fit the bagged forest.

    Args:
      key: PRNG key (drives the Poisson bootstrap).
      y: [M] observed objective (arbitrary value where unobserved).
      obs_mask: [M] bool/float — 1 for observed points.
      points: [M, F] normalized features of the whole space.
      left: [M, F, T] precomputed ``make_left_table``.
      thresholds: [F, T] normalized threshold values (+inf padded).
      n_chunks: None to draw the bootstrap at all M points
        (:func:`bootstrap_weights`), or a traced count of passes that
        covers ``obs_mask``'s points (:func:`draw_chunks`) to draw only
        those (:func:`observed_bootstrap_weights`).  The weights, and so
        the fit, are the same bits either way; only the work follows the
        observed count.  The served segment passes the count its *active*
        lanes need (``lookahead.draw_passes``): an idle seat keeps
        the stale mask of its last run and its pick is discarded, so it
        is left out rather than let widen every lane's draw.
    Returns:
      (ForestParams, per_tree_leaf_assignment [B, M]) — the assignment lets
      tabular callers predict with a single gather.
    """
    m = y.shape[0]
    width = 2 ** (depth - 1) if depth > 0 else 1

    def one(wi):
        assign, leaf_vals, feat_lvls, thr_meta = _fit_one_tree(
            y, wi, points, left, depth=depth, min_weight=min_weight)
        feat = jnp.stack(feat_lvls) if depth > 0 else jnp.zeros((0, width), jnp.int32)
        thr_rows = []
        thr_flat = thresholds.reshape(-1)
        with scope("fit/split_choice"):
            for (best, degenerate, n) in thr_meta:
                # The split's threshold as an exact one-hot select (a max
                # over one value and -infs): a TPU gathers per node very
                # slowly.
                hit = jnp.arange(thr_flat.shape[0])[None, :] == best[:, None]
                tv = jnp.max(jnp.where(hit, thr_flat[None, :], -jnp.inf),
                             axis=1)
                tv = jnp.where(degenerate, jnp.inf, tv)
                thr_rows.append(jnp.full((width,), jnp.inf).at[:n].set(tv))
        thr = jnp.stack(thr_rows) if depth > 0 else jnp.zeros((0, width), jnp.float32)
        return feat, thr, leaf_vals, assign

    with scope("fit"):
        obs = obs_mask.astype(jnp.float32)
        with scope("fit/bootstrap"):
            if n_chunks is None:
                boot = bootstrap_weights(key, n_trees, m)
            else:
                boot = observed_bootstrap_weights(key, n_trees, obs_mask,
                                                  n_chunks)
            w = boot * obs[None, :]
            # Guard: a tree whose bootstrap came up all-zero falls back to
            # plain obs.
            dead = jnp.sum(w, axis=1, keepdims=True) < min_weight
            w = jnp.where(dead, obs[None, :], w)
        feat, thr, leaf, assign = jax.vmap(one)(w)
    return ForestParams(feat, thr, leaf), assign


def predict_forest(params: ForestParams, xq: jax.Array) -> jax.Array:
    """Per-tree predictions for arbitrary query points. xq: [Q, F] -> [B, Q]."""
    q = xq.shape[0]

    def one(feat, thr, leaf):
        pos = jnp.zeros((q,), jnp.int32)
        depth = feat.shape[0]
        for lvl in range(depth):
            f = feat[lvl][pos]
            t = thr[lvl][pos]
            x = jnp.take_along_axis(xq, f[:, None], axis=1)[:, 0]
            pos = 2 * pos + (x > t).astype(jnp.int32)
        return leaf[pos]

    return jax.vmap(one)(params.feat, params.thr, params.leaf)


def forest_mu_sigma(preds: jax.Array, sigma_floor) -> tuple[jax.Array, jax.Array]:
    """Ensemble mean / spread from per-tree predictions [B, Q].

    The tree axis is reduced with an explicitly left-associated add chain
    rather than ``jnp.mean``/``jnp.std``: XLA's ``reduce`` leaves the
    accumulation order unspecified, so the same forest could yield
    last-ulp-different mu/sigma depending on what the reduction fuses
    with.  Each squared deviation is fenced (``acquisition.no_contract``)
    so the backend cannot contract ``acc + d*d`` into an FMA in one
    compile context but not another.  Pinning both keeps the unfused
    selector and the fused Pallas kernel (kernels/select_step)
    bit-identical.
    """
    n = preds.shape[0]
    acc = preds[0]
    for i in range(1, n):
        acc = acc + preds[i]
    mu = acc / n

    def _sq(d):
        return _no_contract(d * d)

    acc2 = _sq(preds[0] - mu)
    for i in range(1, n):
        acc2 = acc2 + _sq(preds[i] - mu)
    sigma = jnp.sqrt(acc2 / n)
    return mu, jnp.maximum(sigma, sigma_floor)


@functools.partial(jax.jit, static_argnames=("n_trees", "depth"))
def fit_predict_mu_sigma(key, y, obs_mask, points, left, thresholds,
                         sigma_floor, *, n_trees: int, depth: int):
    """Fit on (y, obs_mask) and predict mu/sigma over the whole space [M].

    The tabular fast path: training points == query points, so prediction is
    the leaf-assignment gather computed during fitting (no re-traversal).
    """
    params, assign = fit_forest(key, y, obs_mask, points, left, thresholds,
                                n_trees=n_trees, depth=depth)
    preds = jnp.take_along_axis(params.leaf, assign, axis=1)   # [B, M]
    mu, sigma = forest_mu_sigma(preds, sigma_floor)
    return mu, sigma
