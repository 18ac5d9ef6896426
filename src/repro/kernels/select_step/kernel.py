"""Fused selector-step Pallas kernel — one pass per speculative-state block.

The Lynceus selector's per-step hot path is, for every speculative state s
of the lookahead frontier: traverse the bagged forest fit for s over all M
candidate configs (``tree_predict``), turn the posterior into constrained
EI + budget filter + Gauss-Hermite cost nodes (``gh_ei``), and argmax the
quantized masked scores.  Unfused, each stage round-trips its [S, M]
intermediates through HBM.  This kernel keeps one state block's whole
[bs, M] sweep in VMEM: ensemble descent by exact selects on [bs, M] tiles,
the *exact* acquisition expressions from ``repro.core.acquisition``
(called verbatim, so the primitive sequence is the unfused selector's),
and the in-kernel argmax over ``quantize_scores``-rounded integers.

TPU layout: every operand is a rank-2 block whose minor axis is either
long and lane-dense (M, or the flattened per-state forest params) or the
whole array axis; per-state scalars travel as one [S, 4] block and
per-state results as [S, 1] columns.  Rank-1 blocks off the 128-lane tile,
batched SMEM scalars (the R-lane selector ``vmap``s this call) and 3-D
in-kernel reshapes do not lower on a TPU.

Bit-exactness contract (pinned by tests/test_kernels.py): with the forest
params of ``trees.fit_forest``, the in-kernel traversal reproduces the
fit-side leaf ``assign`` exactly — ``right = x > thr`` with the stored
threshold value is the complement of the fit's ``left`` table routing, and
degenerate splits store ``thr = +inf`` (everything left) in both.  Selects
move single finite values (exact), and mean/std/erf are evaluated by the
same jnp calls the unfused program traces.

Geometry-bucket padding lanes arrive via ``valid`` and are masked out of
the untested set and the incumbent fallback before their ``-inf`` scores
enter the quantized argmax — the PR 5 mask semantics, consumed natively.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import acquisition as acq
from repro.core import trees

__all__ = ["select_step_call"]

# Same ratio-score guard as lookahead._EPS (the la0/lynceus cost divisor).
_EPS = 1e-9


def _kernel(*refs, n_trees, depth, width, n_leaves, n_feat, m_dim, conf,
            cens_rel, score_mode, use_budget, emit_full, want_nodes,
            has_cens, has_valid):
    it = iter(refs)
    scal_ref = next(it)
    feat_ref = next(it)
    thr_ref = next(it)
    leaf_ref = next(it)
    y_ref = next(it)
    obs_ref = next(it)
    cens_ref = next(it) if has_cens else None
    points_ref = next(it)
    u_ref = next(it)
    valid_ref = next(it) if has_valid else None
    xi_ref = next(it) if want_nodes else None
    outs = list(it)

    beta = scal_ref[:, 0]                                # [bs]
    bf = scal_ref[:, 1]                                  # [bs]
    t_max = scal_ref[:, 2:3]                             # [bs, 1]
    floor = scal_ref[:, 3:4]                             # [bs, 1]
    y = y_ref[...]                                       # [bs, M]
    obs = obs_ref[...]                                   # [bs, M] bool
    u = u_ref[0]                                         # [M]
    bs = y.shape[0]
    # Feature rows of the space, lane-dense over M: x_rows[f] is [1, M].
    x_rows = [points_ref[f:f + 1, :] for f in range(n_feat)]

    # Ensemble descent, batched over the state block, as exact selects on
    # [bs, M] tiles: per (tree, level) the node position picks its split's
    # feature and threshold (a where-chain over the 2**lvl reachable
    # nodes), the feature picks the point's coordinate, and ``right = val >
    # thr`` replays the fit-side left-table routing exactly (+inf threshold
    # => degenerate => left).  Selects move values without arithmetic, so
    # the descent is bit-exact on every backend.
    def pick(idx, col, n):
        out = col(0)
        for j in range(1, n):
            out = jnp.where(idx == j, col(j), out)
        return out

    preds = []
    for b in range(n_trees):
        pos = jnp.zeros((bs, m_dim), jnp.int32)
        for lvl in range(depth):
            base = (b * depth + lvl) * width
            node_col = lambda ref: lambda j: ref[:, base + j:base + j + 1]
            f_at = pick(pos, node_col(feat_ref), 2 ** lvl)   # [bs, M]
            th = pick(pos, node_col(thr_ref), 2 ** lvl)
            val = pick(f_at, lambda f: x_rows[f], n_feat)
            right = val > th
            pos = 2 * pos + right.astype(jnp.int32)
        base = b * n_leaves
        preds.append(pick(pos, lambda j: leaf_ref[:, base + j:base + j + 1],
                          n_leaves))                     # [bs, M]
    preds = jnp.stack(preds)                             # [B, bs, M]

    mu, sigma = trees.forest_mu_sigma(preds, floor)
    if has_cens:
        mu, sigma = acq.censored_adjust(mu, sigma, y, cens_ref[...],
                                        cens_rel)
    valid = valid_ref[0] if has_valid else None
    ystar = acq.incumbent_fallback(bf, y, obs, sigma, valid)
    eic = acq.ei_constrained(mu, sigma, ystar[:, None], u[None, :], t_max)
    untested = ~obs
    if has_valid:
        untested = untested & valid
    cand = untested
    if use_budget:
        cand = cand & acq.budget_ok(mu, sigma, beta[:, None], conf)
    raw = eic if score_mode == "eic" else eic / jnp.maximum(mu, _EPS)
    score = acq.quantize_scores(jnp.where(cand, raw, -jnp.inf))
    # Quantized scores tie exactly by design, and Mosaic's argmax breaks
    # ties differently from XLA's lowest-index rule (it disagreed on 49 of
    # 256 states on a v5e).  So the pick is read directly: the lowest index
    # among the top scores.
    iota = jax.lax.broadcasted_iota(jnp.int32, score.shape, 1)
    top = jnp.max(score, axis=1, keepdims=True)
    sel = jnp.min(jnp.where(score == top, iota, m_dim), axis=1)
    has_cand = jnp.any(cand, axis=1)

    # Per-state outputs are [bs, 1] columns (a rank-1 block would have to
    # be a multiple of the 128-lane tile on TPU); ``col`` lifts [bs] -> [bs, 1].
    col = lambda a: a[:, None]
    if emit_full:
        (mu_ref, sig_ref, eic_ref, ystar_ref, cand_ref, sel_ref,
         has_ref) = outs[:7]
        mu_ref[...] = mu
        sig_ref[...] = sigma
        eic_ref[...] = eic
        ystar_ref[...] = col(ystar)
        cand_ref[...] = cand
        sel_ref[...] = col(sel)
        has_ref[...] = col(has_cand)
        if want_nodes:
            outs[7][...] = acq.gh_cost_nodes(mu, sigma, xi_ref[...])
        return
    sel_oh = (jax.lax.broadcasted_iota(jnp.int32, (bs, m_dim), 1)
              == sel[:, None])
    take = lambda a: jnp.sum(jnp.where(sel_oh, a, 0.0), axis=1)
    eic_sel = take(eic)
    mu_sel = take(mu)
    sig_sel = take(sigma)
    sel_ref, has_ref, eics_ref, mus_ref, sigs_ref = outs[:5]
    sel_ref[...] = col(sel)
    has_ref[...] = col(has_cand)
    eics_ref[...] = col(eic_sel)
    mus_ref[...] = col(mu_sel)
    sigs_ref[...] = col(sig_sel)
    if want_nodes:
        outs[5][...] = acq.gh_cost_nodes(mu_sel, sig_sel, xi_ref[...])


def select_step_call(feat, thr, leaf, y, obs, beta, bf, points, u, t_max,
                     floor, xi=None, cens=None, valid=None, *, conf=0.99,
                     cens_rel=0.5, score_mode="eic", use_budget=True,
                     emit_full=False, want_nodes=False, bs=32,
                     interpret=False):
    """Fused selector step over S speculative states.

    feat/thr: [S, B, D, W]; leaf: [S, B, L]; y/obs[/cens]: [S, M];
    beta/bf: [S]; points: [M, F]; u[/valid]: [M]; xi: [K] (required iff
    ``want_nodes``); t_max/floor: scalars.  The grid tiles the state axis
    in blocks of ``bs``; the whole [bs, M] candidate sweep of a block stays
    in VMEM from ensemble descent to the quantized argmax.

    Returns (all [:S] along the state axis):
      ``emit_full=False`` — (sel i32, has_cand bool, eic_sel, mu_sel,
      sig_sel[, nodes [S, K]]): each state's own argmax pick (the lookahead
      recursion contract of ``lookahead._recurse``).
      ``emit_full=True`` — (mu, sigma, eic [S, M], ystar [S], cand [S, M]
      bool, sel [S], has_cand [S][, nodes [S, M, K]]): the root contract,
      where diagnostics and the policy layer need the full sweep.
    """
    s_dim, n_trees, depth, width = feat.shape
    n_leaves = leaf.shape[-1]
    m_dim, n_feat = points.shape
    if want_nodes and xi is None:
        raise ValueError("want_nodes=True requires xi")
    bs = min(bs, s_dim)
    pad = (-s_dim) % bs
    pad_s = lambda a: jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
    # Per-state scalars as one [S, 4] block (beta, y*, t_max, floor): a
    # vmapped call batches every operand, and the TPU lowering takes no
    # batched SMEM scalars nor rank-1 blocks off the 128-lane tile.
    scal = jnp.stack(jnp.broadcast_arrays(
        *(jnp.asarray(a, jnp.float32) for a in (beta, bf, t_max, floor))),
        axis=1)
    if pad:
        feat, thr, leaf, y, obs, scal = map(
            pad_s, (feat, thr, leaf, y, obs, scal))
        if cens is not None:
            cens = pad_s(cens)
    sp = s_dim + pad

    has_cens = cens is not None
    has_valid = valid is not None

    # Every operand is rank-2 and lane-dense over its minor axis where it
    # has a long one: per-state forest params flattened to [S, B*D*W] /
    # [S, B*L], the space transposed to [F, M], shared rows as [1, M] /
    # [1, K].
    row = lambda a: a.reshape(1, -1)
    operands = [scal, feat.astype(jnp.int32).reshape(sp, -1),
                thr.astype(jnp.float32).reshape(sp, -1),
                leaf.astype(jnp.float32).reshape(sp, -1),
                y.astype(jnp.float32), obs.astype(bool)]
    blk = lambda *tail: pl.BlockSpec((bs,) + tail,
                                     lambda i: (i,) + (0,) * len(tail))
    whole = lambda *shape: pl.BlockSpec(shape, lambda i: (0,) * len(shape))
    node_w = n_trees * depth * width
    in_specs = [blk(4), blk(node_w), blk(node_w), blk(n_trees * n_leaves),
                blk(m_dim), blk(m_dim)]
    if has_cens:
        operands.append(cens.astype(bool))
        in_specs.append(blk(m_dim))
    operands += [points.astype(jnp.float32).T, row(u.astype(jnp.float32))]
    in_specs += [whole(n_feat, m_dim), whole(1, m_dim)]
    if has_valid:
        operands.append(row(valid.astype(bool)))
        in_specs.append(whole(1, m_dim))
    if want_nodes:
        k_gh = xi.shape[0]
        operands.append(row(xi.astype(jnp.float32)))
        in_specs.append(whole(1, k_gh))

    f32, i32, b1 = jnp.float32, jnp.int32, jnp.bool_
    if emit_full:
        shapes = [((m_dim,), f32), ((m_dim,), f32), ((m_dim,), f32),
                  ((1,), f32), ((m_dim,), b1), ((1,), i32), ((1,), b1)]
        if want_nodes:
            shapes.append(((m_dim, k_gh), f32))
    else:
        shapes = [((1,), i32), ((1,), b1), ((1,), f32), ((1,), f32),
                  ((1,), f32)]
        if want_nodes:
            shapes.append(((k_gh,), f32))
    out_specs = [blk(*tail) for tail, _ in shapes]
    out_shape = [jax.ShapeDtypeStruct((sp,) + tail, dt)
                 for tail, dt in shapes]

    kernel = functools.partial(
        _kernel, n_trees=n_trees, depth=depth, width=width,
        n_leaves=n_leaves, n_feat=n_feat, m_dim=m_dim, conf=conf,
        cens_rel=cens_rel, score_mode=score_mode, use_budget=use_budget,
        emit_full=emit_full, want_nodes=want_nodes, has_cens=has_cens,
        has_valid=has_valid)
    outs = pl.pallas_call(
        kernel,
        grid=(sp // bs,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(*operands)
    # Drop the state padding and the per-state columns' unit lane axis.
    return tuple(o[:s_dim, 0] if tail == (1,) else o[:s_dim]
                 for o, (tail, _) in zip(outs, shapes))
